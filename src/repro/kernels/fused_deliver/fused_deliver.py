"""Pallas TPU kernel: fused stage-1 scatter + stage-2 CAM match.

The separate-stage pipeline writes the tag-activity matrix ``A[B, nc, K]``
to HBM after stage 1 and reads it straight back for stage 2. This kernel
fuses the two: for each (slot block, cluster) the block's activity *rows*
are built in a VMEM scratch buffer directly from the queued events and
consumed by the CAM match before the grid moves on — ``A`` never exists in
HBM. That is the TPU transcription of the chip's datapath, where the R1
router feeds the core's broadcast driver directly (no DRAM between fabric
and CAM).

Inputs are the AER queue's SRAM entries, pre-gathered and flattened to
``ev_flat[B, QE]`` (``dest * K + tag`` per queued (event, SRAM-entry) pair,
``-1`` = empty) with matching weights ``ev_w[B, QE]``. The lossless queue
has a slot per neuron (QE = N*E), but its occupied entries form a prefix:
a scalar-prefetched ``n_entries[B]`` gives each slot's occupied count, and
stage 1 scans only ``cdiv(n_entries[b], ev_chunk)`` chunks of it, so its
work follows the event count, not the network size. The chunks past the
prefix hold only empty entries (weight 0) and would add exactly 0.

Grid ``(slot block, n_clusters, neuron-tile)``; TPU grids execute
sequentially with the last dimension minor, so the ``[Bb, K]`` row scratch
built at tile ``j == 0`` of a (block, cluster) pair persists for that
pair's remaining neuron tiles. Stage 1 stays per slot: each slot's entries
differ, so its compare planes do too. Stage 2 (``cam_match.cam_drive``)
builds each tile's compare plane once per block and contracts it with all
``Bb`` rows. ``Bb`` is the largest block whose entry rows fit the VMEM
budget (``cam_match.slot_block``); they change only with the block, so
they are held in one buffer.

Stage 1 in-kernel uses the same MXU idiom as the CAM compare: a one-hot
compare plane ``(ev_flat == c*K + iota(K))`` contracted against the weights
— a scatter-free scatter-add. The plane is built over event chunks of
``ev_chunk`` so VMEM holds at most ``ev_chunk * K`` floats at once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cam_match.cam_match import (
    N_SYN_TYPES,
    cam_drive,
    cam_rows,
    check_lane_aligned,
    compiler_params,
    drive_from_tiles,
    event_chunk,
    pad_slots,
    slot_block,
    stage2_bytes,
    stage2_slot_bytes,
    tile_bytes,
)


def _fused_deliver_kernel(
    n_entries_ref,  # SMEM [B_pad] int32 — occupied prefix of each slot's entries
    ev_flat_ref,  # [Bb, QE] int32 — flat (dest*K + tag) per queued entry, -1 empty
    ev_w_ref,  # [Bb, QE] — event weight per entry (0 for empty)
    ext_ref,  # [Bb, K] — external input activity of the block at this cluster
    tag_ref,  # [1, 1, Cb*S] — CAM tags of the neuron tile (batch-shared)
    syn_ref,  # [1, 1, Cb*S] — synapse types of the neuron tile
    out_ref,  # [1, Bb, Cb*4] — per-type synaptic drive
    act_ref,  # VMEM scratch [Bb, K] — the block's activity rows at this cluster
    *,
    k_tags: int,
    ev_chunk: int,
    words: int,
):
    blk = pl.program_id(0)
    c = pl.program_id(1)
    j = pl.program_id(2)
    bb = act_ref.shape[0]

    @pl.when(j == 0)
    def _build_activity_rows():
        # stage 1 for (block, c): each slot accumulates its cluster-c K-row
        # from its own queue prefix.
        base = c * k_tags
        full = ev_flat_ref.shape[1] // ev_chunk

        def slot_body(b, carry):
            n_chunks = jnp.minimum(
                (n_entries_ref[blk * bb + b] + ev_chunk - 1) // ev_chunk, full
            )
            row_at = pl.ds(b, 1)

            def chunk_body(i, acc):
                at = pl.ds(pl.multiple_of(i * ev_chunk, ev_chunk), ev_chunk)
                f = ev_flat_ref[row_at, at]  # [1, ev_chunk]
                w = ev_w_ref[row_at, at]
                kk = jax.lax.broadcasted_iota(jnp.int32, (ev_chunk, k_tags), 1) + base
                match = (f.reshape(ev_chunk, 1) == kk).astype(acc.dtype)  # [ev_chunk, K]
                return acc + jax.lax.dot_general(
                    w,
                    match,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

            row = jax.lax.fori_loop(
                0, n_chunks, chunk_body, ext_ref[row_at, :].astype(jnp.float32)
            )
            act_ref[row_at, :] = row.astype(act_ref.dtype)
            return carry

        jax.lax.fori_loop(0, bb, slot_body, 0)

    # stage 2: CAM match of the block's VMEM-resident rows against this tile.
    drive = cam_drive(act_ref[...], tag_ref[0], syn_ref[0], words)
    out_ref[0] = drive.astype(out_ref.dtype)


def fused_plan(b: int, k_tags: int, words: int, qe: int, block_c: int = 16):
    """``(Bb, B_pad, ev_chunk, QE_pad, VMEM estimate)`` of
    :func:`fused_deliver_pallas` for ``b`` slots of ``qe`` queue entries and
    neuron tiles of ``block_c``."""
    ev_chunk = event_chunk(qe, k_tags)
    qe_pad = -(-qe // ev_chunk) * ev_chunk
    fixed = stage2_bytes(k_tags, words, block_c) + 3 * tile_bytes(ev_chunk, k_tags)
    # entry rows single-buffered, external input double-buffered, plus
    # stage 2's share
    per_slot = 2 * qe_pad * 4 + 2 * k_tags * 4 + stage2_slot_bytes(
        k_tags, words, block_c
    )
    bb = slot_block(b, per_slot, fixed)
    return bb, -(-b // bb) * bb, ev_chunk, qe_pad, fixed + bb * per_slot


@functools.partial(
    jax.jit, static_argnames=("cluster_size", "k_tags", "block_c", "interpret")
)
def fused_deliver_pallas(
    ev_flat: jax.Array,  # [..., QE] int32, -1 = empty entry
    ev_w: jax.Array,  # [..., QE] event weights (0 for empty)
    cam_tag: jax.Array,  # [N, S]
    cam_syn: jax.Array,  # [N, S]
    external_activity: jax.Array,  # [..., n_clusters, K]
    cluster_size: int,
    k_tags: int,
    n_entries: jax.Array | None = None,  # [...] int32 occupied prefix; None = all
    block_c: int = 16,
    interpret: bool = True,
) -> jax.Array:  # [..., N, N_SYN_TYPES]
    n, s = cam_tag.shape
    n_clusters = n // cluster_size
    k = k_tags
    batch_shape = ev_flat.shape[:-1]
    b = math.prod(batch_shape)
    qe = ev_flat.shape[-1]
    block_c = min(block_c, cluster_size)
    assert cluster_size % block_c == 0, (cluster_size, block_c)
    check_lane_aligned(k, interpret)
    dtype = ev_w.dtype
    tiles = cluster_size // block_c
    bb, b_pad, ev_chunk, qe_pad, vmem = fused_plan(b, k, s, qe, block_c)

    # chunk the stage-1 compare plane to a fixed VMEM budget; pad QE up so
    # the chunks tile it exactly, and the slots to whole blocks (padding
    # entries are -1/0 = no-ops, padded slots hold no entries)
    ev_flat2 = ev_flat.reshape(b, qe)
    ev_w2 = ev_w.reshape(b, qe)
    if qe_pad != qe:
        ev_flat2 = jnp.pad(ev_flat2, ((0, 0), (0, qe_pad - qe)), constant_values=-1)
        ev_w2 = jnp.pad(ev_w2, ((0, 0), (0, qe_pad - qe)))
    if n_entries is None:
        n_entries = jnp.full((b,), qe, jnp.int32)
    n_entries = pad_slots(jnp.asarray(n_entries, jnp.int32).reshape(b), b_pad)

    ext2 = jnp.broadcast_to(
        external_activity, (*batch_shape, n_clusters, k)
    ).reshape(b, n_clusters * k).astype(dtype)
    grid = (b_pad // bb, n_clusters, tiles)
    tile_spec = pl.BlockSpec(
        (1, 1, block_c * s), lambda bi, i, j, ne: (i * tiles + j, 0, 0)
    )
    # the entry rows change only with the slot block: one buffer, so the
    # block can be twice as wide (a block's first step waits for its rows)
    entry_spec = pl.BlockSpec(
        (bb, qe_pad), lambda bi, i, j, ne: (bi, 0), pipeline_mode=pl.Buffered(1)
    )

    out = pl.pallas_call(
        functools.partial(_fused_deliver_kernel, k_tags=k, ev_chunk=ev_chunk, words=s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                entry_spec,
                entry_spec,
                pl.BlockSpec((bb, k), lambda bi, i, j, ne: (bi, i)),
                tile_spec,
                tile_spec,
            ],
            out_specs=pl.BlockSpec(
                (1, bb, block_c * N_SYN_TYPES),
                lambda bi, i, j, ne: (i * tiles + j, bi, 0),
            ),
            scratch_shapes=[pltpu.VMEM((bb, k), dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n // block_c, b_pad, block_c * N_SYN_TYPES), dtype
        ),
        compiler_params=compiler_params(vmem),
        interpret=interpret,
        name="fused_deliver",
    )(
        n_entries,
        pad_slots(ev_flat2, b_pad, -1),
        pad_slots(ev_w2, b_pad),
        pad_slots(ext2, b_pad),
        cam_rows(cam_tag, block_c),
        cam_rows(cam_syn, block_c),
    )
    return drive_from_tiles(out, b).reshape(*batch_shape, n, N_SYN_TYPES)
