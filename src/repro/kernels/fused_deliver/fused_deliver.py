"""Pallas TPU kernel: fused stage-1 scatter + stage-2 CAM match.

The separate-stage pipeline writes the tag-activity matrix ``A[B, nc, K]``
to HBM after stage 1 and reads it straight back for stage 2. This kernel
fuses the two: for each (batch, cluster) grid step the activity *row* is
built in a VMEM scratch buffer directly from the queued events and consumed
by the CAM match before the grid moves on — ``A`` never exists in HBM.
That is the TPU transcription of the chip's datapath, where the R1 router
feeds the core's broadcast driver directly (no DRAM between fabric and CAM).

Inputs are the AER queue's SRAM entries, pre-gathered and flattened to
``ev_flat[B, QE]`` (``dest * K + tag`` per queued (event, SRAM-entry) pair,
``-1`` = empty) with matching weights ``ev_w[B, QE]``. The lossless queue
has a slot per neuron (QE = N*E), but its occupied entries form a prefix:
a scalar-prefetched ``n_entries[B]`` gives each batch row's occupied count,
and stage 1 scans only ``cdiv(n_entries[b], ev_chunk)`` chunks of it, so its
work follows the event count, not the network size. The chunks past the
prefix hold only empty entries (weight 0) and would add exactly 0.

Grid ``(B, n_clusters, neuron-tile)``; TPU grids execute sequentially with
the last dimension minor, so the row scratch built at tile ``j == 0`` of a
(batch, cluster) pair persists for that pair's remaining neuron tiles.

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
    check_lane_aligned,
    event_chunk,
)


def _fused_deliver_kernel(
    n_entries_ref,  # SMEM [B] int32 — occupied prefix of each row's entries
    ev_flat_ref,  # [1, 1, QE] int32 — flat (dest*K + tag) per queued entry, -1 empty
    ev_w_ref,  # [1, 1, QE] — event weight per entry (0 for empty)
    ext_ref,  # [1, 1, K] — external input activity for this (batch, cluster)
    tag_ref,  # [1, Cb, S] — CAM tags of the neuron tile (batch-shared)
    syn_ref,  # [1, Cb, S] — synapse types of the neuron tile
    out_ref,  # [1, 1, Cb, 4] — per-type synaptic drive
    act_ref,  # VMEM scratch [1, K] — this (batch, cluster)'s activity row
    *,
    k_tags: int,
    ev_chunk: int,
):
    bi = pl.program_id(0)
    c = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _build_activity_row():
        # stage 1 for (b, c): accumulate this cluster's K-row from the queue.
        base = c * k_tags
        full = ev_flat_ref.shape[2] // ev_chunk
        n_chunks = jnp.minimum((n_entries_ref[bi] + ev_chunk - 1) // ev_chunk, full)

        def chunk_body(i, acc):
            at = pl.ds(pl.multiple_of(i * ev_chunk, ev_chunk), ev_chunk)
            f = ev_flat_ref[0, 0, at]  # [ev_chunk]
            w = ev_w_ref[0, 0, at]
            kk = jax.lax.broadcasted_iota(jnp.int32, (ev_chunk, k_tags), 1) + base
            match = (f[:, None] == kk).astype(acc.dtype)  # [ev_chunk, K]
            return acc + jax.lax.dot_general(
                w.reshape(1, ev_chunk),
                match,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        row = jax.lax.fori_loop(
            0, n_chunks, chunk_body, ext_ref[0].astype(jnp.float32)
        )
        act_ref[...] = row.astype(act_ref.dtype)

    # stage 2: CAM match of the VMEM-resident row against this neuron tile.
    drive = cam_drive(act_ref[0, :], tag_ref[0], syn_ref[0])
    out_ref[0, 0] = drive.astype(out_ref.dtype)


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
    block_c = min(block_c, cluster_size)
    assert cluster_size % block_c == 0, (cluster_size, block_c)
    check_lane_aligned(k, interpret)
    dtype = ev_w.dtype

    # free [B, 1, QE] / [B, 1, nc * K] views: every block's last two dims are
    # then full or (1, K) — the TPU's (8, 128) tiling rule (cam_match.py)
    ev_flat2 = ev_flat.reshape(b, 1, -1)
    ev_w2 = ev_w.reshape(b, 1, -1)
    qe = ev_flat2.shape[2]
    # chunk the stage-1 compare plane to a fixed VMEM budget; pad QE up so
    # the chunks tile it exactly (padding entries are -1/0 = no-ops).
    ev_chunk = event_chunk(qe, k)
    qe_pad = -(-qe // ev_chunk) * ev_chunk
    if qe_pad != qe:
        pad = ((0, 0), (0, 0), (0, qe_pad - qe))
        ev_flat2 = jnp.pad(ev_flat2, pad, constant_values=-1)
        ev_w2 = jnp.pad(ev_w2, pad)
    if n_entries is None:
        n_entries = jnp.full((b,), qe, jnp.int32)
    n_entries = jnp.asarray(n_entries, jnp.int32).reshape(b)

    ext3 = jnp.broadcast_to(
        external_activity, (*batch_shape, n_clusters, k)
    ).reshape(b, 1, n_clusters * k).astype(dtype)
    tags3 = cam_tag.reshape(n_clusters, cluster_size, s)
    syn3 = cam_syn.reshape(n_clusters, cluster_size, s)
    grid = (b, n_clusters, cluster_size // block_c)

    out = pl.pallas_call(
        functools.partial(_fused_deliver_kernel, k_tags=k, ev_chunk=ev_chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, qe_pad), lambda bi, i, j, ne: (bi, 0, 0)),
                pl.BlockSpec((1, 1, qe_pad), lambda bi, i, j, ne: (bi, 0, 0)),
                pl.BlockSpec((1, 1, k), lambda bi, i, j, ne: (bi, 0, i)),
                pl.BlockSpec((1, block_c, s), lambda bi, i, j, ne: (i, j, 0)),
                pl.BlockSpec((1, block_c, s), lambda bi, i, j, ne: (i, j, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, block_c, N_SYN_TYPES), lambda bi, i, j, ne: (bi, i, j, 0)
            ),
            scratch_shapes=[pltpu.VMEM((1, k), dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_clusters, cluster_size, N_SYN_TYPES), dtype
        ),
        interpret=interpret,
        name="fused_deliver",
    )(n_entries, ev_flat2, ev_w2, ext3, tags3, syn3)
    return out.reshape(*batch_shape, n, N_SYN_TYPES)
