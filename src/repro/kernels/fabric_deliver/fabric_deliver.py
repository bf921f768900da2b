"""Pallas TPU kernel: fused time-wheel fabric delivery (DESIGN.md §14).

The jnp fabric path updates the carried ring buffer in HBM, reads the
arrival slot back, and hands it to the stage-2 CAM match — the arrival
activity row makes an HBM round-trip between the ring update and the match.
This kernel fuses the three per (slot block, cluster) grid step:

  1. the block's ring *columns* ``ring[blk, :, c, :]`` ([Bb, max_delay + 1,
     K]) are pulled into VMEM and the step's surviving events are
     scatter-added into them via the one-hot compare-plane matmul idiom of
     kernels/fused_deliver — one plane per (event chunk, delay slot), events
     pre-addressed as flat ring targets ``slot * (nc * K) + dst * K + tag``
     (slot already cursor-rotated). The targets are shared by every slot
     and only the weights differ, so each plane is built once per block and
     contracted with the block's ``[Bb, ev_chunk]`` weights;
  2. the cursor rows (slot-0 arrivals) are captured — carried events + this
     step's zero-delay events + external input — into a VMEM scratch that
     never round-trips HBM, and zeroed in the outgoing ring columns
     (read-then-clear, the time-wheel pop);
  3. the neuron tiles of cluster ``c`` CAM-match the block's VMEM-resident
     rows (``cam_match.cam_drive``, shared with kernels/fused_deliver).

Arbitration (per-directed-link FIFOs) and queue admission happen *outside*
in O(events) masked prefix sums (kernels/fabric_deliver/ops.py) — they are
cheap, shared with the jnp fast path, and produce the masked event weights
this kernel consumes (weight 0 = not delivered).

Grid ``(slot block, n_clusters, neuron-tile)``; TPU grids execute
sequentially with the last dimension minor, so the scratch rows built at
tile ``j == 0`` persist for the (block, cluster) pair's remaining neuron
tiles, and the ring columns written once at ``j == 0`` are flushed when the
block changes. ``Bb`` comes from the shapes (``cam_match.slot_block``).

VMEM sizing: the compare plane is chunked to ``ev_chunk * K`` floats under
the budget of ``cam_match.event_chunk`` (one plane per delay slot is built
at a time); each slot adds its ring column ``(max_delay + 1) * K``, its
weights and its scratch row, which ``slot_block`` counts.
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


def _fabric_deliver_kernel(
    cur_ref,  # SMEM [1, 1] int32 — the time-wheel write cursor
    ev_flat_ref,  # [1, Mp] int32 — flat ring target per entry (-1 = pad)
    ev_w_ref,  # [Bb, Mp] — masked event weights (0 = dropped/silent/pad)
    ext_ref,  # [Bb, K] — external input activity of the block at this cluster
    ring_ref,  # [Bb, D1, K] — carried ring columns of the block at this cluster
    tag_ref,  # [1, 1, Cb*S] — CAM tags of the neuron tile (batch-shared)
    syn_ref,  # [1, 1, Cb*S] — synapse types of the neuron tile
    out_ref,  # [1, Bb, Cb*4] — per-type synaptic drive
    ring_out_ref,  # [Bb, D1, K] — updated ring columns (cursor row zeroed)
    act_ref,  # VMEM scratch [Bb, K] — the block's arrival rows
    *,
    k_tags: int,
    n_clusters: int,
    d1: int,  # max_delay + 1 ring slots
    ev_chunk: int,
    words: int,
):
    c = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _update_ring_columns():
        cur = cur_ref[0, 0]
        mp = ev_flat_ref.shape[1]

        def chunk_body(i, cols):
            at = pl.ds(pl.multiple_of(i * ev_chunk, ev_chunk), ev_chunk)
            f = ev_flat_ref[:, at].reshape(ev_chunk, 1)
            w = ev_w_ref[:, at].astype(jnp.float32)  # [Bb, ev_chunk]
            out = []
            for d in range(d1):  # static, small: one compare plane per slot
                base = (d * n_clusters + c) * k_tags
                kk = (
                    jax.lax.broadcasted_iota(jnp.int32, (ev_chunk, k_tags), 1)
                    + base
                )
                match = (f == kk).astype(jnp.float32)  # [ev_chunk, K]
                out.append(
                    cols[d]
                    + jax.lax.dot_general(
                        w,
                        match,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                )  # [Bb, K]
            return tuple(out)

        cols = jax.lax.fori_loop(
            0,
            mp // ev_chunk,
            chunk_body,
            tuple(ring_ref[:, d, :].astype(jnp.float32) for d in range(d1)),
        )
        # pop the cursor slot: arrivals = carried + zero-delay + external,
        # then clear the row so the wheel can reuse it next revolution
        arrivals = ext_ref[...].astype(jnp.float32)
        for d in range(d1):
            arrivals = arrivals + jnp.where(cur == d, cols[d], 0.0)
            ring_out_ref[:, d, :] = jnp.where(cur == d, 0.0, cols[d]).astype(
                ring_out_ref.dtype
            )
        act_ref[...] = arrivals.astype(act_ref.dtype)

    # stage 2: CAM match of the block's VMEM-resident arrival rows
    drive = cam_drive(act_ref[...], tag_ref[0], syn_ref[0], words)
    out_ref[0] = drive.astype(out_ref.dtype)


def fabric_plan(b: int, k_tags: int, words: int, m: int, d1: int, block_c: int = 16):
    """``(Bb, B_pad, ev_chunk, M_pad, VMEM estimate)`` of
    :func:`fabric_deliver_ring_pallas` for ``b`` slots, ``m`` entries, a
    ring of ``d1`` slots and neuron tiles of ``block_c``."""
    ev_chunk = event_chunk(m, k_tags)
    m_pad = -(-m // ev_chunk) * ev_chunk
    fixed = (
        stage2_bytes(k_tags, words, block_c)
        + 2 * tile_bytes(1, m_pad)
        + 3 * tile_bytes(ev_chunk, k_tags)
    )
    # weights, external input and ring in and out, double-buffered (a slot's
    # ring column pads to 8 rows), the ring columns being updated, and stage
    # 2's share
    per_slot = (
        2 * (m_pad + k_tags) * 4
        + 2 * 2 * tile_bytes(d1, k_tags)
        + d1 * k_tags * 4
        + stage2_slot_bytes(k_tags, words, block_c)
    )
    bb = slot_block(b, per_slot, fixed)
    return bb, -(-b // bb) * bb, ev_chunk, m_pad, fixed + bb * per_slot


@functools.partial(
    jax.jit,
    static_argnames=("cluster_size", "k_tags", "max_delay", "block_c", "interpret"),
)
def fabric_deliver_ring_pallas(
    ev_flat: jax.Array,  # [M] int32 flat ring targets (cursor-rotated), -1 pad
    ev_w: jax.Array,  # [..., M] masked event weights (0 = not delivered)
    ring: jax.Array,  # [..., max_delay + 1, n_clusters, K] carried ring
    cursor: jax.Array,  # int32 scalar write cursor
    external_activity: jax.Array,  # [..., n_clusters, K]
    cam_tag: jax.Array,  # [N, S]
    cam_syn: jax.Array,  # [N, S]
    cluster_size: int,
    k_tags: int,
    max_delay: int,
    block_c: int = 16,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:  # (drive [..., N, 4], new ring)
    n, s = cam_tag.shape
    n_clusters = n // cluster_size
    k = k_tags
    d1 = max_delay + 1
    batch_shape = ev_w.shape[:-1]
    b = math.prod(batch_shape)
    m = ev_w.shape[-1]
    block_c = min(block_c, cluster_size)
    assert cluster_size % block_c == 0, (cluster_size, block_c)
    check_lane_aligned(k, interpret)
    dtype = ev_w.dtype
    tiles = cluster_size // block_c
    bb, b_pad, ev_chunk, m_pad, vmem = fabric_plan(b, k, s, m, d1, block_c)

    # free views that keep every block's last two dims full or (8, 128)-tiled
    # (cam_match.py): weights [B, M], external input [B, nc * K], and the
    # ring [B, D1, nc * K], whose block's columns at cluster c are the block
    # (Bb, D1, K) at lane-block c. The compare plane is chunked to a fixed
    # VMEM budget; M is padded up so the chunks tile it exactly (padding
    # entries are -1/0 = no-ops), and the slots to whole blocks
    ev_w2 = ev_w.reshape(b, m)
    ev_flat2 = ev_flat.reshape(1, m)
    if m_pad != m:
        ev_flat2 = jnp.pad(ev_flat2, ((0, 0), (0, m_pad - m)), constant_values=-1)
        ev_w2 = jnp.pad(ev_w2, ((0, 0), (0, m_pad - m)))

    ring2 = ring.reshape(b, d1, n_clusters * k)
    ext2 = jnp.broadcast_to(
        external_activity, (*batch_shape, n_clusters, k)
    ).reshape(b, n_clusters * k).astype(dtype)
    cur2 = jnp.asarray(cursor, jnp.int32).reshape(1, 1)
    grid = (b_pad // bb, n_clusters, tiles)
    tile_spec = pl.BlockSpec((1, 1, block_c * s), lambda bi, i, j: (i * tiles + j, 0, 0))
    ring_spec = pl.BlockSpec((bb, d1, k), lambda bi, i, j: (bi, 0, i))

    drive, new_ring = pl.pallas_call(
        functools.partial(
            _fabric_deliver_kernel,
            k_tags=k,
            n_clusters=n_clusters,
            d1=d1,
            ev_chunk=ev_chunk,
            words=s,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda bi, i, j: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, m_pad), lambda bi, i, j: (0, 0)),
            pl.BlockSpec((bb, m_pad), lambda bi, i, j: (bi, 0)),
            pl.BlockSpec((bb, k), lambda bi, i, j: (bi, i)),
            ring_spec,
            tile_spec,
            tile_spec,
        ],
        out_specs=[
            pl.BlockSpec(
                (1, bb, block_c * N_SYN_TYPES), lambda bi, i, j: (i * tiles + j, bi, 0)
            ),
            ring_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n // block_c, b_pad, block_c * N_SYN_TYPES), dtype),
            jax.ShapeDtypeStruct((b_pad, d1, n_clusters * k), ring.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bb, k), dtype)],
        compiler_params=compiler_params(vmem),
        interpret=interpret,
        name="fabric_deliver",
    )(
        cur2,
        ev_flat2,
        pad_slots(ev_w2, b_pad),
        pad_slots(ext2, b_pad),
        pad_slots(ring2, b_pad),
        cam_rows(cam_tag, block_c),
        cam_rows(cam_syn, block_c),
    )
    new_ring = new_ring[:b]
    return (
        drive_from_tiles(drive, b).reshape(*batch_shape, n, N_SYN_TYPES),
        new_ring.reshape(*batch_shape, d1, n_clusters, k)
        if batch_shape
        else new_ring.reshape(d1, n_clusters, k),
    )
