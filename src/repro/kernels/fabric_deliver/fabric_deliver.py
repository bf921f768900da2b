"""Pallas TPU kernel: fused time-wheel fabric delivery (DESIGN.md §14).

The jnp fabric path updates the carried ring buffer in HBM, reads the
arrival slot back, and hands it to the stage-2 CAM match — the arrival
activity row makes an HBM round-trip between the ring update and the match.
This kernel fuses the three per (batch, cluster) grid step:

  1. the ring *column* ``ring[b, :, c, :]`` ([max_delay + 1, K]) is pulled
     into VMEM and the step's surviving events are scatter-added into it via
     the one-hot compare-plane matmul idiom of kernels/fused_deliver — one
     plane per delay slot, events pre-addressed as flat ring targets
     ``slot * (nc * K) + dst * K + tag`` (slot already cursor-rotated);
  2. the cursor row (slot-0 arrivals) is captured — carried events + this
     step's zero-delay events + external input — into a VMEM scratch row
     that never round-trips HBM, and zeroed in the outgoing ring column
     (read-then-clear, the time-wheel pop);
  3. the neuron tiles of cluster ``c`` CAM-match the VMEM-resident row
     (identical to kernels/fused_deliver stage 2).

Arbitration (per-directed-link FIFOs) and queue admission happen *outside*
in O(events) masked prefix sums (kernels/fabric_deliver/ops.py) — they are
cheap, shared with the jnp fast path, and produce the masked event weights
this kernel consumes (weight 0 = not delivered).

Grid ``(B, n_clusters, neuron-tile)``; TPU grids execute sequentially with
the last dimension minor, so the scratch row built at tile ``j == 0``
persists for the (batch, cluster) pair's remaining neuron tiles, and the
ring column written once at ``j == 0`` is flushed when the block changes.

VMEM sizing: the compare plane is chunked to ``ev_chunk * K`` floats under
the budget of ``cam_match.event_chunk`` (one plane per delay slot is built
at a time); the
resident ring column adds ``(max_delay + 1) * K`` floats and the scratch
row ``K`` — small next to the plane budget for any realistic ``max_delay``.
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


def _fabric_deliver_kernel(
    cur_ref,  # SMEM [1, 1] int32 — the time-wheel write cursor
    ev_flat_ref,  # [1, Mp] int32 — flat ring target per entry (-1 = pad)
    ev_w_ref,  # [1, 1, Mp] — masked event weight (0 = dropped/silent/pad)
    ext_ref,  # [1, 1, K] — external input activity for this (batch, cluster)
    ring_ref,  # [1, D1, K] — carried ring column of this (batch, cluster)
    tag_ref,  # [1, Cb, S] — CAM tags of the neuron tile (batch-shared)
    syn_ref,  # [1, Cb, S] — synapse types of the neuron tile
    out_ref,  # [1, 1, Cb, 4] — per-type synaptic drive
    ring_out_ref,  # [1, D1, K] — updated ring column (cursor row zeroed)
    act_ref,  # VMEM scratch [1, K] — this (batch, cluster)'s arrival row
    *,
    k_tags: int,
    n_clusters: int,
    d1: int,  # max_delay + 1 ring slots
    ev_chunk: int,
):
    c = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _update_ring_column():
        cur = cur_ref[0, 0]
        mp = ev_flat_ref.shape[1]

        def chunk_body(i, col):
            at = pl.ds(pl.multiple_of(i * ev_chunk, ev_chunk), ev_chunk)
            f = ev_flat_ref[0, at]  # [ev_chunk]
            w = ev_w_ref[0, 0, at]
            rows = []
            for d in range(d1):  # static, small: one compare plane per slot
                base = (d * n_clusters + c) * k_tags
                kk = (
                    jax.lax.broadcasted_iota(jnp.int32, (ev_chunk, k_tags), 1)
                    + base
                )
                match = (f[:, None] == kk).astype(jnp.float32)
                rows.append(
                    jax.lax.dot_general(
                        w.reshape(1, ev_chunk).astype(jnp.float32),
                        match,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                )  # [1, K]
            return col + jnp.concatenate(rows, axis=0)  # [D1, K]

        col = jax.lax.fori_loop(
            0, mp // ev_chunk, chunk_body, ring_ref[0].astype(jnp.float32)
        )
        # pop the cursor slot: arrivals = carried + zero-delay + external,
        # then clear the row so the wheel can reuse it next revolution
        sel = jax.lax.broadcasted_iota(jnp.int32, (d1, k_tags), 0) == cur
        arrivals = jnp.sum(jnp.where(sel, col, 0.0), axis=0)  # [K]
        act_ref[0, :] = (arrivals + ext_ref[0, 0, :].astype(jnp.float32)).astype(
            act_ref.dtype
        )
        ring_out_ref[0] = jnp.where(sel, 0.0, col).astype(ring_out_ref.dtype)

    # stage 2: CAM match of the VMEM-resident arrival row (kernels/cam_match)
    drive = cam_drive(act_ref[0, :], tag_ref[0], syn_ref[0])
    out_ref[0, 0] = drive.astype(out_ref.dtype)


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
    block_c = min(block_c, cluster_size)
    assert cluster_size % block_c == 0, (cluster_size, block_c)
    check_lane_aligned(k, interpret)
    dtype = ev_w.dtype

    # free views that keep every block's last two dims full or (D1|1, K) —
    # the TPU's (8, 128) tiling rule (cam_match.py): weights [B, 1, M],
    # external input [B, 1, nc * K], and the ring [B, D1, nc * K], whose
    # (b, c) column is the block (1, D1, K) at lane-block c
    ev_w2 = ev_w.reshape(b, 1, -1)
    m = ev_w2.shape[2]
    # chunk the compare plane to a fixed VMEM budget; pad M up so the chunks
    # tile it exactly (padding entries are -1/0 = no-ops)
    ev_chunk = event_chunk(m, k)
    m_pad = -(-m // ev_chunk) * ev_chunk
    ev_flat2 = ev_flat.reshape(1, m)
    if m_pad != m:
        ev_flat2 = jnp.pad(ev_flat2, ((0, 0), (0, m_pad - m)), constant_values=-1)
        ev_w2 = jnp.pad(ev_w2, ((0, 0), (0, 0), (0, m_pad - m)))

    ring2 = ring.reshape(b, d1, n_clusters * k)
    ext3 = jnp.broadcast_to(
        external_activity, (*batch_shape, n_clusters, k)
    ).reshape(b, 1, n_clusters * k).astype(dtype)
    tags3 = cam_tag.reshape(n_clusters, cluster_size, s)
    syn3 = cam_syn.reshape(n_clusters, cluster_size, s)
    cur2 = jnp.asarray(cursor, jnp.int32).reshape(1, 1)
    grid = (b, n_clusters, cluster_size // block_c)

    drive, new_ring = pl.pallas_call(
        functools.partial(
            _fabric_deliver_kernel,
            k_tags=k,
            n_clusters=n_clusters,
            d1=d1,
            ev_chunk=ev_chunk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda bi, i, j: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, m_pad), lambda bi, i, j: (0, 0)),
            pl.BlockSpec((1, 1, m_pad), lambda bi, i, j: (bi, 0, 0)),
            pl.BlockSpec((1, 1, k), lambda bi, i, j: (bi, 0, i)),
            pl.BlockSpec((1, d1, k), lambda bi, i, j: (bi, 0, i)),
            pl.BlockSpec((1, block_c, s), lambda bi, i, j: (i, j, 0)),
            pl.BlockSpec((1, block_c, s), lambda bi, i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_c, N_SYN_TYPES), lambda bi, i, j: (bi, i, j, 0)),
            pl.BlockSpec((1, d1, k), lambda bi, i, j: (bi, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_clusters, cluster_size, N_SYN_TYPES), dtype),
            jax.ShapeDtypeStruct((b, d1, n_clusters * k), ring.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, k), dtype)],
        interpret=interpret,
        name="fabric_deliver",
    )(cur2, ev_flat2, ev_w2, ext3, ring2, tags3, syn3)
    return (
        drive.reshape(*batch_shape, n, N_SYN_TYPES),
        new_ring.reshape(*batch_shape, d1, n_clusters, k)
        if batch_shape
        else new_ring.reshape(d1, n_clusters, k),
    )
