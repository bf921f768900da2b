"""Pallas TPU kernel for the stage-2 CAM match.

TPU-native rethink of the chip's CAM core (DESIGN.md §8): the hardware
performs a *parallel compare* of an incoming 10-bit tag against all 64 CAM
words of all 256 neurons in the core simultaneously (pre-charged match
lines). The TPU analogue of "compare one word against everything at once" is
a one-hot compare matrix contracted on the MXU:

    match[c, s, k] = (cam_tag[c, s] == k)            # the CAM compare plane
    vals[c, s]     = sum_k match[c, s, k] * A[k]     # match-line AND activity
    drive[c, t]    = sum_s vals[c, s] * (cam_syn[c, s] == t)

The kernel is batch-native: the grid is ``(B, cluster, neuron-tile)``. One
(batch, cluster) pair's activity row is pinned in VMEM per grid step (the
"broadcast within the core"), while neurons tile within the cluster so the
compare plane (block_c * S * K floats) stays within VMEM. The CAM tables are
shared across the batch — the same neuron tile is revisited for every batch
element with only the [1, K] activity row changing, so B tiles the MXU
without growing the VMEM-resident CAM state. All events of a timestep that
target one core are therefore resolved against VMEM-resident state, exactly
the paper's "CAM cells of different cores operate in parallel" argument.

Block layout: the activity is viewed (for free) as ``[B, 1, nc * K]`` so
each grid step's block ``(1, 1, K)`` has a full second-minor dim and a
lane dim of K — the TPU's (8, 128) tiling rule then only needs
``K % 128 == 0``, which the compiled path checks (interpret mode, the CPU
validation path, accepts any shape).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

N_SYN_TYPES = 4

# stage-1 compare-plane budget of the fused kernels: ev_chunk * K floats
# kept under ~2 MB of VMEM
_PLANE_BUDGET_ELEMS = 512 * 1024


def event_chunk(n_entries: int, k_tags: int) -> int:
    """Entries per stage-1 compare plane (fused and fabric kernels).

    The whole entry axis when it fits the plane budget, else the largest
    multiple of 128 lanes that does, so every in-kernel ``pl.ds`` chunk
    starts lane-aligned.
    """
    fit = _PLANE_BUDGET_ELEMS // max(1, k_tags)
    if n_entries <= fit:
        return max(1, n_entries)
    return max(128, fit // 128 * 128)


def check_lane_aligned(k_tags: int, interpret: bool) -> None:
    """The compiled kernels tile a cluster's K-row as one lane block, so K
    must be a multiple of the TPU's 128 lanes; interpret mode takes any K."""
    if not interpret and k_tags % 128:
        raise ValueError(
            f"the compiled Pallas delivery kernels need K % 128 == 0, got "
            f"K={k_tags}; pad the tag space or run with interpret=True"
        )


def cam_drive(a: jax.Array, tags: jax.Array, syn: jax.Array) -> jax.Array:
    """Stage-2 CAM match of one VMEM-resident activity row against a neuron
    tile: ``a [K]``, ``tags``/``syn [Cb, S]`` -> drive ``[Cb, 4]`` (f32).

    Shared by the cam_match, fused_deliver and fabric_deliver kernel bodies.
    """
    k_tags = a.shape[0]
    cb, s = tags.shape
    valid = tags >= 0
    # CAM compare plane: [Cb, S, K] one-hot (the parallel match-line search).
    kk = jax.lax.broadcasted_iota(jnp.int32, (cb, s, k_tags), 2)
    match = (tags[:, :, None] == kk).astype(a.dtype)
    # match-line x activity: contract K on the MXU.
    vals = jax.lax.dot_general(
        match.reshape(cb * s, k_tags),
        a.reshape(k_tags, 1),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(cb, s)
    vals = jnp.where(valid, vals, 0.0)
    # accumulate into the 4 synapse-type lines (pulse-decoder DECs).
    tt = jax.lax.broadcasted_iota(jnp.int32, (cb, s, N_SYN_TYPES), 2)
    syn1h = (syn[:, :, None] == tt).astype(vals.dtype)
    return jax.lax.dot_general(
        vals.reshape(cb, 1, s),
        syn1h,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ).reshape(cb, N_SYN_TYPES)


def _cam_match_kernel(activity_ref, tag_ref, syn_ref, out_ref):
    # activity_ref: [1, 1, K]     — this (batch, cluster)'s broadcast activity
    # tag_ref:      [1, Cb, S]    — CAM tags of the neuron tile (batch-shared)
    # syn_ref:      [1, Cb, S]    — synapse types of the neuron tile
    # out_ref:      [1, 1, Cb, 4] — per-type synaptic drive
    drive = cam_drive(activity_ref[0, 0, :], tag_ref[0], syn_ref[0])
    out_ref[0, 0] = drive.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("cluster_size", "block_c", "interpret"))
def cam_match_pallas(
    activity: jax.Array,  # [..., n_clusters, K]
    cam_tag: jax.Array,  # [N, S]
    cam_syn: jax.Array,  # [N, S]
    cluster_size: int,
    block_c: int = 16,
    interpret: bool = True,
) -> jax.Array:  # [..., N, N_SYN_TYPES]
    n, s = cam_tag.shape
    n_clusters, k = activity.shape[-2:]
    batch_shape = activity.shape[:-2]
    b = math.prod(batch_shape)
    assert n == n_clusters * cluster_size
    block_c = min(block_c, cluster_size)
    assert cluster_size % block_c == 0, (cluster_size, block_c)
    check_lane_aligned(k, interpret)

    act3 = activity.reshape(b, 1, n_clusters * k)
    tags3 = cam_tag.reshape(n_clusters, cluster_size, s)
    syn3 = cam_syn.reshape(n_clusters, cluster_size, s)
    grid = (b, n_clusters, cluster_size // block_c)

    out = pl.pallas_call(
        _cam_match_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, k), lambda bi, i, j: (bi, 0, i)),
            pl.BlockSpec((1, block_c, s), lambda bi, i, j: (i, j, 0)),
            pl.BlockSpec((1, block_c, s), lambda bi, i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_c, N_SYN_TYPES), lambda bi, i, j: (bi, i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_clusters, cluster_size, N_SYN_TYPES), activity.dtype
        ),
        interpret=interpret,
        name="cam_match",
    )(act3, tags3, syn3)
    return out.reshape(*batch_shape, n, N_SYN_TYPES)
