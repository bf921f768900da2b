"""Pallas TPU kernel for the stage-2 CAM match.

TPU-native rethink of the chip's CAM core (DESIGN.md §8): the hardware
performs a *parallel compare* of an incoming 10-bit tag against all 64 CAM
words of all 256 neurons in the core simultaneously (pre-charged match
lines). The TPU analogue of "compare one word against everything at once" is
a one-hot compare matrix contracted on the MXU:

    match[k, q]    = (cam_tag[q] == k)               # the CAM compare plane
    vals[b, q]     = sum_k A[b, k] * match[k, q]     # match-line AND activity
    drive[b, c, t] = sum_s vals[b, (c, s)] * (cam_syn[c, s] == t)

for the ``q = (c, s)`` CAM words of a tile of ``Cb`` neurons. The compare
plane depends only on the CAM tables, never on the slot, so it is built once
per *block of slots* and contracted with the block's ``[Bb, K]`` activity
rows: the slots ride the MXU's N axis. The grid is
``(slot block, cluster, neuron-tile)``; the block's activity rows are pinned
in VMEM (the "broadcast within the core") while the neuron tiles of the
cluster step past them, so the plane (``K * Cb * S`` floats) stays within
VMEM. All events of a timestep that target one core are therefore resolved
against VMEM-resident state, the paper's "CAM cells of different cores
operate in parallel" argument.

``Bb`` comes from the shapes (:func:`slot_block`): the largest block of at
most 128 slots whose VMEM estimate fits a fixed budget; the batch is padded
to whole blocks with empty slots, which are sliced off. The compiler's VMEM
limit is set from the same estimate.

Block layout: the activity is viewed as ``[B, nc * K]`` and the CAM tables
as one row of ``Cb * S`` words a tile (``[N / Cb, 1, Cb * S]``), so every
block's last two dims are full or (8, 128)-tiled — the TPU's tiling rule then
only needs ``K % 128 == 0``, which the compiled path checks (interpret mode,
the CPU validation path, accepts any shape). The drive comes out as
``[N / Cb, B, Cb * 4]`` and is put back to ``[B, N, 4]`` by the wrapper.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N_SYN_TYPES = 4

# stage-1 compare-plane budget of the fused kernels: ev_chunk * K floats
# kept under ~2 MB of VMEM
_PLANE_BUDGET_ELEMS = 512 * 1024

# most slots a grid step contracts: the MXU's N width
MAX_SLOT_BLOCK = 128
# VMEM a kernel's blocks, scratch and compare planes may take (a v5e core has
# 128 MiB; the compiler's default scoped limit is 16 MiB)
_VMEM_BUDGET = 72 * 2**20
# room above the estimate for the compiler's own temporaries
_VMEM_HEADROOM = 16 * 2**20


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


def tile_bytes(rows: int, lanes: int) -> int:
    """VMEM bytes of a ``[rows, lanes]`` 32-bit array, padded to the
    (8, 128) tiles."""
    return (-(-rows // 8) * 8) * (-(-lanes // 128) * 128) * 4


def stage2_bytes(k_tags: int, words: int, block_c: int) -> int:
    """VMEM of one stage-2 grid step apart from its slot rows: the CAM rows
    (double-buffered), the ``[K, Cb*S]`` compare plane with its int32 compare
    and the synapse-type selector."""
    q = block_c * words
    return (
        2 * 2 * tile_bytes(1, q)
        + 2 * tile_bytes(k_tags, q)
        + 2 * tile_bytes(block_c * N_SYN_TYPES, q)
    )


def stage2_slot_bytes(k_tags: int, words: int, block_c: int) -> int:
    """VMEM of one slot in a stage-2 grid step: its activity row (scratch or
    double-buffered input), its match values and its drive row
    (double-buffered, lane-padded)."""
    return (2 * k_tags + block_c * words + 2 * max(128, block_c * N_SYN_TYPES)) * 4


def slot_block(b: int, per_slot: int, fixed: int, align: int = 8) -> int:
    """Slots per grid step: the largest block of at most ``min(b, 128)``
    slots whose VMEM estimate ``fixed + Bb * per_slot`` fits the budget.

    One block holds the whole batch where it fits (a full dim needs no
    alignment). Otherwise the blocks are evened out and each is rounded up to
    whole ``align``-row tiles, never past the largest block that fits; the
    batch is then padded to whole blocks.
    """
    fit = max(1, (_VMEM_BUDGET - fixed) // per_slot)
    most = min(b, MAX_SLOT_BLOCK, fit)
    if most >= b:
        return b
    most = max(align, most // align * align)
    n_blocks = -(-b // most)
    return -(-(-(-b // n_blocks)) // align) * align


def compiler_params(vmem_estimate: int) -> pltpu.CompilerParams:
    """The kernel's VMEM limit, from the same estimate that sized its slot
    block."""
    return pltpu.CompilerParams(vmem_limit_bytes=int(vmem_estimate) + _VMEM_HEADROOM)


def pad_slots(x: jax.Array, b_pad: int, value=0) -> jax.Array:
    """Pad the leading slot axis to ``b_pad`` with empty slots."""
    extra = b_pad - x.shape[0]
    if not extra:
        return x
    return jnp.pad(x, ((0, extra),) + ((0, 0),) * (x.ndim - 1), constant_values=value)


def cam_rows(cam: jax.Array, block_c: int) -> jax.Array:
    """``[N, S]`` CAM table -> ``[N / Cb, 1, Cb * S]``: one row of words a
    neuron tile, so a tile's words lie along the lanes."""
    n, s = cam.shape
    return cam.reshape(n // block_c, 1, block_c * s)


def drive_from_tiles(out: jax.Array, b: int) -> jax.Array:
    """Kernel drive ``[N / Cb, B_pad, Cb * 4]`` -> ``[b, N, 4]``."""
    n_tiles, _, width = out.shape
    n = n_tiles * width // N_SYN_TYPES
    return out.transpose(1, 0, 2)[:b].reshape(b, n, N_SYN_TYPES)


def cam_drive(a: jax.Array, tags: jax.Array, syn: jax.Array, words: int) -> jax.Array:
    """Stage-2 CAM match of a block of VMEM-resident activity rows against a
    neuron tile: ``a [Bb, K]``, ``tags``/``syn [1, Cb*S]`` (the tile's words
    as one row, ``S = words``) -> drive ``[Bb, Cb*4]`` (f32), neuron-major.

    The ``[K, Cb*S]`` compare plane does not depend on the slot: it is built
    once and contracted with all ``Bb`` rows on the MXU.
    Shared by the cam_match, fused_deliver and fabric_deliver kernel bodies.
    """
    k_tags = a.shape[1]
    q = tags.shape[1]
    lines = q // words * N_SYN_TYPES
    # CAM compare plane: [K, Cb*S] one-hot (the parallel match-line search).
    kk = jax.lax.broadcasted_iota(jnp.int32, (k_tags, q), 0)
    match = (tags == kk).astype(a.dtype)
    # match-line x activity: contract K on the MXU, the slots on its N axis.
    vals = jax.lax.dot_general(
        a, match, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Bb, Cb*S]
    # accumulate into the 4 synapse-type lines (pulse-decoder DECs): line
    # r = 4 * neuron + type takes the valid words of that neuron and type.
    r = jax.lax.broadcasted_iota(jnp.int32, (lines, q), 0)
    w = jax.lax.broadcasted_iota(jnp.int32, (lines, q), 1)
    first = jax.lax.shift_right_logical(r, 2) * words
    select = (
        (w >= first)
        & (w < first + words)
        & (syn == jnp.bitwise_and(r, N_SYN_TYPES - 1))
        & (tags >= 0)
    ).astype(vals.dtype)
    return jax.lax.dot_general(
        vals, select, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Bb, Cb*4]


def _cam_match_kernel(activity_ref, tag_ref, syn_ref, out_ref, *, words: int):
    # activity_ref: [Bb, K]        — the block's activity rows at this cluster
    # tag_ref:      [1, 1, Cb*S]   — CAM tags of the neuron tile (batch-shared)
    # syn_ref:      [1, 1, Cb*S]   — synapse types of the neuron tile
    # out_ref:      [1, Bb, Cb*4]  — per-type synaptic drive
    drive = cam_drive(activity_ref[...], tag_ref[0], syn_ref[0], words)
    out_ref[0] = drive.astype(out_ref.dtype)


def cam_match_plan(b: int, k_tags: int, words: int, block_c: int, itemsize: int = 4):
    """``(Bb, B_pad, VMEM estimate)`` of :func:`cam_match_pallas`."""
    fixed = stage2_bytes(k_tags, words, block_c)
    per_slot = stage2_slot_bytes(k_tags, words, block_c)
    bb = slot_block(b, per_slot, fixed, align=8 * 4 // itemsize)
    return bb, -(-b // bb) * bb, fixed + bb * per_slot


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
    tiles = cluster_size // block_c
    bb, b_pad, vmem = cam_match_plan(b, k, s, block_c, activity.dtype.itemsize)

    act2 = pad_slots(activity.reshape(b, n_clusters * k), b_pad)
    grid = (b_pad // bb, n_clusters, tiles)
    tile_spec = pl.BlockSpec((1, 1, block_c * s), lambda bi, i, j: (i * tiles + j, 0, 0))

    out = pl.pallas_call(
        functools.partial(_cam_match_kernel, words=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, k), lambda bi, i, j: (bi, i)),
            tile_spec,
            tile_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, bb, block_c * N_SYN_TYPES), lambda bi, i, j: (i * tiles + j, bi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n // block_c, b_pad, block_c * N_SYN_TYPES), activity.dtype
        ),
        compiler_params=compiler_params(vmem),
        interpret=interpret,
        name="cam_match",
    )(act2, cam_rows(cam_tag, block_c), cam_rows(cam_syn, block_c))
    return drive_from_tiles(out, b).reshape(*batch_shape, n, N_SYN_TYPES)
