"""Public jit'd wrapper for fused event-sparse delivery.

Chooses kernel vs reference by platform, mirroring kernels/cam_match/ops:
the fused Pallas kernel targets TPU; on CPU we default to the jnp
event-sparse oracle (queue-compacted stage 1 + indexed stage 2) and can
validate the kernel in interpret mode via ``interpret=True`` (slow).

Consumes an :class:`~repro.core.two_stage.EventQueue` — the SRAM gather for
queued events happens here (outside the kernel, where XLA fuses it with the
queue build) and the kernel receives pre-flattened ``(dest * K + tag)``
entries. Most callers should go through the ``fused`` dispatch backend
(repro.core.dispatch) instead of calling this directly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.two_stage import EventQueue, gather_event_entries
from repro.kernels.fused_deliver.fused_deliver import fused_deliver_pallas
from repro.kernels.fused_deliver.ref import fused_deliver_ref


@jax.named_scope("stage1")
def _event_entries_flat(
    queue: EventQueue, src_tag: jax.Array, src_dest: jax.Array, k_tags: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Queue -> kernel inputs: flat ``dest*K + tag`` [..., Q*E], weights,
    and the occupied entry count [...] — the queue holds its events in a
    prefix of its slots, so every valid entry lies in ``[0, n_entries)``."""
    ev_tag, ev_dest = gather_event_entries(queue, src_tag, src_dest)
    valid = ev_tag >= 0
    ev_flat = jnp.where(valid, ev_dest * k_tags + ev_tag, -1)
    ev_w = queue.weight[..., None] * valid.astype(queue.weight.dtype)
    batch_shape = queue.src.shape[:-1]
    n_entries = (queue.src >= 0).sum(-1, dtype=jnp.int32) * src_tag.shape[1]
    return (
        ev_flat.reshape(*batch_shape, -1),
        ev_w.reshape(*batch_shape, -1),
        n_entries,
    )


def fused_deliver(
    queue: EventQueue,
    src_tag: jax.Array,
    src_dest: jax.Array,
    cam_tag: jax.Array,
    cam_syn: jax.Array,
    cluster_size: int,
    k_tags: int,
    external_activity: jax.Array | None = None,
    syn_onehot: jax.Array | None = None,
    block_c: int = 16,
    interpret: bool | None = None,
) -> jax.Array:
    # same policy as PallasBackend: None = platform default (compiled kernel
    # on TPU, jnp reference elsewhere); True/False = force the kernel in
    # interpret/compiled mode regardless of platform.
    if interpret is None:
        if jax.default_backend() != "tpu":
            return fused_deliver_ref(
                queue, src_tag, src_dest, cam_tag, cam_syn, cluster_size, k_tags,
                external_activity=external_activity, syn_onehot=syn_onehot,
            )
        interpret = False
    ev_flat, ev_w, n_entries = _event_entries_flat(queue, src_tag, src_dest, k_tags)
    n_clusters = src_tag.shape[0] // cluster_size
    if external_activity is None:
        external_activity = jnp.zeros(
            (*queue.src.shape[:-1], n_clusters, k_tags), ev_w.dtype
        )
    return fused_deliver_pallas(
        ev_flat, ev_w, cam_tag, cam_syn, external_activity, cluster_size, k_tags,
        n_entries=n_entries, block_c=block_c, interpret=interpret,
    )
