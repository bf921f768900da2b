"""Time-wheel fabric delivery: static entry tables + the ring fast path.

The roll-based fabric step (core/dispatch.py ``deliver_fabric``) re-derives
every event's route *per step*: gather the queued sources' SRAM rows, bin by
tile pair, argsort-arbitrate the link FIFOs, gather four ``[nc, nc]`` stats
matrices, then concat-shift the whole delay-line buffer. All of that is a
function of the *routing tables*, which never change at run time.

:func:`build_fabric_entries` hoists it to engine construction: one host-side
pass enumerates the ``M`` occupied SRAM entries and precomputes, per entry,
the flat destination address, arrival delay, directed-link bin and the
Table II-IV per-event figures — statically sorted in **arbitration order**
``(link, src, entry)``, which is exactly the order the per-step
``dispatch_slots`` argsort would produce (queue slots ascend by source id,
entries by index). Per step, delivery is then event-count-proportional:

  * queue admission  = one masked prefix count over the spike vector
    (bit-identical to ``compact_events`` truncation: first ``capacity``
    active sources, lowest id first);
  * link arbitration = one masked prefix count over the entry axis — the
    in-link FIFO position of an active cross-tile entry is the number of
    active cross-tile entries before it in its statically-sorted link
    group, no sort at run time (bit-identical keep set);
  * delay scatter    = one scatter-add of masked weights at
    ``(cursor + delay) % (max_delay + 1)`` into the carried ring — the
    time-wheel replacing the dense ``advance_inflight`` shift;
  * stats            = masked sums of the static per-entry columns
    (integer stats bit-identical; float latency/energy sums may associate
    differently than the roll path's gather — same addends).

:func:`fabric_deliver_ring` follows the kernels platform policy: the fused
Pallas kernel (fabric_deliver.py) on TPU, the jnp ring update + stage-2
reference elsewhere; ``interpret=True`` forces the kernel in interpret mode
for CPU validation.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import DeliveryStats
from repro.core.two_stage import _accumulate_into, _scatter_count, stage2_cam_match
from repro.kernels.fabric_deliver.fabric_deliver import fabric_deliver_ring_pallas

__all__ = [
    "FabricEntries",
    "build_fabric_entries",
    "fabric_deliver_ring",
]


@dataclasses.dataclass(frozen=True)
class FabricEntries:
    """Static per-SRAM-entry routing table, sorted in arbitration order.

    One row per *occupied* SRAM entry (``src_tag >= 0``), statically
    lexsorted by ``(link, src, entry)`` — intra-tile entries (``link = -1``)
    first, then each directed link's group in the arbiter's scan order.
    ``link_start[m]`` is the index of row ``m``'s link-group start, so an
    active entry's FIFO position is a prefix-count difference. ``valid`` is
    ``False`` only on the single pad row of an entry-less table.
    """

    src: jax.Array  # [M] int32 source neuron id
    dstk: jax.Array  # [M] int32 flat dst_cluster * K + tag
    delay: jax.Array  # [M] int32 arrival delay in steps
    cross: jax.Array  # [M] bool inter-tile (link-arbitrated)
    link_start: jax.Array  # [M] int32 index of this entry's link-group start
    # flat directed tile pair src_tile * n_tiles + dst_tile for per-link
    # stats attribution (DESIGN.md §18); intra-tile entries carry the tile's
    # self-link diagonal. NOT the sort key — ordering still groups intra
    # entries first (see _entries_from_raw), so carries stay bit-identical.
    link: jax.Array  # [M] int32
    hops: jax.Array  # [M] int32 mesh hops (Table IV)
    latency_s: jax.Array  # [M] float32 per-event latency (Table II)
    energy_j: jax.Array  # [M] float32 per-event energy (Table III/IV)
    valid: jax.Array  # [M] bool
    # fault injection (DESIGN.md §15): a False entry is statically severed
    # (dead tile/link or Bernoulli route erasure) — its events always drop,
    # are counted in link_dropped, and never consume link-FIFO capacity
    alive: jax.Array  # [M] bool


jax.tree_util.register_dataclass(
    FabricEntries,
    data_fields=[
        "src", "dstk", "delay", "cross", "link_start", "link", "hops",
        "latency_s", "energy_j", "valid", "alive",
    ],
    meta_fields=[],
)


def build_fabric_entries(
    src_tag,  # [N, E] int32, -1 = empty (numpy or jax)
    src_dest,  # [N, E] int32 destination cluster ids
    cluster_size: int,
    k_tags: int,
    model,  # routing.FabricDeliveryModel
    entry_alive=None,  # [N, E] bool fault mask (faults.entry_alive_mask)
) -> FabricEntries:
    """Host-side precompute of the static entry table (numpy, once per engine).

    ``entry_alive`` (from :func:`repro.core.faults.entry_alive_mask`, or
    derived here from the model's fault matrices when omitted) statically
    severs faulted entries: they keep their table row — so the fault is
    *observable* as a per-step ``link_dropped`` count — but never deliver
    and never occupy link-FIFO capacity (a dead link has no FIFO).
    """
    src_tag = np.asarray(src_tag)
    src_dest = np.asarray(src_dest)
    tiles = np.asarray(model.tile_of_cluster)
    n_clusters = tiles.shape[0]
    if entry_alive is None and getattr(model, "pair_alive", None) is not None:
        from repro.core.faults import entry_alive_mask

        entry_alive = entry_alive_mask(src_tag, src_dest, cluster_size, model)
    src_ids, e_ids = np.nonzero(src_tag >= 0)
    if src_ids.size == 0:  # entry-less table: one inert pad row
        return _pad_entries()
    tag = src_tag[src_ids, e_ids].astype(np.int64)
    dst = np.clip(src_dest[src_ids, e_ids], 0, n_clusters - 1).astype(np.int64)
    alive = (
        None if entry_alive is None else np.asarray(entry_alive)[src_ids, e_ids]
    )
    src_cl = src_ids // cluster_size
    s_tile = tiles[src_cl]
    d_tile = tiles[dst]
    cross = s_tile != d_tile
    link = np.where(cross, s_tile * model.n_tiles + d_tile, -1)
    # stats attribution column: intra-tile entries map to the tile's
    # self-link diagonal (the sort key keeps -1 so ordering is unchanged)
    stat_link = np.where(cross, s_tile * model.n_tiles + d_tile,
                         s_tile * model.n_tiles + s_tile)
    # arbitration order: link groups, each scanned (src asc, entry asc) —
    # identical to dispatch_slots' stable argsort of queue-major event order
    order = np.lexsort((e_ids, src_ids, link))
    src_s, dst_s, tag_s = src_ids[order], dst[order], tag[order]
    cl_s, link_s, cross_s = src_cl[order], link[order], cross[order]
    stat_link_s = stat_link[order]
    alive_s = np.ones(src_s.size, bool) if alive is None else alive[order]
    m = src_s.size
    is_start = np.ones(m, bool)
    is_start[1:] = link_s[1:] != link_s[:-1]
    link_start = np.maximum.accumulate(np.where(is_start, np.arange(m), 0))
    return FabricEntries(
        src=jnp.asarray(src_s.astype(np.int32)),
        dstk=jnp.asarray((dst_s * k_tags + tag_s).astype(np.int32)),
        delay=jnp.asarray(np.asarray(model.delay_steps)[cl_s, dst_s].astype(np.int32)),
        cross=jnp.asarray(cross_s),
        link_start=jnp.asarray(link_start.astype(np.int32)),
        link=jnp.asarray(stat_link_s.astype(np.int32)),
        hops=jnp.asarray(np.asarray(model.mesh_hops)[cl_s, dst_s].astype(np.int32)),
        latency_s=jnp.asarray(
            np.asarray(model.latency_s)[cl_s, dst_s].astype(np.float32)
        ),
        energy_j=jnp.asarray(
            np.asarray(model.energy_j)[cl_s, dst_s].astype(np.float32)
        ),
        valid=jnp.asarray(np.ones(m, bool)),
        alive=jnp.asarray(alive_s),
    )


def _pad_entries() -> FabricEntries:
    """One inert pad row for an entry-less table."""
    z = np.zeros(1, np.int32)
    return FabricEntries(
        src=jnp.asarray(z), dstk=jnp.asarray(z), delay=jnp.asarray(z),
        cross=jnp.asarray(np.zeros(1, bool)), link_start=jnp.asarray(z),
        link=jnp.asarray(z),
        hops=jnp.asarray(z), latency_s=jnp.zeros(1, jnp.float32),
        energy_j=jnp.zeros(1, jnp.float32),
        valid=jnp.asarray(np.zeros(1, bool)),
        alive=jnp.asarray(np.ones(1, bool)),
    )


def _count_bins(mask, bins, size):
    """Per-bin counts of a ``[..., M]`` entry mask at static ``[M]`` bins."""
    return _scatter_count(
        mask[..., None], jnp.broadcast_to(bins[:, None], mask.shape + (1,)), size
    )


def _ring_update_jnp(
    ring, flat, w, cursor, external_activity, cam_tag, cam_syn, cluster_size,
    k_tags, d1, syn_onehot,
):
    """jnp fast path: scatter into the carried ring, pop the cursor slot."""
    batch_shape = w.shape[:-1]
    n_clusters = cam_tag.shape[0] // cluster_size
    size = d1 * n_clusters * k_tags
    b = math.prod(batch_shape) if batch_shape else 1
    buf = _accumulate_into(ring.reshape(b, size), flat, w.reshape(b, -1))
    ring = buf.reshape(*batch_shape, d1, n_clusters, k_tags)
    ax = ring.ndim - 3
    a = jnp.take(ring, cursor, axis=ax)
    ring = jax.lax.dynamic_update_index_in_dim(ring, jnp.zeros_like(a), cursor, ax)
    if external_activity is not None:
        a = a + external_activity
    drive = stage2_cam_match(a, cam_tag, cam_syn, cluster_size, syn_onehot)
    return drive, ring


def _prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum over the last axis, as the windowed sum that
    ``jnp.cumsum`` lowers to. Written out, its instructions keep the
    caller's ``named_scope`` (``cumsum`` lowers out of line, and its
    instructions lose the scope)."""
    n = x.shape[-1]
    return jax.lax.reduce_window(
        x, jnp.zeros((), x.dtype), jax.lax.add,
        window_dimensions=(1,) * (x.ndim - 1) + (n,),
        window_strides=(1,) * x.ndim,
        padding=((0, 0),) * (x.ndim - 1) + ((n - 1, 0),),
    )


def fabric_deliver_ring(
    spikes: jax.Array,  # [..., N]
    entries: FabricEntries,
    cam_tag: jax.Array,  # [N, S]
    cam_syn: jax.Array,  # [N, S]
    cluster_size: int,
    k_tags: int,
    ring: jax.Array,  # [..., max_delay + 1, n_clusters, K]
    cursor: jax.Array,  # int32 scalar
    *,
    max_delay: int,
    link_capacity: int | None,
    queue_capacity: int | None = None,
    external_activity: jax.Array | None = None,
    syn_onehot: jax.Array | None = None,
    block_c: int = 16,
    interpret: bool | None = None,
    per_link_stats: bool = False,
    n_tiles: int | None = None,  # required when per_link_stats
) -> tuple[jax.Array, jax.Array, jax.Array, DeliveryStats]:
    """One time-wheel fabric step: ``(drive, ring, cursor, DeliveryStats)``.

    Bit-identical arrival steps, drop counts and integer stats to the
    roll-based ``compact_events`` + ``stage1_route_events_fabric`` +
    ``advance_inflight`` pipeline (the ring property suite locks this);
    float latency/energy sums agree to reduction-order tolerance.

    ``per_link_stats`` widens ``link_dropped`` to per directed tile pair
    (``[..., n_tiles**2]``, fault drops of intra-tile entries on the
    diagonal) and ``delivered`` to per (src, dst) cluster pair
    (``[..., n_clusters**2]``) — same convention as the roll path, summing
    to exactly the scalar counters. The delivery itself (and hence the ring
    carry) is untouched: stats live outside the kernel.
    """
    n = spikes.shape[-1]
    n_clusters = n // cluster_size
    d1 = max_delay + 1
    cursor = jnp.asarray(cursor, jnp.int32)
    batch_shape = spikes.shape[:-1]

    # queue admission — compact_events truncation in mask form: the first
    # ``capacity`` active sources (ascending id = arbiter scan order) win
    with jax.named_scope("compact"):
        active = spikes != 0
        cap = n if queue_capacity is None else min(int(queue_capacity), n)
        if cap >= n:
            in_q = active
            dropped = jnp.zeros(batch_shape, jnp.int32)
        else:
            pos = _prefix_sum(active.astype(jnp.int32))
            in_q = active & (pos <= cap)
            dropped = jnp.maximum(pos[..., -1] - cap, 0)

    act_all = jnp.take(in_q, entries.src, axis=-1) & entries.valid  # [..., M]
    # fault-severed entries (DESIGN.md §15) always drop — counted with the
    # link drops (a dead link is a zero-capacity link) — and never contend
    # for a live link's FIFO slots
    act_e = act_all & entries.alive
    fault_mask = act_all & ~entries.alive

    # per-directed-link FIFO arbitration without a sort: entries are already
    # in the arbiter's scan order, so an active cross-tile entry's FIFO
    # position is the count of active cross-tile entries since its link start
    if link_capacity is None:
        kept = act_e
        drop_mask = fault_mask
    else:
        with jax.named_scope("link_arbitration"):
            cnt = (act_e & entries.cross).astype(jnp.int32)
            excl = _prefix_sum(cnt) - cnt
            pos_in_link = excl - jnp.take(excl, entries.link_start, axis=-1)
            keep_cross = pos_in_link < link_capacity
            kept = act_e & (~entries.cross | keep_cross)
            # disjoint masks (alive vs severed), so the union's per-bin
            # counts sum to exactly the scalar fault + overflow totals
            drop_mask = fault_mask | (act_e & entries.cross & ~keep_cross)

    if per_link_stats:
        if n_tiles is None:
            raise ValueError("per_link_stats=True requires n_tiles")
        link_dropped = _count_bins(drop_mask, entries.link, n_tiles * n_tiles)
        pair = (entries.src // cluster_size) * n_clusters + entries.dstk // k_tags
        delivered = _count_bins(kept, pair, n_clusters * n_clusters)
    else:
        link_dropped = drop_mask.sum(-1, dtype=jnp.int32)
        delivered = kept.sum(-1, dtype=jnp.int32)

    stats = DeliveryStats(
        dropped=dropped,
        link_dropped=link_dropped,
        delivered=delivered,
        hops=jnp.where(kept, entries.hops, 0).sum(-1, dtype=jnp.int32),
        latency_s=jnp.where(kept, entries.latency_s, 0.0).sum(-1, dtype=jnp.float32),
        energy_j=jnp.where(kept, entries.energy_j, 0.0).sum(-1, dtype=jnp.float32),
    )

    # delay-indexed scatter targets on the wheel; dropped/silent entries
    # carry weight exactly 0 (their flat target stays in range — adding 0.0
    # is the no-op, so no sentinel slot is needed)
    w = jnp.take(spikes, entries.src, axis=-1) * kept.astype(spikes.dtype)
    slot = (cursor + entries.delay) % d1
    flat = slot * (n_clusters * k_tags) + entries.dstk  # [M], batch-shared

    if interpret is None and jax.default_backend() != "tpu":
        drive, ring = _ring_update_jnp(
            ring, flat, w, cursor, external_activity, cam_tag, cam_syn,
            cluster_size, k_tags, d1, syn_onehot,
        )
    else:
        if external_activity is None:
            external_activity = jnp.zeros(
                (*batch_shape, n_clusters, k_tags), w.dtype
            )
        drive, ring = fabric_deliver_ring_pallas(
            flat, w, ring, cursor, external_activity, cam_tag, cam_syn,
            cluster_size, k_tags, max_delay, block_c=block_c,
            interpret=bool(interpret),
        )
    return drive, ring, (cursor + 1) % d1, stats
