"""Generic two-stage tag dispatch in JAX (the paper's §II scheme, executable).

Stage 1 (point-to-point, "R1-SRAM -> fabric"): every active source emits its
stage-1 entries ``(tag, dest_cluster)``; all events are accumulated into a
tag-activity matrix ``A[n_clusters, K]`` — entry ``A[c, t]`` is the summed
event weight arriving at cluster ``c`` under tag ``t`` this step. On hardware
this is the SRAM memory-address loop + mesh routing; on TPU it is a
scatter-add, and across devices a reduce-scatter over the cluster axis
(each device owns a contiguous slab of clusters = "cores").

Stage 2 (broadcast + CAM match, "R1 -> core"): each cluster broadcasts its
activity row to all member neurons; every CAM word that matches contributes
its event weight to the synapse-type accumulator of its neuron. This is the
compute hot-spot and has Pallas kernels (kernels/cam_match and the fused
kernels/fused_deliver); the functions here are the pure-jnp implementations
used as reference and CPU fallback.

Both stages are **batch-native** (DESIGN.md §9): ``spikes`` may carry any
leading batch shape ``[..., N]`` (many concurrent event streams / network
instances over shared routing tables), producing ``A[..., n_clusters, K]``
and drive ``[..., N, 4]``.

**Event-sparse delivery** (DESIGN.md §10): the fabric carries *events*, not
dense activity — on the chip only neurons that spiked occupy the AER bus.
:func:`compact_events` models the core's output FIFO: active sources are
compacted (in arbiter scan order) into a fixed-capacity ``(src, weight)``
queue with an overflow/drop counter matching the chip's congestion
behavior. :func:`stage1_route_events` then scatters only the queued events'
SRAM entries, so stage-1 cost scales with event count, not network size.

**Fabric-mode stage 1** (DESIGN.md §11): :func:`stage1_route_events_fabric`
bins the queued events by (source, destination) tile pair, arbitrates each
directed inter-tile link's bandwidth FIFO (via :func:`dispatch_slots`, bins =
tile pairs), and scatters survivors into a delay-indexed buffer so cross-tile
events arrive hop-latency steps later.

The same functions implement MoE dispatch in models/moe.py:
clusters = expert groups, tags = expert ids, CAM subscription = expert
residency; :func:`dispatch_slots` is the shared sort-based slot assignment.
See DESIGN.md §3.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

__all__ = [
    "stage1_route",
    "stage2_cam_match",
    "compact_events",
    "stage1_route_events",
    "stage1_route_events_fabric",
    "FabricRouteResult",
    "gather_event_entries",
    "precompute_syn_onehot",
    "dispatch_slots",
    "EventQueue",
    "N_SYN_TYPES",
]

N_SYN_TYPES = 4  # fast-exc, slow-exc, subtractive-inh, shunting-inh

_INT32_MAX = jnp.iinfo(jnp.int32).max


# ---------------------------------------------------------------------------
# AER event queue (the core's output FIFO)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EventQueue:
    """Fixed-capacity compaction of one step's active sources.

    ``src[..., Q]`` holds source neuron ids in arbiter scan order (lowest id
    first — the chip's priority encoder), ``-1`` marks empty slots past the
    last event. ``weight`` is the event weight (``spikes[src]``); ``dropped``
    counts events that did not fit (the FIFO-overflow / congestion counter).
    """

    src: jax.Array  # [..., Q] int32, -1 = empty
    weight: jax.Array  # [..., Q]
    dropped: jax.Array  # [...] int32


jax.tree_util.register_dataclass(
    EventQueue, data_fields=["src", "weight", "dropped"], meta_fields=[]
)


@jax.named_scope("compact")
def compact_events(spikes: jax.Array, capacity: int) -> EventQueue:
    """Compact active spikes into a fixed-capacity AER queue (jit-able).

    The hardware analogue is the core's arbitrated output FIFO: sources are
    scanned in id order and the first ``capacity`` active ones win the bus;
    the rest are dropped and counted. Source ``i`` is the ``pos[i]``-th
    active one (``pos`` the running active count), so when it is active and
    ``pos[i] <= Q`` it writes its id and weight to queue slot ``pos[i] - 1``
    in one scatter with no loop and no gather. Every other source indexes
    past the end of the queue, where the scatter drops it, so no two writes
    meet; slots nobody writes stay empty.
    """
    n = spikes.shape[-1]
    q = min(int(capacity), n)
    if q <= 0:
        raise ValueError(f"queue capacity must be positive, got {capacity}")
    batch_shape = spikes.shape[:-1]
    b = math.prod(batch_shape)
    if b * q > _INT32_MAX:
        raise ValueError(f"{b} queues of {q} slots exceed int32 indexing")
    rows = spikes.reshape(b, n)  # one row per stream
    active = rows != 0
    pos = jnp.cumsum(active, axis=-1, dtype=jnp.int32)  # running active count
    # one 1-D scatter for all streams: stream r's queue is flat [r*Q, (r+1)*Q)
    first = jnp.arange(b, dtype=jnp.int32)[:, None] * q
    dest = jnp.where(active & (pos <= q), first + pos - 1, b * q).reshape(-1)
    ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), rows.shape)
    src = jnp.full((b * q,), -1, jnp.int32)
    src = src.at[dest].set(ids.reshape(-1), mode="drop").reshape(b, q)
    weight = jnp.zeros((b * q,), spikes.dtype)
    weight = weight.at[dest].set(rows.reshape(-1), mode="drop").reshape(b, q)
    n_active = pos[:, -1]
    dropped = n_active - jnp.minimum(n_active, q)
    return EventQueue(
        src=src.reshape(*batch_shape, q),
        weight=weight.reshape(*batch_shape, q),
        dropped=dropped.reshape(batch_shape),
    )


def gather_event_entries(
    queue: EventQueue,
    src_tag: jax.Array,  # [N, E] int32, -1 = empty
    src_dest: jax.Array,  # [N, E] int32 cluster ids
) -> tuple[jax.Array, jax.Array]:
    """Fetch the queued events' SRAM rows: ``(ev_tag, ev_dest) [..., Q, E]``.

    This is the per-event "SRAM memory-address loop": only queued sources'
    entries are read. Empty queue slots yield ``ev_tag = -1`` rows.
    """
    safe = jnp.clip(queue.src, 0, src_tag.shape[0] - 1)
    ev_tag = jnp.take(src_tag, safe, axis=0)  # [..., Q, E]
    ev_dest = jnp.take(src_dest, safe, axis=0)
    ev_tag = jnp.where(queue.src[..., None] >= 0, ev_tag, -1)
    return ev_tag, ev_dest


# ---------------------------------------------------------------------------
# stage 1 — scatter-add into the tag-activity matrix
# ---------------------------------------------------------------------------
def _accumulate_activity(
    flat: jax.Array,  # [B, M] int32 per-batch flat indices; invalid -> size
    weights: jax.Array,  # [B, M]
    size: int,
    _force_path: str | None = None,  # tests only: "flat32" | "flat64" | "2d"
) -> jax.Array:  # [B, size]
    """Batched scatter-add into per-batch activity slabs, int32-overflow-safe.

    The fast path linearizes (batch, slot) into one flat index so the whole
    batch is a single 1-D scatter. When ``b * (size + 1)`` exceeds the int32
    range that index would wrap, so offsets are computed in int64 when x64 is
    enabled, and otherwise the scatter falls back to 2-D (batch, slot)
    indices — each component stays comfortably within int32.
    """
    b, _ = flat.shape
    span = size + 1  # slot ``size`` absorbs invalid entries
    path = _force_path
    if path is None:
        if b * span - 1 <= _INT32_MAX:
            path = "flat32"
        elif jax.config.jax_enable_x64:
            path = "flat64"
        else:
            path = "2d"
    if path in ("flat32", "flat64"):
        dt = jnp.int32 if path == "flat32" else jnp.int64
        offsets = jnp.arange(b, dtype=dt)[:, None] * span
        flat_b = flat.astype(dt) + offsets
        a = jnp.zeros((b * span,), dtype=weights.dtype)
        a = a.at[flat_b.reshape(-1)].add(weights.reshape(-1), mode="drop")
        return a.reshape(b, span)[:, :size]
    bidx = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None], flat.shape)
    a = jnp.zeros((b, span), dtype=weights.dtype)
    a = a.at[bidx.reshape(-1), flat.reshape(-1)].add(weights.reshape(-1), mode="drop")
    return a[:, :size]


def _accumulate_into(
    buf: jax.Array,  # [B, size] existing per-batch accumulator (e.g. the ring)
    flat: jax.Array,  # [B, M] or [M] int32 in-range flat indices
    weights: jax.Array,  # [B, M]
    _force_path: str | None = None,  # tests only: "flat32" | "flat64" | "2d"
) -> jax.Array:  # [B, size]
    """Scatter-add into an EXISTING accumulator, int32-overflow-safe.

    The time-wheel ring fast path (kernels/fabric_deliver) scatters each
    step's events into the carried ring buffer in place — unlike
    :func:`_accumulate_activity` there is no sentinel slot, so every index
    must already be in ``[0, size)`` and masked-out events must carry weight
    exactly 0 (adding 0.0 is the no-op). Path selection mirrors
    :func:`_accumulate_activity`: flat int32 offsets while they fit, int64
    under x64, else 2-D (batch, slot) indices.
    """
    b, size = buf.shape
    if flat.ndim == 1:
        flat = jnp.broadcast_to(flat[None, :], (b, flat.shape[0]))
    path = _force_path
    if path is None:
        if b * size - 1 <= _INT32_MAX:
            path = "flat32"
        elif jax.config.jax_enable_x64:
            path = "flat64"
        else:
            path = "2d"
    if path in ("flat32", "flat64"):
        dt = jnp.int32 if path == "flat32" else jnp.int64
        offsets = jnp.arange(b, dtype=dt)[:, None] * size
        flat_b = flat.astype(dt) + offsets
        a = buf.reshape(b * size)
        a = a.at[flat_b.reshape(-1)].add(weights.reshape(-1), mode="drop")
        return a.reshape(b, size)
    bidx = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None], flat.shape)
    return buf.at[bidx.reshape(-1), flat.reshape(-1)].add(
        weights.reshape(-1), mode="drop"
    )


def _scatter_count(
    mask: jax.Array,  # [..., Q, E] bool events to count
    bins: jax.Array,  # [..., Q, E] int32 bin per event (value under ~mask ignored)
    size: int,
) -> jax.Array:  # [..., size] int32
    """Per-bin event counts — the attribution-preserving form of ``mask.sum()``.

    Used by the ``per_link_stats`` mode of :func:`stage1_route_events_fabric`
    to keep drops per directed link and deliveries per cluster pair instead
    of collapsing them to scalars. Masked-out events land in a sentinel slot
    that is sliced off, so out-of-range bins never alias a real counter.
    """
    flat = jnp.where(mask, jnp.clip(bins, 0, size - 1), size)
    counts = mask.astype(jnp.int32)
    batch_shape = mask.shape[:-2]
    if not batch_shape:
        out = jnp.zeros((size + 1,), jnp.int32)
        out = out.at[flat.reshape(-1)].add(counts.reshape(-1), mode="drop")
        return out[:size]
    b = math.prod(batch_shape)
    m = mask.shape[-2] * mask.shape[-1]
    out = _accumulate_activity(flat.reshape(b, m), counts.reshape(b, m), size)
    return out.reshape(*batch_shape, size)


@jax.named_scope("stage1")
def stage1_route(
    spikes: jax.Array,  # [..., N] float event weights (0/1 spikes or rates)
    src_tag: jax.Array,  # [N, E] int32, -1 = empty
    src_dest: jax.Array,  # [N, E] int32 cluster ids
    n_clusters: int,
    k_tags: int,
) -> jax.Array:
    """Scatter stage-1 events into the tag-activity matrix ``A[..., n_clusters, K]``.

    Dense path: all ``N x E`` SRAM entries are scattered regardless of
    activity (cost scales with network size). For event-sparse delivery use
    :func:`compact_events` + :func:`stage1_route_events` instead. The routing
    tables are shared across the batch; each batch element scatters into its
    own slab of a single flat accumulator.
    """
    valid = src_tag >= 0
    size = n_clusters * k_tags
    # flat index into A; invalid entries are routed to a sentinel slot.
    flat = jnp.where(valid, src_dest * k_tags + src_tag, size)  # [N, E]
    weights = spikes[..., None] * valid.astype(spikes.dtype)  # [..., N, E]
    batch_shape = spikes.shape[:-1]
    if not batch_shape:
        a = jnp.zeros((size,), dtype=spikes.dtype)
        a = a.at[flat.reshape(-1)].add(weights.reshape(-1), mode="drop")
        return a.reshape(n_clusters, k_tags)
    b = math.prod(batch_shape)
    flat_b = jnp.broadcast_to(flat.reshape(1, -1), (b, flat.size))
    a = _accumulate_activity(flat_b, weights.reshape(b, -1), size)
    return a.reshape(*batch_shape, n_clusters, k_tags)


@jax.named_scope("stage1")
def stage1_route_events(
    queue: EventQueue,  # src [..., Q], weight [..., Q]
    src_tag: jax.Array,  # [N, E]
    src_dest: jax.Array,  # [N, E]
    n_clusters: int,
    k_tags: int,
) -> jax.Array:
    """Event-sparse stage 1: scatter only the queued events' SRAM entries.

    Cost is ``O(Q x E)`` per stream — event count, not network size. Produces
    the same ``A[..., n_clusters, K]`` as :func:`stage1_route` whenever the
    queue holds every active source (no overflow).
    """
    ev_tag, ev_dest = gather_event_entries(queue, src_tag, src_dest)
    valid = ev_tag >= 0
    size = n_clusters * k_tags
    flat = jnp.where(valid, ev_dest * k_tags + ev_tag, size)  # [..., Q, E]
    weights = queue.weight[..., None] * valid.astype(queue.weight.dtype)
    batch_shape = queue.src.shape[:-1]
    if not batch_shape:
        a = jnp.zeros((size,), dtype=weights.dtype)
        a = a.at[flat.reshape(-1)].add(weights.reshape(-1), mode="drop")
        return a.reshape(n_clusters, k_tags)
    b = math.prod(batch_shape)
    a = _accumulate_activity(flat.reshape(b, -1), weights.reshape(b, -1), size)
    return a.reshape(*batch_shape, n_clusters, k_tags)


# ---------------------------------------------------------------------------
# stage 1, fabric mode — tile binning, link FIFOs, delay-indexed scatter
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FabricRouteResult:
    """Outcome of one fabric-mode stage-1 pass (DESIGN.md §11).

    ``buffer[..., d, c, t]`` is the tag activity arriving at cluster ``c``
    under tag ``t`` in ``d`` steps (``d = 0`` = this step); ``link_dropped``
    counts events lost to inter-tile link-FIFO overflow; ``delivered``
    counts routed (kept) events. ``hops`` / ``latency_s`` / ``energy_j``
    are per-step sums over delivered events of the Table II-IV per-event
    figures (``None`` when the matrices were not supplied).

    With ``per_link_stats`` (DESIGN.md §18) the two counters keep their
    attribution instead of collapsing to scalars: ``link_dropped`` becomes
    ``[..., n_tiles * n_tiles]`` (flat directed tile pair; fault drops of
    intra-tile entries land on the ``src == dst`` diagonal) and
    ``delivered`` becomes ``[..., n_clusters * n_clusters]`` (flat
    (src_cluster, dst_cluster) pair — the observed traffic matrix). Both
    sum over their trailing axis to exactly the scalar-mode values.
    """

    buffer: jax.Array  # [..., max_delay + 1, n_clusters, K]
    link_dropped: jax.Array  # [...] int32, or [..., T*T] per-link
    delivered: jax.Array  # [...] int32, or [..., nc*nc] per-pair
    hops: jax.Array | None = None  # [...] int32
    latency_s: jax.Array | None = None  # [...] float32
    energy_j: jax.Array | None = None  # [...] float32


jax.tree_util.register_dataclass(
    FabricRouteResult,
    data_fields=["buffer", "link_dropped", "delivered", "hops", "latency_s", "energy_j"],
    meta_fields=[],
)


@jax.named_scope("stage1")
def stage1_route_events_fabric(
    queue: EventQueue,  # src [..., Q] LOCAL neuron ids into src_tag's rows
    src_tag: jax.Array,  # [N_local, E]
    src_dest: jax.Array,  # [N_local, E] GLOBAL destination cluster ids
    n_clusters: int,  # global cluster count
    k_tags: int,
    cluster_size: int,
    cluster_tile: jax.Array,  # [n_clusters] int32 linear tile id per cluster
    delay_steps: jax.Array,  # [n_clusters, n_clusters] int32 arrival delays
    n_tiles: int,
    max_delay: int,
    link_capacity: int | None,  # events per directed tile pair per step; None = inf
    mesh_hops: jax.Array | None = None,  # [nc, nc] optional stats matrices
    latency_s: jax.Array | None = None,
    energy_j: jax.Array | None = None,
    src_cluster_offset: int | jax.Array = 0,  # sharded: global id of local cluster 0
    cursor: jax.Array | None = None,  # time-wheel write cursor (ring addressing)
    entry_alive: jax.Array | None = None,  # [N_local, E] bool fault mask (§15)
    per_link_stats: bool = False,  # keep drop/delivered attribution (§18)
) -> FabricRouteResult:
    """Event-sparse stage 1 through the R1/R2/R3 fabric.

    The zero-latency :func:`stage1_route_events` scatters every queued
    event's SRAM entries straight into this step's activity. Here each entry
    is first *binned by its (source tile, destination tile) pair*:

      * intra-tile entries (R1/R2 only) keep the zero-latency path — they
        land in ``buffer[0]``;
      * cross-tile entries contend for their directed link's FIFO — the
        first ``link_capacity`` events per link (arbiter order: queue slot
        order, i.e. lowest source id first) win, the rest are dropped and
        counted (:func:`dispatch_slots` semantics, bins = tile pairs);
      * surviving cross-tile entries land ``delay_steps[src, dst]`` slots
        deep in the buffer — the delay line the engine's scan carries.

    Per-event stats are summed over *delivered* entries only (each SRAM
    entry is one AER event on the fabric, regardless of its weight).

    With ``cursor`` set, the buffer is addressed as a **time-wheel ring**
    (DESIGN.md §14): an event with arrival delay ``d`` lands in slot
    ``(cursor + d) % (max_delay + 1)`` instead of slot ``d``, so the caller
    can carry the buffer across steps with a pointer bump instead of the
    dense :func:`~repro.core.dispatch.advance_inflight` shift. Everything
    else — arbitration, drops, stats — is bit-identical to the roll layout.

    With ``per_link_stats`` the drop and delivered counters are scattered
    instead of summed (see :class:`FabricRouteResult`): link-FIFO drops at
    their directed (src_tile, dst_tile) link, fault drops at the same link
    (or the tile's self-link diagonal for intra-tile entries, so the
    per-link sum stays exactly equal to the scalar mode), and delivered
    events at their (src_cluster, dst_cluster) pair — the empirical traffic
    matrix that feeds :class:`repro.core.compiler.TrafficProfile`.

    ``entry_alive`` is the static per-SRAM-entry fault mask of
    :func:`repro.core.faults.entry_alive_mask`: a ``False`` entry's events
    are dropped before link arbitration (they never consume a live link's
    FIFO slots) and counted in ``link_dropped`` — a dead link is a
    zero-capacity link. Same semantics as the severed entries of the ring
    fast path, so ring/roll parity holds under faults too.
    """
    ev_tag, ev_dest = gather_event_entries(queue, src_tag, src_dest)  # [..., Q, E]
    valid = ev_tag >= 0
    fault_mask = None
    if entry_alive is not None:
        safe = jnp.clip(queue.src, 0, src_tag.shape[0] - 1)
        ev_alive = jnp.take(entry_alive, safe, axis=0)  # [..., Q, E]
        fault_mask = valid & ~ev_alive
        valid = valid & ev_alive
    src_cl = jnp.where(
        queue.src >= 0, queue.src // cluster_size + src_cluster_offset, 0
    ).astype(jnp.int32)
    src_cl_e = jnp.broadcast_to(src_cl[..., None], ev_tag.shape)  # [..., Q, E]
    dst_cl = jnp.clip(ev_dest, 0, n_clusters - 1)
    pair = src_cl_e * n_clusters + dst_cl  # flat [nc, nc] index
    src_tile = jnp.take(cluster_tile, src_cl_e, mode="clip")
    dst_tile = jnp.take(cluster_tile, dst_cl, mode="clip")
    cross = (src_tile != dst_tile) & valid

    if link_capacity is None:
        keep_cross = jnp.ones_like(cross)
    else:
        with jax.named_scope("link_arbitration"):
            bins = jnp.where(cross, src_tile * n_tiles + dst_tile, -1)
            batch_shape = bins.shape[:-2]
            flat_bins = bins.reshape(-1, bins.shape[-2] * bins.shape[-1])
            _, keep_flat = jax.vmap(
                lambda e: dispatch_slots(e, n_tiles * n_tiles, link_capacity)
            )(flat_bins)
            keep_cross = keep_flat.reshape(*batch_shape, *bins.shape[-2:])

    kept = valid & (~cross | keep_cross)
    if per_link_stats:
        link_bins = src_tile * n_tiles + dst_tile
        link_dropped = _scatter_count(cross & ~keep_cross, link_bins, n_tiles * n_tiles)
        if fault_mask is not None:
            # intra-tile fault drops land on the tile's self-link diagonal so
            # the per-link sum equals the scalar-mode count exactly
            fault_bins = jnp.where(
                src_tile != dst_tile, link_bins, src_tile * n_tiles + src_tile
            )
            link_dropped = link_dropped + _scatter_count(
                fault_mask, fault_bins, n_tiles * n_tiles
            )
        delivered = _scatter_count(kept, pair, n_clusters * n_clusters)
    else:
        link_dropped = (cross & ~keep_cross).sum((-1, -2), dtype=jnp.int32)
        if fault_mask is not None:
            link_dropped = link_dropped + fault_mask.sum((-1, -2), dtype=jnp.int32)
        delivered = kept.sum((-1, -2), dtype=jnp.int32)

    delay = jnp.take(delay_steps.reshape(-1), pair, mode="clip")
    slot = delay if cursor is None else (cursor + delay) % (max_delay + 1)
    size = (max_delay + 1) * n_clusters * k_tags
    flat = jnp.where(
        kept, (slot * n_clusters + dst_cl) * k_tags + jnp.clip(ev_tag, 0), size
    )
    weights = queue.weight[..., None] * kept.astype(queue.weight.dtype)
    batch_shape = queue.src.shape[:-1]
    if not batch_shape:
        a = jnp.zeros((size,), dtype=weights.dtype)
        a = a.at[flat.reshape(-1)].add(weights.reshape(-1), mode="drop")
    else:
        b = math.prod(batch_shape)
        a = _accumulate_activity(flat.reshape(b, -1), weights.reshape(b, -1), size)
    buffer = a.reshape(*batch_shape, max_delay + 1, n_clusters, k_tags)

    def _sum_over_kept(matrix, dtype):
        if matrix is None:
            return None
        vals = jnp.take(matrix.reshape(-1), pair, mode="clip")
        return jnp.where(kept, vals, 0).sum((-1, -2), dtype=dtype)

    return FabricRouteResult(
        buffer=buffer,
        link_dropped=link_dropped,
        delivered=delivered,
        hops=_sum_over_kept(mesh_hops, jnp.int32),
        latency_s=_sum_over_kept(latency_s, jnp.float32),
        energy_j=_sum_over_kept(energy_j, jnp.float32),
    )


# ---------------------------------------------------------------------------
# stage 2 — broadcast + CAM match
# ---------------------------------------------------------------------------
def precompute_syn_onehot(cam_syn: jax.Array, dtype=jnp.float32) -> jax.Array:
    """One-hot synapse-type plane ``[N, S, N_SYN_TYPES]`` for stage 2.

    A per-table constant (the CAM's synapse-type wiring never changes at
    run time) — precompute once and pass to :func:`stage2_cam_match` to keep
    the one-hot expansion out of the per-step cost.
    """
    return jax.nn.one_hot(cam_syn, N_SYN_TYPES, dtype=dtype)


@jax.named_scope("stage2")
def stage2_cam_match(
    activity: jax.Array,  # [..., n_clusters, K]
    cam_tag: jax.Array,  # [N, S] int32, -1 = empty
    cam_syn: jax.Array,  # [N, S] int32 in [0, N_SYN_TYPES)
    cluster_size: int,
    syn_onehot: jax.Array | None = None,  # [N, S, N_SYN_TYPES] precomputed
) -> jax.Array:
    """Broadcast + CAM match: returns synaptic drive ``I[..., N, N_SYN_TYPES]``.

    Pure-jnp reference. CAM word ``(j, s)`` reads exactly one activity cell —
    ``activity[cluster_of(j), cam_tag[j, s]]`` — so the gather is a direct
    advanced-indexing ``take`` on the flattened activity; no intermediate
    ``[..., n_clusters, cluster_size, K]`` broadcast is ever materialized
    (that tensor is ~1 GB at B=64 on the benchmark geometry). The Pallas
    kernels in kernels/cam_match and kernels/fused_deliver compute the same
    quantity with the activity row pinned in VMEM.
    """
    n, s = cam_tag.shape
    n_clusters, k = activity.shape[-2:]
    batch_shape = activity.shape[:-2]
    assert n == n_clusters * cluster_size, (n, n_clusters, cluster_size)
    valid = cam_tag >= 0
    # flat (cluster, tag) address of each CAM word; invalid words clamped.
    cluster_of_word = jnp.arange(n, dtype=jnp.int32)[:, None] // cluster_size
    flat_word = cluster_of_word * k + jnp.clip(cam_tag, 0, k - 1)  # [N, S]
    act_flat = activity.reshape(*batch_shape, n_clusters * k)
    vals = jnp.take(act_flat, flat_word, axis=-1, mode="clip")  # [..., N, S]
    vals = jnp.where(valid, vals, jnp.zeros((), activity.dtype))
    if syn_onehot is None:
        syn_onehot = precompute_syn_onehot(cam_syn, dtype=vals.dtype)
    # HIGHEST: a TPU would otherwise round the f32 operands to bf16
    out = jnp.einsum(
        "...ns,nst->...nt",
        vals,
        syn_onehot.astype(vals.dtype),
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.reshape(*batch_shape, n, N_SYN_TYPES)


# ---------------------------------------------------------------------------
# shared sort-based slot assignment (AER queue / MoE expert buffers)
# ---------------------------------------------------------------------------
def dispatch_slots(flat_e: jax.Array, n_bins: int, cap: int):
    """Assign each event a slot in its bin's fixed-capacity buffer.

    ``flat_e [A]`` is a bin id per event (out-of-range = inactive); returns
    ``(slot [A], keep [A])`` where ``slot = bin * cap + position`` for the
    first ``cap`` events of each bin (stable order) and ``keep`` masks the
    rest — the same FIFO-overflow semantics as :func:`compact_events`, for
    many bins at once. Used by the MoE expert-dispatch path (models/moe.py),
    where bins are experts/shards and ``cap`` is the expert capacity.
    """
    a = flat_e.shape[0]
    # normalize inactive markers: a negative bin would sort BEFORE the valid
    # bins (inflating their in-bin positions) and wrap in the counts scatter —
    # fold them onto the high sentinel the masking already handles
    flat_e = jnp.where(flat_e < 0, n_bins, flat_e)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((n_bins,), jnp.int32).at[sorted_e].add(1, mode="drop")
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(a, dtype=jnp.int32) - starts[sorted_e]
    keep = (pos_in_e < cap) & (sorted_e >= 0) & (sorted_e < n_bins)
    slot_sorted = jnp.where(keep, sorted_e * cap + pos_in_e, -1)
    # undo the sort: slot for the original assignment order
    slot = jnp.zeros((a,), jnp.int32).at[order].set(slot_sorted)
    return slot, slot >= 0
