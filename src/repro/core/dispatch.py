"""Pluggable dispatch backends for batched event delivery (DESIGN.md §9/§10).

A dispatch backend turns (spikes, routing tables, external tag activity)
into per-neuron synaptic drive — the full stage-1 + stage-2 path of the
paper — for a whole batch of concurrent event streams at once. All backends
consume ``spikes [..., N]`` / ``external_activity [..., n_clusters, K]`` and
return ``drive [..., N, N_SYN_TYPES]``; they differ in *where* the stage-2
CAM match runs and whether the two stages are fused:

  * ``reference`` — pure-jnp scatter + indexed gather (oracle, CPU default)
  * ``pallas``    — the kernels/cam_match TPU kernel, grid (B, cluster,
                    neuron-tile): the activity row stays VMEM-pinned per
                    cluster while neurons and batch tile the MXU
  * ``fused``     — the kernels/fused_deliver TPU kernel: stage-1 scatter
                    AND stage-2 CAM match in one kernel, the activity row
                    built and consumed in VMEM without an HBM round-trip;
                    always event-queued (DESIGN.md §10)
  * ``sharded``   — shard_map over a 2-D mesh (batch over ``data``,
                    clusters over ``model``): stage-1 partials are
                    reduce-scattered to the owning cluster slab (the
                    R2/R3 point-to-point hop), stage-2 is fully local
  * ``fabric``    — latency/bandwidth-aware delivery through the executable
                    R1/R2/R3 model (DESIGN.md §11): tile binning, per-link
                    FIFOs, delay lines, Table II-IV stats accumulators

Every backend supports **event-sparse delivery**: pass ``queue_capacity`` to
compact active spikes into a fixed-capacity AER queue (core/two_stage.py)
and scatter only queued events' SRAM entries in stage 1. ``with_stats=True``
additionally returns a :class:`DeliveryStats` with the queue's drop counter
(the chip's congestion behavior).

Backends are selected by name through :func:`get_backend` from the fixed
set above.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.two_stage import (
    N_SYN_TYPES,
    compact_events,
    stage1_route,
    stage1_route_events,
    stage1_route_events_fabric,
    stage2_cam_match,
)

__all__ = [
    "DispatchBackend",
    "DeliveryStats",
    "ReferenceBackend",
    "PallasBackend",
    "FusedBackend",
    "ShardedBackend",
    "FabricBackend",
    "advance_inflight",
    "get_backend",
    "available_backends",
    "two_stage_deliver",
]


@dataclasses.dataclass(frozen=True)
class DeliveryStats:
    """Per-stream delivery statistics.

    ``dropped [...]`` int32 counts events lost to AER-queue overflow this
    step (0 everywhere on the dense path). The remaining fields are filled
    only by the fabric backend (DESIGN.md §11) and stay ``None`` elsewhere:
    ``link_dropped`` counts events lost to inter-tile link-FIFO overflow,
    ``delivered`` counts routed events, and ``hops`` / ``latency_s`` /
    ``energy_j`` are per-step sums of the Table II-IV per-event figures
    over delivered events.
    """

    dropped: jax.Array
    link_dropped: jax.Array | None = None
    delivered: jax.Array | None = None
    hops: jax.Array | None = None
    latency_s: jax.Array | None = None
    energy_j: jax.Array | None = None


jax.tree_util.register_dataclass(
    DeliveryStats,
    data_fields=["dropped", "link_dropped", "delivered", "hops", "latency_s", "energy_j"],
    meta_fields=[],
)


def _stage1_activity(
    spikes: jax.Array,
    src_tag: jax.Array,
    src_dest: jax.Array,
    n_clusters: int,
    k_tags: int,
    queue_capacity: int | None,
) -> tuple[jax.Array, jax.Array]:
    """Stage-1 scatter, dense or event-queued: ``(activity, dropped)``."""
    if queue_capacity is None or queue_capacity >= spikes.shape[-1]:
        # capacity >= N makes the queue lossless AND makes compaction pure
        # overhead: the dense scatter visits the same nonzero entries in the
        # same (src, entry) order, adding only exact-0.0 terms for silent
        # sources — bit-identical activity, zero drops, no cumsum/scatter
        a = stage1_route(spikes, src_tag, src_dest, n_clusters, k_tags)
        dropped = jnp.zeros(spikes.shape[:-1], jnp.int32)
        return a, dropped
    queue = compact_events(spikes, queue_capacity)
    a = stage1_route_events(queue, src_tag, src_dest, n_clusters, k_tags)
    return a, queue.dropped


class DispatchBackend:
    """Interface: batched stage-1 scatter shared, stage-2 pluggable."""

    # -- stage 2 -----------------------------------------------------------
    def cam_match(
        self,
        activity: jax.Array,  # [..., n_clusters, K]
        cam_tag: jax.Array,  # [N, S]
        cam_syn: jax.Array,  # [N, S]
        cluster_size: int,
        syn_onehot: jax.Array | None = None,  # [N, S, 4] per-table constant
    ) -> jax.Array:  # [..., N, N_SYN_TYPES]
        raise NotImplementedError

    # -- full delivery -----------------------------------------------------
    def deliver(
        self,
        spikes: jax.Array,  # [..., N]
        src_tag: jax.Array,
        src_dest: jax.Array,
        cam_tag: jax.Array,
        cam_syn: jax.Array,
        cluster_size: int,
        k_tags: int,
        external_activity: jax.Array | None = None,
        queue_capacity: int | None = None,
        syn_onehot: jax.Array | None = None,
        with_stats: bool = False,
    ):
        n = spikes.shape[-1]
        a, dropped = _stage1_activity(
            spikes, src_tag, src_dest, n // cluster_size, k_tags, queue_capacity
        )
        if external_activity is not None:
            a = a + external_activity
        drive = self.cam_match(a, cam_tag, cam_syn, cluster_size, syn_onehot)
        if with_stats:
            return drive, DeliveryStats(dropped=dropped)
        return drive


@dataclasses.dataclass(frozen=True)
class ReferenceBackend(DispatchBackend):
    """Pure-jnp stage 2 (direct indexed gather + synapse-type einsum)."""

    def cam_match(self, activity, cam_tag, cam_syn, cluster_size, syn_onehot=None):
        return stage2_cam_match(activity, cam_tag, cam_syn, cluster_size, syn_onehot)


@dataclasses.dataclass(frozen=True)
class PallasBackend(DispatchBackend):
    """Stage 2 on the kernels/cam_match Pallas kernel.

    ``interpret=None`` (default) follows the platform policy of
    kernels/cam_match/ops: compiled kernel on TPU, fast jnp reference on
    other platforms — same behavior the old ``use_kernel`` bool had.
    ``interpret=True`` forces the kernel in interpret mode anywhere
    (slow — CPU validation only). ``block_c`` tiles neurons within a
    cluster; see kernels/cam_match.
    """

    block_c: int = 16
    interpret: bool | None = None

    @jax.named_scope("stage2")
    def cam_match(self, activity, cam_tag, cam_syn, cluster_size, syn_onehot=None):
        # the kernel builds its compare planes in-register; the precomputed
        # one-hot is a jnp-path optimization and is ignored here.
        if self.interpret is None:
            from repro.kernels.cam_match import ops as cam_ops

            return cam_ops.cam_match(
                activity, cam_tag, cam_syn, cluster_size, block_c=self.block_c
            )
        from repro.kernels.cam_match.cam_match import cam_match_pallas

        return cam_match_pallas(
            activity, cam_tag, cam_syn, cluster_size, block_c=self.block_c,
            interpret=self.interpret,
        )


@dataclasses.dataclass(frozen=True)
class FusedBackend(DispatchBackend):
    """Single-kernel delivery: stage-1 scatter + stage-2 CAM match fused.

    The kernels/fused_deliver kernel builds each (batch, cluster) activity
    row in VMEM from the queued events and immediately CAM-matches it — the
    ``[B, n_clusters, K]`` activity matrix never round-trips HBM. Always
    event-queued: ``queue_capacity=None`` sizes the queue to N (lossless).

    ``interpret=None`` follows the platform policy of fused_deliver/ops
    (compiled kernel on TPU, jnp event-sparse reference elsewhere);
    ``interpret=True`` forces the kernel in interpret mode (CPU validation).
    """

    block_c: int = 16
    interpret: bool | None = None

    def cam_match(self, activity, cam_tag, cam_syn, cluster_size, syn_onehot=None):
        # stage 2 alone (no queue to fuse with): reference semantics.
        return stage2_cam_match(activity, cam_tag, cam_syn, cluster_size, syn_onehot)

    def deliver(
        self,
        spikes,
        src_tag,
        src_dest,
        cam_tag,
        cam_syn,
        cluster_size,
        k_tags,
        external_activity=None,
        queue_capacity=None,
        syn_onehot=None,
        with_stats=False,
    ):
        from repro.kernels.fused_deliver import ops as fused_ops

        capacity = spikes.shape[-1] if queue_capacity is None else queue_capacity
        queue = compact_events(spikes, capacity)
        drive = fused_ops.fused_deliver(
            queue,
            src_tag,
            src_dest,
            cam_tag,
            cam_syn,
            cluster_size,
            k_tags,
            external_activity=external_activity,
            syn_onehot=syn_onehot,
            block_c=self.block_c,
            interpret=self.interpret,
        )
        if with_stats:
            return drive, DeliveryStats(dropped=queue.dropped)
        return drive


def advance_inflight(buffer, inflight, max_delay: int):
    """Advance the fabric delay line one step: ``(activity_now, new_inflight)``.

    ``buffer [..., max_delay + 1, nc, K]`` is this step's routed scatter
    (slot 0 = arriving now); ``inflight [..., max_delay, nc, K]`` is the
    carried tail, or ``None`` to collapse every delay slot into the current
    step (the single-shot statistical mode — returns ``None`` back). Shared
    by :class:`FabricBackend` and the engine's sharded fabric step so local
    and sharded execution cannot drift.
    """
    if inflight is None:
        return buffer.sum(axis=-3), None
    if max_delay == 0:
        return buffer[..., 0, :, :], inflight  # inflight is empty [..., 0, nc, K]
    a = buffer[..., 0, :, :] + inflight[..., 0, :, :]
    shifted = jnp.concatenate(
        [inflight[..., 1:, :, :], jnp.zeros_like(inflight[..., :1, :, :])], axis=-3
    )
    return a, shifted + buffer[..., 1:, :, :]


class FabricBackend(DispatchBackend):
    """Latency/bandwidth-aware delivery over the R1/R2/R3 fabric (§11).

    Events are compacted into the AER queue, binned by (source, destination)
    tile pair, pushed through per-link bandwidth FIFOs
    (``r3_throughput_eps * dt`` events per directed tile pair per step,
    deterministic lowest-source-id-first overflow), and scattered into a
    delay-indexed activity buffer — cross-tile events arrive
    ``ceil(mesh_hops * latency_across_chip_s / dt)`` steps later.

    Two entry points:

    * :meth:`deliver` (the backend interface) models one *isolated* timestep:
      link capacity and the hop/latency/energy accounting apply, but with no
      delay line to thread the buffer is collapsed — every surviving event
      is delivered in the same step ("zero-warp" statistical mode). With
      infinite link capacity this is bit-identical to ``reference``.
    * :meth:`deliver_fabric` takes and returns the in-flight buffer
      (``[..., max_delay, n_clusters, K]``) so ``EventEngine(fabric=...)``
      can carry it through the scan — events then really arrive late.
    * :meth:`deliver_fabric_ring` is the **fast path** (DESIGN.md §14): the
      carried buffer is a time-wheel ring ``[..., max_delay + 1, nc, K]``
      indexed by a carried write cursor, delivery runs over a static
      per-SRAM-entry table (kernels/fabric_deliver), and advancing the delay
      line is a pointer bump — no dense shift. Bit-identical arrival steps,
      drops and integer stats to the roll path (locked by the ring property
      suite); the default mode of ``EventEngine(fabric=...)``.

    ``ring=False`` keeps the roll-based carry (the parity reference).
    ``tile_of_cluster`` pins the placement (default: hierarchical linear);
    per-event constants are precomputed once per cluster count
    (routing.build_delivery_model) and uploaded as jnp constants.
    ``interpret``/``block_c`` configure the fabric_deliver kernel exactly
    like :class:`FusedBackend` (None = kernel on TPU, jnp fast path
    elsewhere; True = force interpret mode for CPU validation).
    """

    def __init__(
        self,
        fabric=None,
        tile_of_cluster=None,
        dt: float = 1e-3,
        vdd: float = 1.3,
        link_capacity: int | None = None,
        ring: bool = True,
        block_c: int = 16,
        interpret: bool | None = None,
        faults=None,  # faults.FaultSpec | None — injected topology faults (§15)
        per_link_stats: bool = False,  # keep drop/delivered attribution (§18)
    ):
        from repro.core.routing import Fabric

        self.fabric = fabric if fabric is not None else Fabric()
        self.tile_of_cluster = tile_of_cluster
        self.dt = float(dt)
        self.vdd = vdd
        self.link_capacity = link_capacity
        self.ring = bool(ring)
        self.block_c = block_c
        self.interpret = interpret
        self.faults = faults
        self.per_link_stats = bool(per_link_stats)
        if faults is not None:
            faults.validate(self.fabric)
        self._models: dict[int, tuple] = {}
        self._entry_alive_cache: dict[tuple, jax.Array | None] = {}

    def model_for(self, n_clusters: int):
        """(FabricDeliveryModel, jnp constant arrays) for a cluster count."""
        cached = self._models.get(n_clusters)
        if cached is None:
            from repro.core.routing import build_delivery_model

            model = build_delivery_model(
                self.fabric,
                n_clusters,
                self.dt,
                tile_of_cluster=self.tile_of_cluster,
                vdd=self.vdd,
                link_capacity=self.link_capacity,
                faults=self.faults,
            )
            arrays = {
                "cluster_tile": jnp.asarray(model.tile_of_cluster),
                "delay_steps": jnp.asarray(model.delay_steps),
                "mesh_hops": jnp.asarray(model.mesh_hops),
                "latency_s": jnp.asarray(model.latency_s),
                "energy_j": jnp.asarray(model.energy_j),
            }
            cached = (model, arrays)
            self._models[n_clusters] = cached
        return cached

    def init_inflight(
        self,
        n_clusters: int,
        k_tags: int,
        batch: int | tuple[int, ...] | None = None,
        dtype=jnp.float32,
    ) -> jax.Array:
        """Zero in-flight buffer ``[..., max_delay, n_clusters, K]``."""
        model, _ = self.model_for(n_clusters)
        lead = () if batch is None else (batch,) if isinstance(batch, int) else tuple(batch)
        return jnp.zeros((*lead, model.max_delay, n_clusters, k_tags), dtype)

    def init_ring(
        self,
        n_clusters: int,
        k_tags: int,
        batch: int | tuple[int, ...] | None = None,
        dtype=jnp.float32,
    ) -> tuple[jax.Array, jax.Array]:
        """Zero time-wheel ring ``[..., max_delay + 1, nc, K]`` + cursor 0.

        The cursor is a shared int32 scalar — every batch slot steps in
        lockstep, so one phase pointer serves the whole pool (DESIGN.md §14).
        """
        model, _ = self.model_for(n_clusters)
        lead = () if batch is None else (batch,) if isinstance(batch, int) else tuple(batch)
        ring = jnp.zeros((*lead, model.max_delay + 1, n_clusters, k_tags), dtype)
        return ring, jnp.zeros((), jnp.int32)

    def build_entries(self, src_tag, src_dest, cluster_size: int, k_tags: int):
        """Static per-SRAM-entry table for the ring fast path (host-side).

        Precomputed once per engine from the routing tables + the delivery
        model: destination address, arrival delay, link bin and Table II-IV
        figures per *occupied* SRAM entry, statically sorted in arbitration
        order. See kernels/fabric_deliver/ops.py.
        """
        from repro.kernels.fabric_deliver import ops as fabric_ops

        n_clusters = src_tag.shape[0] // cluster_size
        model, _ = self.model_for(n_clusters)
        return fabric_ops.build_fabric_entries(
            src_tag, src_dest, cluster_size, k_tags, model
        )

    def entry_alive_for(self, src_tag, src_dest, cluster_size: int):
        """Per-SRAM-entry survival mask ``[N, E]`` (bool) or ``None``.

        ``None`` when no faults sever any route — the roll path then skips
        the per-event gather entirely. Cached per table identity so repeat
        engine builds don't redraw the erasure Bernoulli.
        """
        if self.faults is None or not self.faults.routes_faulted:
            return None
        src_tag = np.asarray(src_tag)
        src_dest = np.asarray(src_dest)
        key = (id(src_tag), id(src_dest), cluster_size)
        if key not in self._entry_alive_cache:
            from repro.core.faults import entry_alive_mask

            n_clusters = src_tag.shape[0] // cluster_size
            model, _ = self.model_for(n_clusters)
            mask = entry_alive_mask(src_tag, src_dest, cluster_size, model)
            self._entry_alive_cache[key] = None if mask is None else jnp.asarray(mask)
        return self._entry_alive_cache[key]

    def deliver_fabric_ring(
        self,
        spikes,
        entries,  # FabricEntries from build_entries
        cam_tag,
        cam_syn,
        cluster_size,
        k_tags,
        ring,  # [..., max_delay + 1, nc, K]
        cursor,  # int32 scalar write cursor
        external_activity=None,
        queue_capacity=None,
        syn_onehot=None,
    ):
        """Ring fast-path fabric step: ``(drive, ring, cursor, DeliveryStats)``.

        Event-count-proportional delivery over the static entry table —
        no per-step SRAM gather, no argsort arbitration, no dense delay-line
        shift. Kernel-fused on TPU (kernels/fabric_deliver), jnp fast path
        elsewhere; ``interpret=True`` at construction forces the kernel in
        interpret mode for CPU validation.
        """
        from repro.kernels.fabric_deliver import ops as fabric_ops

        n_clusters = spikes.shape[-1] // cluster_size
        model, _ = self.model_for(n_clusters)
        return fabric_ops.fabric_deliver_ring(
            spikes,
            entries,
            cam_tag,
            cam_syn,
            cluster_size,
            k_tags,
            ring,
            cursor,
            max_delay=model.max_delay,
            link_capacity=model.link_capacity,
            queue_capacity=queue_capacity,
            external_activity=external_activity,
            syn_onehot=syn_onehot,
            block_c=self.block_c,
            interpret=self.interpret,
            per_link_stats=self.per_link_stats,
            n_tiles=model.n_tiles,
        )

    def cam_match(self, activity, cam_tag, cam_syn, cluster_size, syn_onehot=None):
        return stage2_cam_match(activity, cam_tag, cam_syn, cluster_size, syn_onehot)

    def deliver_fabric(
        self,
        spikes,
        src_tag,
        src_dest,
        cam_tag,
        cam_syn,
        cluster_size,
        k_tags,
        inflight=None,  # [..., max_delay, n_clusters, K] or None (collapse delays)
        external_activity=None,
        queue_capacity=None,
        syn_onehot=None,
        entry_alive=None,  # [N, E] bool fault-survival mask (None → auto from faults)
    ):
        """Full fabric step: ``(drive, new_inflight, DeliveryStats)``.

        ``new_inflight`` is ``None`` when ``inflight`` was ``None`` (the
        collapsed single-shot mode used by :meth:`deliver`).
        """
        n = spikes.shape[-1]
        n_clusters = n // cluster_size
        model, arrs = self.model_for(n_clusters)
        if entry_alive is None and self.faults is not None:
            entry_alive = self.entry_alive_for(src_tag, src_dest, cluster_size)
        capacity = n if queue_capacity is None else queue_capacity
        queue = compact_events(spikes, capacity)
        route = stage1_route_events_fabric(
            queue,
            src_tag,
            src_dest,
            n_clusters,
            k_tags,
            cluster_size,
            arrs["cluster_tile"],
            arrs["delay_steps"],
            model.n_tiles,
            model.max_delay,
            model.link_capacity,
            mesh_hops=arrs["mesh_hops"],
            latency_s=arrs["latency_s"],
            energy_j=arrs["energy_j"],
            entry_alive=entry_alive,
            per_link_stats=self.per_link_stats,
        )
        a, new_inflight = advance_inflight(route.buffer, inflight, model.max_delay)
        if external_activity is not None:
            a = a + external_activity
        drive = stage2_cam_match(a, cam_tag, cam_syn, cluster_size, syn_onehot)
        stats = DeliveryStats(
            dropped=queue.dropped,
            link_dropped=route.link_dropped,
            delivered=route.delivered,
            hops=route.hops,
            latency_s=route.latency_s,
            energy_j=route.energy_j,
        )
        return drive, new_inflight, stats

    def deliver(
        self,
        spikes,
        src_tag,
        src_dest,
        cam_tag,
        cam_syn,
        cluster_size,
        k_tags,
        external_activity=None,
        queue_capacity=None,
        syn_onehot=None,
        with_stats=False,
    ):
        drive, _, stats = self.deliver_fabric(
            spikes,
            src_tag,
            src_dest,
            cam_tag,
            cam_syn,
            cluster_size,
            k_tags,
            inflight=None,
            external_activity=external_activity,
            queue_capacity=queue_capacity,
            syn_onehot=syn_onehot,
        )
        if with_stats:
            return drive, stats
        return drive


def sharded_local_deliver(
    spikes: jax.Array,  # [..., N_local] this device's neuron slab
    src_tag: jax.Array,
    src_dest: jax.Array,
    cam_tag: jax.Array,
    cam_syn: jax.Array,
    cluster_size: int,
    n_clusters: int,  # GLOBAL cluster count (stage-1 targets any cluster)
    k_tags: int,
    cluster_axis: str,
    external_activity: jax.Array | None = None,  # [..., n_clusters/n_dev, K]
    queue_capacity: int | None = None,
    syn_onehot: jax.Array | None = None,
    with_stats: bool = False,
):
    """Per-device delivery body shared by ShardedBackend and
    ``EventEngine.make_sharded_step`` (runs INSIDE shard_map).

    Stage 1 scatters this device's sources into a partial activity matrix
    covering ALL clusters; the reduce-scatter over ``cluster_axis`` hands
    each owner its slab (the R2/R3 point-to-point hop); stage 2 is local.

    With ``queue_capacity`` each device compacts its own slab's spikes — the
    hardware picture of one output FIFO per core. ``with_stats=True`` returns
    ``(drive, dropped)`` where ``dropped`` is already summed over the cluster
    axis (total events lost fabric-wide, replicated per device).
    """
    a_partial, dropped = _stage1_activity(
        spikes, src_tag, src_dest, n_clusters, k_tags, queue_capacity
    )
    a_local = jax.lax.psum_scatter(
        a_partial, cluster_axis, scatter_dimension=a_partial.ndim - 2, tiled=True
    )
    if external_activity is not None:
        a_local = a_local + external_activity
    drive = stage2_cam_match(a_local, cam_tag, cam_syn, cluster_size, syn_onehot)
    if with_stats:
        return drive, jax.lax.psum(dropped, cluster_axis)
    return drive


class ShardedBackend(DispatchBackend):
    """Full delivery under shard_map on a 2-D (batch, cluster) mesh.

    ``batch_axis`` shards event streams (data parallel — no communication),
    ``cluster_axis`` shards clusters/cores (model parallel — stage-1 partial
    activity is reduce-scattered to the slab owner, DESIGN.md §2). A 1x1
    default mesh makes the backend runnable — and testable — on one device.
    """

    def __init__(
        self,
        mesh: jax.sharding.Mesh | None = None,
        batch_axis: str = "data",
        cluster_axis: str = "model",
    ):
        if mesh is None:
            mesh = jax.make_mesh((1, 1), (batch_axis, cluster_axis))
        self.mesh = mesh
        self.batch_axis = batch_axis
        self.cluster_axis = cluster_axis

    def cam_match(self, activity, cam_tag, cam_syn, cluster_size, syn_onehot=None):
        # stage 2 alone is embarrassingly parallel; the interesting
        # communication lives in deliver(). Reference semantics here.
        return stage2_cam_match(activity, cam_tag, cam_syn, cluster_size, syn_onehot)

    def deliver(
        self,
        spikes,
        src_tag,
        src_dest,
        cam_tag,
        cam_syn,
        cluster_size,
        k_tags,
        external_activity=None,
        queue_capacity=None,
        syn_onehot=None,
        with_stats=False,
    ):
        from jax.sharding import PartitionSpec as P

        from repro.core.shard_compat import SM_CHECK_KW, shard_map

        # normalize any leading batch shape (incl. none) to one flat B
        batch_shape = spikes.shape[:-1]
        n = spikes.shape[-1]
        spikes = spikes.reshape(-1, n)
        b = spikes.shape[0]
        n_clusters = n // cluster_size
        n_cl_dev = self.mesh.shape[self.cluster_axis]
        n_b_dev = self.mesh.shape[self.batch_axis]
        assert n_clusters % n_cl_dev == 0, (n_clusters, n_cl_dev)
        assert b % n_b_dev == 0, (b, n_b_dev)
        if external_activity is None:
            external_activity = jnp.zeros((b, n_clusters, k_tags), spikes.dtype)
        else:  # broadcast shared (unbatched) stimulus like the other backends
            external_activity = jnp.broadcast_to(
                external_activity, (*batch_shape, n_clusters, k_tags)
            ).reshape(b, n_clusters, k_tags)

        ba, ca = self.batch_axis, self.cluster_axis
        # per-device FIFO: each cluster shard compacts its slab of sources
        local_capacity = queue_capacity
        if local_capacity is not None:
            local_capacity = max(1, -(-local_capacity // n_cl_dev))

        def local(spk, s_tag, s_dest, c_tag, c_syn, s_1h, ext):
            return sharded_local_deliver(
                spk, s_tag, s_dest, c_tag, c_syn, cluster_size, n_clusters,
                k_tags, ca, external_activity=ext,
                queue_capacity=local_capacity, syn_onehot=s_1h, with_stats=True,
            )

        if syn_onehot is None:
            from repro.core.two_stage import precompute_syn_onehot

            syn_onehot = precompute_syn_onehot(cam_syn, dtype=spikes.dtype)

        f = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(ba, ca), P(ca), P(ca), P(ca), P(ca), P(ca), P(ba, ca)),
            out_specs=(P(ba, ca), P(ba)),
            **SM_CHECK_KW,
        )
        drive, dropped = f(
            spikes, src_tag, src_dest, cam_tag, cam_syn, syn_onehot, external_activity
        )
        drive = drive.reshape(*batch_shape, n, N_SYN_TYPES)
        if with_stats:
            return drive, DeliveryStats(dropped=dropped.reshape(batch_shape))
        return drive


_BACKENDS: dict[str, type[DispatchBackend]] = {
    "reference": ReferenceBackend,
    "pallas": PallasBackend,
    "fused": FusedBackend,
    "sharded": ShardedBackend,
    "fabric": FabricBackend,
}


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def get_backend(spec: str | DispatchBackend | None = "reference", **options) -> DispatchBackend:
    """Resolve a backend by name (constructing it with ``options``) or pass
    an already-constructed instance through unchanged."""
    if isinstance(spec, DispatchBackend):
        if options:
            raise ValueError(
                f"backend options {sorted(options)} ignored: "
                f"{type(spec).__name__} was passed as an instance — configure "
                "it at construction instead"
            )
        return spec
    if spec is None:
        spec = "reference"
    try:
        cls = _BACKENDS[spec]
    except KeyError:
        raise ValueError(
            f"unknown dispatch backend {spec!r}; available: {available_backends()}"
        ) from None
    return cls(**options)


def two_stage_deliver(
    spikes: jax.Array,
    src_tag: jax.Array,
    src_dest: jax.Array,
    cam_tag: jax.Array,
    cam_syn: jax.Array,
    cluster_size: int,
    k_tags: int,
    external_activity: jax.Array | None = None,
    backend: str | DispatchBackend = "reference",
    queue_capacity: int | None = None,
    syn_onehot: jax.Array | None = None,
    with_stats: bool = False,
):
    """Full event delivery: spikes -> synaptic drive per neuron & synapse type.

    ``external_activity`` injects input events (the chip's Input Interface /
    FPGA path) directly as tag activity. ``backend`` selects the dispatch
    implementation by name or instance (:func:`get_backend`).
    ``queue_capacity`` enables event-sparse delivery through a fixed-capacity
    AER queue (DESIGN.md §10); with ``with_stats=True`` the return value is
    ``(drive, DeliveryStats)`` carrying the queue's drop counter.
    """
    return get_backend(backend).deliver(
        spikes,
        src_tag,
        src_dest,
        cam_tag,
        cam_syn,
        cluster_size,
        k_tags,
        external_activity=external_activity,
        queue_capacity=queue_capacity,
        syn_onehot=syn_onehot,
        with_stats=with_stats,
    )
