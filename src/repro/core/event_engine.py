"""Event-driven SNN engine: two-stage routing + neuron dynamics, scan-able.

The engine is the executable model of the whole DYNAPs fabric:

  spikes[t] --AER queue--> stage1 --> tag activity A[c, k] --stage2/CAM-->
           drive[N, 4] --AdExp/DPI--> spikes[t+1]

External stimulation (the chip's Input Interface) enters as tag activity
(events addressed to (cluster, tag)), exactly like the FPGA path in Fig. 7.

The whole path is batch-native (DESIGN.md §9): carry and inputs may bear a
leading batch dimension ``B`` — B independent event streams (users / DVS
sensors) stepped against one set of routing tables in a single dispatch.
``EventEngine.run`` scans over a ``[T, n_clusters, K]`` (or batched
``[T, B, n_clusters, K]``) input-event tensor. Delivery is delegated to a
pluggable dispatch backend (core/dispatch.py): ``reference`` (pure jnp),
``pallas`` (TPU stage-2 kernel), ``fused`` (single-kernel stage-1+2), or
``sharded`` (2-D-mesh shard_map), selected by name.

**Event-sparse delivery** (DESIGN.md §10): construct the engine with
``queue_capacity=Q`` to compact each step's spikes into a fixed-capacity AER
queue before stage 1 — delivery cost then scales with event count, and
``step``/``run`` additionally emit a :class:`DeliveryStats` (per-stream
FIFO-overflow drop counts, stacked over time by the scan). With
``donate_carry=True`` the step carry is donated to the compiled step on
accelerators, so the neuron-state buffers are updated in place across a
long run — but a carry you already stepped can then no longer be read
(always thread the returned one).

**Fabric mode** (DESIGN.md §11): construct with ``fabric=routing.Fabric(...)``
(tables compiled with a placement via ``compile_network(spec, fabric=...)``)
to push delivery through the executable R1/R2/R3 model — cross-tile events
traverse per-hop delay lines (arriving ``ceil(hops * latency / dt)`` steps
late; the carry gains the in-flight buffer) and bandwidth-limited inter-tile
link FIFOs, with per-step hop/latency/energy accumulators and link-drop
counts in the :class:`DeliveryStats` output.

**Multi-tenant serving** (DESIGN.md §12): batch slots are tenants.
``EventEngine.reset_slots(carry, mask)`` surgically restores masked slots
to freshly-initialized state — neuron state, undelivered previous-step
spikes, and the fabric in-flight buffer — so a session pool (serve/aer.py)
can admit and evict independent users without recompiling or leaking state
between a slot's successive occupants.

``dense_reference_step`` is the oracle: the same network as one dense
[N, N, 4] connectivity tensor (used by tests to prove routing equivalence),
batched the same way.

For multi-device execution, ``make_sharded_step`` shards clusters (cores)
across a mesh axis with ``shard_map``: stage-1 scatter produces a partial
activity matrix per device which is reduce-scattered over the cluster axis
— the TPU analogue of point-to-point R2/R3 traffic (DESIGN.md §2). With
``batch_axis`` set it runs on a 2-D mesh, sharding event streams over the
data axis as well. With ``queue_capacity`` set, each device compacts its
own neuron slab (one output FIFO per core, like the chip).
"""

from __future__ import annotations

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import neuron as neuron_mod
from repro.core.dispatch import DeliveryStats, DispatchBackend, get_backend
from repro.core.neuron import NeuronParams, NeuronState
from repro.core.shard_compat import SM_CHECK_KW, shard_map
from repro.core.tags import RoutingTables
from repro.core.two_stage import N_SYN_TYPES, precompute_syn_onehot

__all__ = [
    "EventEngine",
    "ShardedEventEngine",
    "DeliveryStats",
    "SlotCarry",
    "ModelRegistry",
    "reset_slots",
    "slice_slot_carry",
    "embed_slot_carry",
    "dense_weights_from_tables",
    "dense_reference_step",
]


@dataclasses.dataclass
class SlotCarry:
    """Host-side serialization of a set of batch slots' full runtime state.

    Produced by :meth:`EventEngine.extract_slots`, consumed by
    :meth:`EventEngine.splice_slots` — the unit of session *migration*
    between engines (DESIGN.md §15). All leaves are numpy with leading dim
    ``S`` (the extracted slot count). ``inflight`` is the delay-line state
    in the *phase-normalized* roll layout — ``inflight[:, i]`` holds tag
    activity arriving ``i + 1`` steps after extraction — regardless of
    whether the source engine ran the ring fast path or the roll buffer, so
    a slot can be spliced across delivery modes and across engines whose
    ring cursors disagree. ``None`` when the source engine had no fabric.
    """

    state: NeuronState  # numpy leaves, each [S, ...]
    spikes: np.ndarray  # [S, N] previous-step spikes
    inflight: np.ndarray | None  # [S, max_delay, n_clusters, K] or None


@dataclasses.dataclass(frozen=True)
class _Tables:
    src_tag: jax.Array
    src_dest: jax.Array
    cam_tag: jax.Array
    cam_syn: jax.Array
    # per-table constant [N, S, 4]: one-hot synapse types, precomputed once so
    # the expansion never runs in the per-step hot path (DESIGN.md §10)
    cam_syn_onehot: jax.Array


jax.tree_util.register_dataclass(
    _Tables,
    data_fields=["src_tag", "src_dest", "cam_tag", "cam_syn", "cam_syn_onehot"],
    meta_fields=[],
)

def _donate_carry_kwargs() -> dict:
    """Carry donation lets XLA reuse the neuron-state buffers across steps;
    the CPU backend does not implement donation and would warn on every
    compile. Resolved at first :class:`EventEngine` construction — not at
    import — so importing this module never initializes the JAX runtime."""
    return {} if jax.default_backend() == "cpu" else {"donate_argnums": (0,)}


class EventEngine:
    """Executable DYNAPs fabric for a compiled network."""

    def __init__(
        self,
        tables: RoutingTables,
        params: NeuronParams | None = None,
        backend: str | DispatchBackend = "reference",
        backend_options: dict | None = None,
        queue_capacity: int | None = None,
        donate_carry: bool = False,
        fabric=None,  # routing.Fabric | dispatch.FabricBackend | None
        fabric_options: dict | None = None,
    ):
        # a compiler-v2 CompileResult (core/compiler.py) carries the tables
        # plus a CompileReport; unwrap it so optimized placements flow
        # end-to-end without the caller re-plumbing
        if not isinstance(tables, RoutingTables) and hasattr(tables, "tables"):
            tables = tables.tables
        self.params = params or NeuronParams()
        self.cluster_size = tables.cluster_size
        self.k_tags = tables.k_tags
        self.n_neurons = tables.n_neurons
        self.n_clusters = tables.n_clusters
        if queue_capacity is not None and queue_capacity <= 0:
            raise ValueError(f"queue_capacity must be positive, got {queue_capacity}")
        self.queue_capacity = queue_capacity
        self.backend = get_backend(backend, **(backend_options or {}))
        # fabric mode (DESIGN.md §11): delivery runs on a FabricBackend and
        # the step carry gains the in-flight delay-line buffer; cross-tile
        # events arrive late and link FIFOs can drop. Takes precedence over
        # ``backend`` for delivery (stage 2 runs the jnp reference there).
        self.fabric_backend = None
        if fabric is not None:
            from repro.core.dispatch import FabricBackend

            if isinstance(fabric, FabricBackend):
                if fabric_options:
                    raise ValueError(
                        "fabric_options ignored: fabric was passed as a "
                        "FabricBackend instance — configure it at construction"
                    )
                self.fabric_backend = fabric
            else:
                opts = dict(fabric_options or {})
                opts.setdefault("tile_of_cluster", tables.tile_of_cluster)
                opts.setdefault("dt", self.params.dt)
                self.fabric_backend = FabricBackend(fabric=fabric, **opts)
            # the backend must agree with this engine however it was built:
            # a dt or placement mismatch silently warps arrival times / hops
            if self.fabric_backend.dt != self.params.dt:
                raise ValueError(
                    f"fabric dt={self.fabric_backend.dt} != NeuronParams.dt="
                    f"{self.params.dt}: delays and link capacity would be "
                    "derived at a timestep the neurons do not integrate with"
                )
            if tables.tile_of_cluster is not None:
                from repro.core.routing import default_tile_of_cluster

                backend_tiles = self.fabric_backend.tile_of_cluster
                if backend_tiles is None:
                    backend_tiles = default_tile_of_cluster(
                        self.n_clusters, self.fabric_backend.fabric
                    )
                if not np.array_equal(
                    np.asarray(backend_tiles), tables.tile_of_cluster
                ):
                    raise ValueError(
                        "fabric placement differs from the compiled tables' "
                        "tile_of_cluster — pass tile_of_cluster="
                        "tables.tile_of_cluster when constructing the backend"
                    )
            # build the delivery model eagerly: placement errors surface at
            # engine construction, and max_delay is needed by init_state
            self.fabric_model, _ = self.fabric_backend.model_for(self.n_clusters)
        # fault injection (DESIGN.md §15): the per-SRAM-entry survival mask is
        # drawn once, host-side, so both delivery paths consume the identical
        # erasure pattern — the ring path bakes it into FabricEntries.alive,
        # the roll path gathers it per queued event through this constant
        self._fault_entry_alive = None
        if self.fabric_backend is not None:
            self._fault_entry_alive = self.fabric_backend.entry_alive_for(
                tables.src_tag, tables.src_dest, self.cluster_size
            )
        cam_syn = jnp.asarray(tables.cam_syn)
        self.tables = _Tables(
            src_tag=jnp.asarray(tables.src_tag),
            src_dest=jnp.asarray(tables.src_dest),
            cam_tag=jnp.asarray(tables.cam_tag),
            cam_syn=cam_syn,
            cam_syn_onehot=precompute_syn_onehot(cam_syn),
        )
        # ring fast path (DESIGN.md §14): the carry gains a time-wheel ring +
        # write cursor instead of the shifted in-flight tail, and delivery
        # runs over a static per-SRAM-entry table precomputed here, once
        self.fabric_ring = (
            self.fabric_backend is not None and self.fabric_backend.ring
        )
        self._fabric_entries = None
        if self.fabric_ring:
            self._fabric_entries = self.fabric_backend.build_entries(
                tables.src_tag, tables.src_dest, self.cluster_size, self.k_tags
            )
        # per-engine compiled step (self is closed over = static). Carry
        # donation is opt-in: with donate_carry=True on an accelerator the
        # neuron-state buffers are updated in place across a long run, but a
        # carry you already stepped can no longer be read (parity tests and
        # debuggers do exactly that — hence the conservative default).
        donate = _donate_carry_kwargs() if donate_carry else {}
        self._jit_step = jax.jit(self._step_impl, **donate)
        self._jit_reset = jax.jit(self._reset_impl)
        # Python-body traces of the jitted step and reset: each is a
        # compilation, so a count that moves while serving names the program
        # that recompiled
        self.step_traces = 0
        self.reset_traces = 0

    # ------------------------------------------------------------------
    def init_state(
        self, batch: int | tuple[int, ...] | None = None
    ) -> tuple:
        """(neuron state, previous-step spikes); batched when ``batch`` set.

        In fabric mode the carry gains the delay-line state: with the ring
        fast path (the default) elements 3 and 4 are the time-wheel ring
        ``[..., max_delay + 1, n_clusters, K]`` and its shared int32 scalar
        write cursor; with ``fabric_options={"ring": False}`` element 3 is
        the roll-carried in-flight buffer ``[..., max_delay, nc, K]``.
        """
        lead = () if batch is None else (batch,) if isinstance(batch, int) else tuple(batch)
        carry = (
            neuron_mod.init_state(self.n_neurons, self.params, batch=batch),
            jnp.zeros((*lead, self.n_neurons), jnp.float32),
        )
        if self.fabric_backend is None:
            return carry
        if self.fabric_ring:
            ring, cursor = self.fabric_backend.init_ring(
                self.n_clusters, self.k_tags, batch=batch
            )
            return (*carry, ring, cursor)
        inflight = self.fabric_backend.init_inflight(
            self.n_clusters, self.k_tags, batch=batch
        )
        return (*carry, inflight)

    def step(
        self,
        carry: tuple[NeuronState, jax.Array],
        input_activity: jax.Array,  # [..., n_clusters, K] external events this step
        i_ext: jax.Array | None = None,
    ):
        """One fabric timestep (jit-compiled per engine; carry donated when
        the engine was built with ``donate_carry=True``).

        Returns ``(carry, spikes)`` — or ``(carry, (spikes, DeliveryStats))``
        when the engine was built with ``queue_capacity`` or in fabric mode
        (stats are part of the observable output so ``run``'s scan stacks
        them over T; fabric mode always emits them — drops, hops, latency
        and energy are the point of running the fabric model). In fabric
        mode the carry is the tuple from :meth:`init_state`, including the
        delay-line state (ring + cursor by default, the in-flight buffer
        with ``fabric_options={"ring": False}``).
        """
        return self._jit_step(carry, input_activity, i_ext)

    def compiled_step_text(self, carry, input_activity) -> str:
        """The compiled step's optimized HLO for these carry and input
        shapes (arrays or ``jax.ShapeDtypeStruct``s): its instructions as a
        profiler trace names them, each with its scope path (``op_name``)."""
        return self._jit_step.lower(carry, input_activity).compile().as_text()

    def _step_impl(self, carry, input_activity, i_ext=None):
        self.step_traces += 1
        # inputs adopt the carry dtype: under x64, default-f64 stimulus
        # arrays would otherwise promote the neuron state mid-scan and trip
        # lax.scan's carry-type check
        dtype = carry[1].dtype
        input_activity = jnp.asarray(input_activity, dtype)
        if i_ext is not None:
            i_ext = jnp.asarray(i_ext, dtype)
        if self.fabric_backend is not None and self.fabric_ring:
            state, prev_spikes, ring, cursor = carry
            with jax.named_scope("deliver"):
                drive, ring, cursor, stats = self.fabric_backend.deliver_fabric_ring(
                    prev_spikes,
                    self._fabric_entries,
                    self.tables.cam_tag,
                    self.tables.cam_syn,
                    self.cluster_size,
                    self.k_tags,
                    ring,
                    cursor,
                    external_activity=input_activity,
                    queue_capacity=self.queue_capacity,
                    syn_onehot=self.tables.cam_syn_onehot,
                )
            state, spikes = neuron_mod.neuron_step(state, drive, self.params, i_ext)
            return (state, spikes, ring, cursor), (spikes, stats)
        if self.fabric_backend is not None:
            state, prev_spikes, inflight = carry
            with jax.named_scope("deliver"):
                drive, inflight, stats = self.fabric_backend.deliver_fabric(
                    prev_spikes,
                    self.tables.src_tag,
                    self.tables.src_dest,
                    self.tables.cam_tag,
                    self.tables.cam_syn,
                    self.cluster_size,
                    self.k_tags,
                    inflight=inflight,
                    external_activity=input_activity,
                    queue_capacity=self.queue_capacity,
                    syn_onehot=self.tables.cam_syn_onehot,
                    entry_alive=self._fault_entry_alive,
                )
            state, spikes = neuron_mod.neuron_step(state, drive, self.params, i_ext)
            # fabric mode always reports stats: drops/hops/latency/energy are
            # the point of running the fabric model
            return (state, spikes, inflight), (spikes, stats)
        state, prev_spikes = carry
        with jax.named_scope("deliver"):
            drive, stats = self.backend.deliver(
                prev_spikes,
                self.tables.src_tag,
                self.tables.src_dest,
                self.tables.cam_tag,
                self.tables.cam_syn,
                self.cluster_size,
                self.k_tags,
                external_activity=input_activity,
                queue_capacity=self.queue_capacity,
                syn_onehot=self.tables.cam_syn_onehot,
                with_stats=True,
            )
        state, spikes = neuron_mod.neuron_step(state, drive, self.params, i_ext)
        out = spikes if self.queue_capacity is None else (spikes, stats)
        return (state, spikes), out

    def reset_slots(self, carry, mask):
        """Per-slot state surgery for multi-tenant serving (DESIGN.md §12).

        ``mask`` is a boolean array over the carry's leading batch dims
        (``True`` = wipe that slot). Masked slots are restored to the
        freshly-initialized state of :meth:`init_state`: neuron state back
        to rest, previous-step spikes cleared, and — in fabric mode — that
        slot's in-flight delay-line buffer zeroed, so a departing tenant's
        still-in-transit cross-tile events can never leak into the slot's
        next occupant. Unmasked slots are untouched (bit-identical), which
        is what lets a session pool admit/evict tenants independently while
        the others keep running.
        """
        return self._jit_reset(carry, jnp.asarray(mask))

    @jax.named_scope("reset_slots")
    def _reset_impl(self, carry, mask):
        self.reset_traces += 1
        if mask.ndim < 1:
            raise ValueError("reset_slots needs a batched carry (mask per slot)")
        lead = tuple(carry[1].shape[: mask.ndim])
        if tuple(mask.shape) != lead:
            raise ValueError(
                f"reset mask shape {tuple(mask.shape)} does not match the "
                f"carry's slot dims {lead} — a mis-sized mask must raise, "
                "not broadcast (it would wipe the wrong tenants)"
            )
        fresh = self.init_state(batch=mask.shape)
        return reset_slots(carry, mask, fresh)

    # ------------------------------------------------------------------
    # Slot migration (DESIGN.md §15): extract_slots / splice_slots generalize
    # reset_slots — instead of wiping a slot, serialize its complete runtime
    # state (including the fabric delay-line contents) so surviving sessions
    # can move onto a repaired engine or come back from a checkpoint.
    def _check_slot_index(self, slots, batch: int) -> np.ndarray:
        idx = np.atleast_1d(np.asarray(slots, dtype=np.int64))
        if idx.ndim != 1 or idx.size == 0:
            raise ValueError("slots must be a non-empty 1-D index sequence")
        if np.unique(idx).size != idx.size:
            raise ValueError(f"slots must be unique, got {idx.tolist()}")
        if np.any(idx < 0) or np.any(idx >= batch):
            raise ValueError(
                f"slots {idx.tolist()} out of range for batch size {batch}"
            )
        return idx

    def extract_slots(self, carry, slots) -> SlotCarry:
        """Serialize ``slots``' full per-slot runtime state (host-side).

        The carry must bear exactly one leading batch dim (a session pool).
        Ring-mode delay state is phase-normalized on the way out: wheel slot
        ``(cursor + i) % (max_delay + 1)`` holds the events arriving in
        ``i + 1`` steps, so the returned ``inflight[:, i]`` has the roll
        layout and the wheel phase does not travel with the snapshot.
        """
        spikes = np.asarray(carry[1])
        if spikes.ndim != 2:
            raise ValueError(
                "extract_slots needs a carry with one leading batch dim, got "
                f"spikes shape {spikes.shape}"
            )
        idx = self._check_slot_index(slots, spikes.shape[0])
        state = jax.tree_util.tree_map(lambda x: np.asarray(x)[idx], carry[0])
        inflight = None
        if self.fabric_backend is not None:
            if self.fabric_ring:
                ring = np.asarray(carry[2])  # [B, max_delay + 1, nc, K]
                cur = int(np.asarray(carry[3]))
                d1 = ring.shape[-3]
                order = (cur + np.arange(d1 - 1)) % d1
                inflight = ring[idx][:, order]
            else:
                inflight = np.asarray(carry[2])[idx]
        return SlotCarry(state=state, spikes=spikes[idx], inflight=inflight)

    def splice_slots(self, carry, slots, sc: SlotCarry):
        """Write ``sc``'s serialized slots into ``carry`` at ``slots``.

        The inverse of :meth:`extract_slots`, on *this* engine's carry —
        the source engine may differ (that is the point: migration onto a
        repaired placement, or restore into a fresh pool). Neuron count,
        cluster count and K must match. Delay-line contents are re-bucketed
        when the two engines' ``max_delay`` differ: shorter horizons gain
        zero tail slots; longer horizons fold the excess tail into the last
        slot (events arrive *earlier* than on the source fabric — best
        effort; the exchange is bit-exact when the horizons agree).
        Unlisted slots are untouched bit-identically.
        """
        spikes_t = carry[1]
        if spikes_t.ndim != 2:
            raise ValueError(
                "splice_slots needs a carry with one leading batch dim, got "
                f"spikes shape {spikes_t.shape}"
            )
        idx = self._check_slot_index(slots, spikes_t.shape[0])
        sp = np.asarray(sc.spikes)
        if sp.shape[0] != idx.size:
            raise ValueError(
                f"{idx.size} slots but SlotCarry holds {sp.shape[0]}"
            )
        if sp.shape[-1] != self.n_neurons:
            raise ValueError(
                f"SlotCarry has {sp.shape[-1]} neurons, engine has "
                f"{self.n_neurons}"
            )
        def _checked_set(cur, new):
            new = jnp.asarray(new, cur.dtype)
            want = (idx.size, *cur.shape[1:])
            if tuple(new.shape) != want:
                raise ValueError(
                    f"SlotCarry state leaf shape {tuple(new.shape)} != "
                    f"expected {want} — a mismatched leaf must raise, not "
                    "broadcast into the pool"
                )
            return cur.at[jidx].set(new)

        jidx = jnp.asarray(idx)
        state = jax.tree_util.tree_map(_checked_set, carry[0], sc.state)
        spikes = spikes_t.at[jidx].set(jnp.asarray(sp, spikes_t.dtype))
        if self.fabric_backend is None:
            if sc.inflight is not None and np.any(np.asarray(sc.inflight)):
                raise ValueError(
                    "SlotCarry holds in-flight fabric events but the target "
                    "engine has no fabric delay line to receive them"
                )
            return (state, spikes)
        d_t = self.fabric_model.max_delay
        if sc.inflight is None:
            inflight = np.zeros(
                (idx.size, d_t, self.n_clusters, self.k_tags), np.float32
            )
        else:
            inflight = np.asarray(sc.inflight)
            if inflight.shape[-2:] != (self.n_clusters, self.k_tags):
                raise ValueError(
                    f"SlotCarry in-flight grid {inflight.shape[-2:]} != "
                    f"engine ({self.n_clusters}, {self.k_tags})"
                )
            d_s = inflight.shape[1]
            if d_s > d_t:  # fold the excess tail into the last live slot
                if d_t == 0:
                    if np.any(inflight):
                        raise ValueError(
                            "target engine has no delay line (max_delay=0) "
                            "but the SlotCarry holds in-flight events"
                        )
                    inflight = inflight[:, :0]
                else:
                    inflight = np.concatenate(
                        [
                            inflight[:, : d_t - 1],
                            inflight[:, d_t - 1 :].sum(axis=1, keepdims=True),
                        ],
                        axis=1,
                    )
            elif d_s < d_t:
                pad = np.zeros(
                    (idx.size, d_t - d_s, *inflight.shape[2:]), inflight.dtype
                )
                inflight = np.concatenate([inflight, pad], axis=1)
        if self.fabric_ring:
            ring, cursor = carry[2], carry[3]
            cur = int(np.asarray(cursor))
            d1 = d_t + 1
            rows = np.zeros((idx.size, d1, *inflight.shape[2:]), inflight.dtype)
            rows[:, (cur + np.arange(d_t)) % d1] = inflight
            ring = ring.at[jidx].set(jnp.asarray(rows, ring.dtype))
            return (state, spikes, ring, cursor)
        infl = carry[2].at[jidx].set(jnp.asarray(inflight, carry[2].dtype))
        return (state, spikes, infl)

    def run(
        self,
        carry: tuple[NeuronState, jax.Array],
        input_events: jax.Array,  # [T, ..., n_clusters, K]
        i_ext: jax.Array | None = None,
    ):
        """Scan T steps; returns ``(final carry, spikes [T, ..., N])`` — with
        ``queue_capacity`` (or fabric mode) set, ``(final carry, (spikes
        [T, ..., N], DeliveryStats stacked over T))``.

        ``i_ext`` may be time-varying: a ``[T, ..., N]`` current (one more
        leading axis than the spike state, first axis of length ``T``) is
        scanned alongside ``input_events`` — step ``t`` sees ``i_ext[t]``.
        Anything of the spike state's rank or below is broadcast as a
        per-step constant, so ``[N]`` with ``N == T`` or batched ``[B, N]``
        with ``B == T`` are never misread as time series.
        """
        t = input_events.shape[0]
        i_shape = () if i_ext is None else np.shape(i_ext)
        time_varying = (
            len(i_shape) == np.ndim(carry[1]) + 1 and i_shape[0] == t
        )
        if time_varying:

            def body_t(c, xs):
                inp, ie = xs
                return self.step(c, inp, ie)

            return jax.lax.scan(body_t, carry, (input_events, jnp.asarray(i_ext)))

        def body(c, inp):
            return self.step(c, inp, i_ext)

        return jax.lax.scan(body, carry, input_events)

    # ------------------------------------------------------------------
    def make_sharded_step(
        self,
        mesh: jax.sharding.Mesh,
        axis: str = "data",
        batch_axis: str | None = None,
    ):
        """shard_map step with clusters sharded over ``axis``.

        Neurons, CAM tables and neuron state are sharded by cluster slab;
        stage-1 partial activity is reduce-scattered across devices (the
        R2/R3 point-to-point hop), stage-2 and dynamics are fully local.

        With ``batch_axis`` set the mesh is 2-D: event streams shard over
        ``batch_axis`` (pure data parallelism) while clusters shard over
        ``axis``; all carried arrays then bear a leading batch dim.

        With the engine's ``queue_capacity`` set, each device compacts its
        local slab through its own AER FIFO and the step returns
        ``(state, spikes, dropped)`` — ``dropped`` already summed fabric-wide.

        In fabric mode (``EventEngine(fabric=...)``) the device mesh mirrors
        the chip mesh: each device owns a contiguous slab of whole *tiles*
        (the placement must not split a tile across devices), per-link FIFO
        arbitration runs where the events originate — exact, since a
        directed link's traffic all comes from one device — and the step
        signature becomes ``(tables, state, prev_spikes, inflight,
        input_activity, i_ext) -> (state, spikes, inflight, DeliveryStats)``
        with the in-flight buffer sharded over the cluster axis and stats
        psum-reduced fabric-wide. With the ring fast path (the default) the
        delay-line carry is instead the time-wheel pair: ``(tables, state,
        prev_spikes, ring, cursor, input_activity, i_ext) -> (state, spikes,
        ring, cursor, DeliveryStats)`` — the ring sharded like the in-flight
        buffer, the scalar cursor replicated (``P()``).
        """
        from jax.sharding import PartitionSpec as P

        n_dev = mesh.shape[axis]
        assert self.n_clusters % n_dev == 0, "clusters must divide device axis"
        params = self.params
        cluster_size, k_tags = self.cluster_size, self.k_tags
        n_clusters = self.n_clusters
        queue_capacity = self.queue_capacity
        if queue_capacity is not None:  # per-core FIFO: split capacity by slab
            queue_capacity = max(1, -(-queue_capacity // n_dev))

        if self.fabric_backend is not None:
            if self.fabric_backend.faults is not None:
                raise NotImplementedError(
                    "fault injection is not supported by the sharded fabric "
                    "step — run faulted scenarios single-device (DESIGN.md §15)"
                )
            return self._make_sharded_fabric_step(
                mesh, axis, batch_axis, n_dev, queue_capacity
            )

        from repro.core.dispatch import sharded_local_deliver

        def local_step(tables, state, prev_spikes, input_activity, i_ext):
            # prev_spikes: local slab [..., N/n_dev]; tables rows local.
            drive, dropped = sharded_local_deliver(
                prev_spikes,
                tables.src_tag,
                tables.src_dest,
                tables.cam_tag,
                tables.cam_syn,
                cluster_size,
                n_clusters,
                k_tags,
                axis,
                external_activity=input_activity,
                queue_capacity=queue_capacity,
                syn_onehot=tables.cam_syn_onehot,
                with_stats=True,
            )
            state, spikes = neuron_mod.neuron_step(state, drive, params, i_ext)
            if queue_capacity is None:
                return state, spikes
            return state, spikes, dropped

        spec_t = P(axis)  # tables: shard rows (neurons) over the cluster axis
        if batch_axis is None:
            spec_c = P(axis)  # unbatched carry: leading dim is neurons
            spec_d = P()  # drop counter: replicated (summed over ``axis``)
        else:
            spec_c = P(batch_axis, axis)  # batched carry: [B, N_local, ...]
            spec_d = P(batch_axis)
        state_spec = NeuronState(spec_c, spec_c, spec_c, spec_c)
        out_specs = (state_spec, spec_c)
        if queue_capacity is not None:
            out_specs = (state_spec, spec_c, spec_d)
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(
                _Tables(spec_t, spec_t, spec_t, spec_t, spec_t),
                state_spec,
                spec_c,
                spec_c,
                spec_c,
            ),
            out_specs=out_specs,
            **SM_CHECK_KW,
        )

    def _make_sharded_fabric_step(self, mesh, axis, batch_axis, n_dev, queue_capacity):
        """Fabric-mode shard_map step: tiles -> devices (see make_sharded_step)."""
        from jax.sharding import PartitionSpec as P

        from repro.core.dispatch import DeliveryStats, advance_inflight
        from repro.core.two_stage import (
            compact_events,
            stage1_route_events_fabric,
            stage2_cam_match,
        )

        params = self.params
        cluster_size, k_tags = self.cluster_size, self.k_tags
        n_clusters = self.n_clusters
        nc_local = n_clusters // n_dev
        model, arrs = self.fabric_backend.model_for(n_clusters)
        # the device mesh mirrors the chip mesh only if no tile straddles a
        # device boundary — every link's traffic then originates on exactly
        # one device and per-device FIFO arbitration is globally exact
        slab_of_cluster = np.arange(n_clusters) // nc_local
        for t in np.unique(model.tile_of_cluster):
            devs = np.unique(slab_of_cluster[model.tile_of_cluster == t])
            if devs.size > 1:
                raise ValueError(
                    f"tile {t} is split across devices {devs.tolist()}: fabric-"
                    "sharded execution needs each tile's clusters on one device "
                    "(use the hierarchical linear placement or re-shard)"
                )

        def _route_local(tables, prev_spikes, cursor=None):
            """Shared stage-1 body: compact the slab, route through the fabric."""
            n_local = prev_spikes.shape[-1]
            capacity = n_local if queue_capacity is None else queue_capacity
            offset = jax.lax.axis_index(axis) * nc_local
            queue = compact_events(prev_spikes, capacity)
            route = stage1_route_events_fabric(
                queue,
                tables.src_tag,
                tables.src_dest,
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
                src_cluster_offset=offset,
                cursor=cursor,
                per_link_stats=self.fabric_backend.per_link_stats,
            )
            # hand every (delay, cluster) slab to its owner — the R3 hop
            buf = jax.lax.psum_scatter(
                route.buffer, axis, scatter_dimension=route.buffer.ndim - 2, tiled=True
            )  # [..., max_delay + 1, nc_local, K]
            # per_link_stats widens link_dropped/delivered with a trailing
            # bin axis; the elementwise psum and the batch-only PartitionSpec
            # (trailing dims replicated) treat both shapes uniformly — each
            # device contributes its own sources' bins, summed fabric-wide
            stats = DeliveryStats(
                dropped=jax.lax.psum(queue.dropped, axis),
                link_dropped=jax.lax.psum(route.link_dropped, axis),
                delivered=jax.lax.psum(route.delivered, axis),
                hops=jax.lax.psum(route.hops, axis),
                latency_s=jax.lax.psum(route.latency_s, axis),
                energy_j=jax.lax.psum(route.energy_j, axis),
            )
            return buf, stats

        def _finish_local(tables, state, a, input_activity, i_ext):
            a = a + input_activity
            drive = stage2_cam_match(
                a, tables.cam_tag, tables.cam_syn, cluster_size, tables.cam_syn_onehot
            )
            return neuron_mod.neuron_step(state, drive, params, i_ext)

        def local_step(tables, state, prev_spikes, inflight, input_activity, i_ext):
            buf, stats = _route_local(tables, prev_spikes)
            a, new_inflight = advance_inflight(buf, inflight, model.max_delay)
            state, spikes = _finish_local(tables, state, a, input_activity, i_ext)
            return state, spikes, new_inflight, stats

        def local_step_ring(
            tables, state, prev_spikes, ring, cursor, input_activity, i_ext
        ):
            # wheel semantics of the single-device ring step, with the routed
            # scatter already cursor-rotated by stage 1: accumulate this
            # step's arrivals, pop + clear the cursor slot, bump the pointer
            buf, stats = _route_local(tables, prev_spikes, cursor=cursor)
            ring = ring + buf
            slot_ax = ring.ndim - 3
            a = jnp.take(ring, cursor, axis=slot_ax)
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, jnp.zeros_like(a), cursor, slot_ax
            )
            state, spikes = _finish_local(tables, state, a, input_activity, i_ext)
            return state, spikes, ring, (cursor + 1) % (model.max_delay + 1), stats

        spec_t = P(axis)
        if batch_axis is None:
            spec_c = P(axis)
            spec_f = P(None, axis)  # delay-line carry [D, nc, K]: shard clusters
            spec_d = P()
        else:
            spec_c = P(batch_axis, axis)
            spec_f = P(batch_axis, None, axis)  # [B, D, nc, K]
            spec_d = P(batch_axis)
        state_spec = NeuronState(spec_c, spec_c, spec_c, spec_c)
        stats_spec = DeliveryStats(spec_d, spec_d, spec_d, spec_d, spec_d, spec_d)
        in_specs = (
            _Tables(spec_t, spec_t, spec_t, spec_t, spec_t),
            state_spec,
            spec_c,
            spec_f,
            spec_c,
            spec_c,
        )
        if self.fabric_ring:
            # ring sharded like the in-flight buffer; scalar cursor replicated
            return shard_map(
                local_step_ring,
                mesh=mesh,
                in_specs=(*in_specs[:4], P(), *in_specs[4:]),
                out_specs=(state_spec, spec_c, spec_f, P(), stats_spec),
                **SM_CHECK_KW,
            )
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(state_spec, spec_c, spec_f, stats_spec),
            **SM_CHECK_KW,
        )


class ShardedEventEngine(EventEngine):
    """:class:`EventEngine` whose jitted step runs multi-device via shard_map.

    The engine owns a 2-D device mesh named ``("data", "model")``: batch
    slots (tenants) shard over ``data`` and clusters (tiles) over ``model``
    — one serving shard of a ``ShardedSessionPool`` (serve/sharded.py,
    DESIGN.md §17). The public step contract is unchanged
    (``step(carry, input_activity, i_ext) -> (carry, (spikes, stats))``),
    so session pools, slot surgery (``reset_slots`` / ``extract_slots`` /
    ``splice_slots``) and checkpointing work on it untouched; only the step
    dispatch is resharded through :meth:`EventEngine.make_sharded_step`.
    Queued engines always report a :class:`DeliveryStats` (drops summed
    fabric-wide by the sharded step), matching the ``queue_capacity``
    contract of the local engine.

    Constraints inherited from the sharded step: the carry must be batched
    and the batch must divide ``batch_devices``; ``n_clusters`` must divide
    ``cluster_devices``; in fabric mode the compiled placement must keep
    every tile's clusters inside one device slab
    (:func:`repro.core.compiler.device_slab_placement` builds such
    placements) and fault injection is rejected. A ``(1, 1)`` mesh is valid
    — serving code paths are then identical with or without real devices.
    """

    def __init__(
        self,
        tables,
        params: NeuronParams | None = None,
        *,
        devices=None,
        cluster_devices: int = 1,
        batch_devices: int = 1,
        **engine_kw,
    ):
        donate = bool(engine_kw.get("donate_carry", False))
        super().__init__(tables, params, **engine_kw)
        if cluster_devices <= 0 or batch_devices <= 0:
            raise ValueError(
                f"mesh extents must be positive, got {batch_devices} x "
                f"{cluster_devices}"
            )
        need = batch_devices * cluster_devices
        if devices is None:
            avail = jax.devices()
            if need > len(avail):
                raise ValueError(
                    f"mesh needs {need} devices, only {len(avail)} visible "
                    "(set --xla_force_host_platform_device_count on CPU)"
                )
            devices = avail[:need]
        devices = np.asarray(devices, dtype=object)
        if devices.size != need:
            raise ValueError(
                f"got {devices.size} devices for a {batch_devices} x "
                f"{cluster_devices} mesh"
            )
        if self.n_clusters % cluster_devices:
            raise ValueError(
                f"{self.n_clusters} clusters do not divide over "
                f"{cluster_devices} cluster devices"
            )
        self.mesh = jax.sharding.Mesh(
            devices.reshape(batch_devices, cluster_devices), ("data", "model")
        )
        self.cluster_devices = cluster_devices
        self.batch_devices = batch_devices
        # the sharded step's flat signature, re-adapted to step()'s contract;
        # placement/tile-split errors surface here, at construction
        sharded = self.make_sharded_step(self.mesh, "model", batch_axis="data")
        fabric = self.fabric_backend is not None
        ring = self.fabric_ring
        qc = self.queue_capacity

        def _wrapped(carry, input_activity, i_ext=None):
            self.step_traces += 1
            dtype = carry[1].dtype
            inp = jnp.asarray(input_activity, dtype)
            # shard_map in_specs cannot carry a None leaf: vacant external
            # drive becomes explicit zeros (free under XLA's simplifier)
            ie = (
                jnp.zeros_like(carry[1])
                if i_ext is None
                else jnp.asarray(i_ext, dtype)
            )
            if fabric and ring:
                state, prev, rg, cur = carry
                state, spikes, rg, cur, stats = sharded(
                    self.tables, state, prev, rg, cur, inp, ie
                )
                return (state, spikes, rg, cur), (spikes, stats)
            if fabric:
                state, prev, infl = carry
                state, spikes, infl, stats = sharded(
                    self.tables, state, prev, infl, inp, ie
                )
                return (state, spikes, infl), (spikes, stats)
            state, prev = carry
            out = sharded(self.tables, state, prev, inp, ie)
            if qc is None:
                state, spikes = out
                return (state, spikes), spikes
            state, spikes, dropped = out
            return (state, spikes), (spikes, DeliveryStats(dropped=dropped))

        self._jit_step = jax.jit(
            _wrapped, **(_donate_carry_kwargs() if donate else {})
        )

    def carry_pspecs(self):
        """PartitionSpec tree for a batched carry under this engine's mesh.

        Matches :meth:`EventEngine.make_sharded_step`'s layout: neuron-state
        leaves and spikes shard ``[B, N]`` over ``(data, model)``, fabric
        delay-line carries shard clusters (``[B, D, nc, K]`` over
        ``(data, None, model)``), and the ring's shared write cursor is
        replicated. Feed through ``distributed.sharding.named`` into
        ``jax.device_put`` / ``Checkpointer.restore(shardings=...)`` to land
        a carry on the mesh — the elastic-restart path
        (distributed/elastic.py, DESIGN.md §17).
        """
        from jax.sharding import PartitionSpec as P

        spec_c = P("data", "model")
        state = NeuronState(spec_c, spec_c, spec_c, spec_c)
        if self.fabric_backend is None:
            return (state, spec_c)
        spec_f = P("data", None, "model")
        if self.fabric_ring:
            return (state, spec_c, spec_f, P())
        return (state, spec_c, spec_f)

    def place_carry(self, carry):
        """device_put ``carry`` onto this engine's mesh per :meth:`carry_pspecs`.

        Splice/restore surgery produces host-backed or default-placed
        arrays; pinning them back onto the shard's own mesh keeps a
        multi-shard fleet's carries resident on their devices instead of
        bouncing through the step's implicit resharding.
        """
        from repro.distributed.sharding import named

        shardings = named(self.mesh, self.carry_pspecs())
        return jax.tree.map(jax.device_put, carry, shardings)


# ---------------------------------------------------------------------------
# Per-slot state surgery
# ---------------------------------------------------------------------------
def reset_slots(carry, mask: jax.Array, fresh):
    """Replace masked slots of ``carry`` with the matching slots of ``fresh``.

    ``carry`` and ``fresh`` are any pytrees of identically-shaped arrays
    whose leading dims start with ``mask``'s shape (the slot axes); every
    leaf is selected slot-wise. This is the functional core of
    :meth:`EventEngine.reset_slots` — kept standalone so custom serving
    loops can splice arbitrary per-slot state (e.g. a checkpointed tenant)
    instead of the engine's fresh init.

    Leaves with fewer dims than ``mask`` are slot-*shared* (the ring-mode
    write cursor: every slot steps in lockstep, so one phase pointer serves
    the whole pool) and pass through unchanged — zeroing a masked slot's
    whole ring is phase-independent, so the evicted tenant leaks nothing at
    any cursor position.
    """
    def sel(cur, new):
        if cur.ndim < mask.ndim:
            return cur
        if tuple(cur.shape[: mask.ndim]) != tuple(mask.shape):
            raise ValueError(
                f"mask shape {tuple(mask.shape)} does not match carry leaf "
                f"slot dims {tuple(cur.shape[: mask.ndim])} — refusing to "
                "broadcast a mis-sized mask across slots"
            )
        m = mask.reshape(mask.shape + (1,) * (cur.ndim - mask.ndim))
        return jnp.where(m, jnp.asarray(new, cur.dtype), cur)

    return jax.tree_util.tree_map(sel, carry, fresh)


# ---------------------------------------------------------------------------
# Multi-model residency (DESIGN.md §16)
# ---------------------------------------------------------------------------
def slice_slot_carry(sc: SlotCarry, slab) -> SlotCarry:
    """Restrict a :class:`SlotCarry` to one resident model's table slab.

    ``slab`` is a :class:`repro.core.tags.TableSlab`. Neuron-state leaves
    carry the neuron axis at position 1 (``[S, N]`` / ``[S, N, 4]``), so one
    slice serves all of them; the in-flight buffer is cut on the cluster
    axis and narrowed to the slab's own ``k_tags`` — the combined engine may
    pad K up to the widest resident model, and tag activity a model never
    compiled is structurally zero in its slab.
    """
    n0, n1 = slab.neuron_lo, slab.neuron_hi
    state = jax.tree_util.tree_map(lambda x: np.asarray(x)[:, n0:n1], sc.state)
    spikes = np.asarray(sc.spikes)[:, n0:n1]
    inflight = None
    if sc.inflight is not None:
        inflight = np.asarray(sc.inflight)[
            :, :, slab.cluster_lo : slab.cluster_hi, : slab.k_tags
        ]
    return SlotCarry(state=state, spikes=spikes, inflight=inflight)


def embed_slot_carry(sc_slab: SlotCarry, engine: "EventEngine", slab) -> SlotCarry:
    """Embed a slab-restricted :class:`SlotCarry` into ``engine``'s geometry.

    The inverse of :func:`slice_slot_carry` for migration onto a pool whose
    slab layout moved (hot-swap of a co-resident model). The base is the
    engine's *fresh* init — not zeros: a zeroed membrane (``v = 0``) sits at
    the firing threshold and every neuron outside the slab would spike on
    the first step. The returned in-flight buffer keeps the source horizon
    ``D_src``; :meth:`EventEngine.splice_slots` re-buckets it to the target
    engine's ``max_delay`` and re-rotates the ring phase.
    """
    part = np.asarray(sc_slab.spikes)
    s = part.shape[0]
    if part.shape[-1] != slab.n_neurons:
        raise ValueError(
            f"SlotCarry holds {part.shape[-1]} neurons but the slab spans "
            f"{slab.n_neurons}"
        )
    base = engine.extract_slots(engine.init_state(batch=s), np.arange(s))
    n0, n1 = slab.neuron_lo, slab.neuron_hi

    def put(full, p):
        full = np.array(full)
        full[:, n0:n1] = p
        return full

    state = jax.tree_util.tree_map(put, base.state, sc_slab.state)
    spikes = put(base.spikes, part)
    inflight = None
    if engine.fabric_backend is not None:
        if sc_slab.inflight is None:
            inflight = base.inflight
        else:
            src = np.asarray(sc_slab.inflight)
            if src.shape[-2:] != (slab.n_clusters, slab.k_tags):
                raise ValueError(
                    f"SlotCarry in-flight grid {src.shape[-2:]} != slab "
                    f"({slab.n_clusters}, {slab.k_tags})"
                )
            if slab.k_tags > engine.k_tags:
                raise ValueError(
                    f"slab k_tags {slab.k_tags} exceeds engine K {engine.k_tags}"
                )
            inflight = np.zeros(
                (s, src.shape[1], engine.n_clusters, engine.k_tags), np.float32
            )
            inflight[
                :, :, slab.cluster_lo : slab.cluster_hi, : slab.k_tags
            ] = src
    elif sc_slab.inflight is not None and np.any(sc_slab.inflight):
        raise ValueError(
            "SlotCarry holds in-flight fabric events but the target engine "
            "has no fabric delay line to receive them"
        )
    return SlotCarry(state=state, spikes=spikes, inflight=inflight)


class ModelRegistry:
    """Ordered set of resident compiled networks sharing ONE engine (§16).

    Each model keeps its own :class:`RoutingTables`; :meth:`combined`
    concatenates them into disjoint neuron/cluster slabs (tag values need no
    rebasing — ``(cluster, tag)`` is the routed address and clusters are
    rebased by :func:`repro.core.tags.concat_tables`). The slab layout is
    insertion-ordered, so *which models are resident, in which order* is the
    whole identity of the combined engine — :meth:`fingerprint` hashes
    exactly that, and checkpoint restore compares it.
    """

    def __init__(self, models=None):
        self._models: dict[str, RoutingTables] = {}
        if models:
            for name, tables in models.items():
                self.load(name, tables)

    @staticmethod
    def _unwrap(tables) -> RoutingTables:
        # accept CompileResult / CompiledArtifact / CompiledCnn wrappers
        while hasattr(tables, "tables"):
            tables = tables.tables
        return tables

    def load(self, name: str, tables) -> None:
        if name in self._models:
            raise ValueError(f"model {name!r} already resident")
        tables = self._unwrap(tables)
        for other_name, other in self._models.items():
            if other.cluster_size != tables.cluster_size:
                raise ValueError(
                    f"model {name!r} cluster_size {tables.cluster_size} != "
                    f"resident {other_name!r} cluster_size {other.cluster_size}"
                )
        self._models[name] = tables

    def unload(self, name: str) -> None:
        if name not in self._models:
            raise KeyError(f"model {name!r} is not resident")
        del self._models[name]

    def __contains__(self, name: str) -> bool:
        return name in self._models

    def __len__(self) -> int:
        return len(self._models)

    @property
    def names(self) -> list[str]:
        return list(self._models)

    def tables_of(self, name: str) -> RoutingTables:
        return self._models[name]

    def slabs(self) -> dict:
        """Slab layout by model name, insertion-ordered (no concat needed)."""
        from repro.core.tags import TableSlab

        out, n0, c0 = {}, 0, 0
        for name, t in self._models.items():
            out[name] = TableSlab(
                neuron_lo=n0,
                neuron_hi=n0 + t.n_neurons,
                cluster_lo=c0,
                cluster_hi=c0 + t.n_clusters,
                k_tags=t.k_tags,
            )
            n0 += t.n_neurons
            c0 += t.n_clusters
        return out

    def combined(self) -> tuple[RoutingTables, dict]:
        """(combined tables, slab layout by name). Single resident model
        returns its tables untouched, so a registry-of-one is free."""
        from repro.core.tags import concat_tables

        if not self._models:
            raise ValueError("registry holds no resident models")
        names = list(self._models)
        if len(names) == 1:
            return self._models[names[0]], self.slabs()
        tables, slab_list = concat_tables(list(self._models.values()))
        return tables, dict(zip(names, slab_list))

    def fingerprint(self) -> str:
        """sha256 over (name, table fingerprint) pairs in slab order."""
        h = hashlib.sha256()
        for name, t in self._models.items():
            h.update(name.encode())
            h.update(b"\x00")
            h.update(t.fingerprint().encode())
            h.update(b"\x01")
        return h.hexdigest()


# ---------------------------------------------------------------------------
# Dense oracle
# ---------------------------------------------------------------------------
def dense_weights_from_tables(tables: RoutingTables) -> np.ndarray:
    """[N, N, 4] dense fan-in counts implied by the routing tables."""
    n = tables.n_neurons
    w = np.zeros((n, n, N_SYN_TYPES), dtype=np.float32)
    for src, dst, syn in tables.dense_equivalent():
        w[dst, src, syn] += 1.0
    return w


def dense_reference_step(
    dense_w: jax.Array,  # [N, N, 4]
    prev_spikes: jax.Array,  # [..., N]
    state: NeuronState,
    params: NeuronParams,
    external_drive: jax.Array | None = None,  # [..., N, 4]
    i_ext: jax.Array | None = None,
):
    """Oracle step: dense matmul delivery instead of two-stage routing."""
    # f32 throughout: HIGHEST keeps a TPU from rounding the operands to bf16
    drive = jnp.einsum(
        "dst,...s->...dt", dense_w, prev_spikes,
        precision=jax.lax.Precision.HIGHEST,
    )
    if external_drive is not None:
        drive = drive + external_drive
    return neuron_mod.neuron_step(state, drive, params, i_ext)
