"""Continuous-batching AER serving: a multi-tenant DVS session pool.

This is the serving layer the ROADMAP's "heavy traffic from millions of
users" north star asks for, on the paper's flagship workload (§V): many
independent users each holding a card to a DVS sensor, classified in real
time on the shared multi-core fabric. The shape mirrors `serve/engine.py`'s
continuous-batching sketch for LM slots, transcribed to the event engine
(DESIGN.md §12):

  * a **fixed-slot pool**: the engine carry is batched to ``pool_size``
    once; every slot is one tenant's complete fabric state (neuron state,
    previous-step spikes, and — in fabric mode — the in-flight delay-line
    buffer of that tenant's cross-tile events still on the mesh);
  * one **jitted micro-batched step** drives all slots through the batched
    ``EventEngine`` (any dispatch backend: reference / pallas / fused /
    sharded, or fabric mode) — occupancy changes never recompile because
    vacancy is data (zero input, zeroed state), not shape;
  * **independent admit/evict**: a departing tenant's slot is wiped with
    ``EventEngine.reset_slots`` before reuse, so no membrane charge, FIFO
    statistics, or still-in-transit fabric events leak between tenants.

Input enters through ``CompiledCnn.input_activity`` with an explicit
malformed-packet policy (``on_invalid``): "clip"/"drop" sanitize at the
edge, and under "raise" the pool converts the rejection into a *session*
fault (the offending tenant is terminated with ``SessionResult.error``
set) — one bad sensor packet never takes down the other tenants' batch.

Readout is the paper's majority rule: per-session cumulative output-
population spike counts, decided when the leading class crosses a
threshold (latency-to-decision in steps = ms at dt = 1 ms), with a forced
argmax decision at ``max_steps``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from collections import deque
from functools import partial

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation, annotate_function

from repro.core.cnn import CompiledCnn, poker_neuron_params
from repro.core.event_engine import (
    EventEngine,
    ModelRegistry,
    SlotCarry,
    embed_slot_carry,
    slice_slot_carry,
)
from repro.core.tags import RoutingTables
from repro.data.pipeline import DvsStreamConfig, DvsStreamSource

__all__ = [
    "AerServeConfig",
    "DvsSession",
    "SessionResult",
    "AerSessionPool",
    "PoolFullError",
    "SlotError",
    "CheckpointMismatchError",
    "build_poker_engine",
    "session_from_meta",
    "table_v_models",
    "POOL_COUNTERS",
]

# Cumulative counters of a pool (AerSessionPool.counters()):
#   steps, lane_steps, occupied_lane_steps  pool steps, slots stepped, and of
#                                           those the slots holding a session;
#   events_in                               sensor events read from sources;
#   input_bytes, readback_bytes             host->device input, device->host
#                                           spikes, drop and mesh counts;
#   admitted, evicted                       fresh admissions, evictions;
#   decided, forced, errored                evictions by outcome: threshold
#                                           crossed, ended undecided, faulted;
#   queue_dropped, link_dropped             AER-queue and fabric-link drops;
#   queued_sources                          spiking neurons read back: the
#                                           sources the next step's AER
#                                           queue holds;
#   delivered, mesh_hops                    fabric mode: SRAM entries
#                                           delivered, and the chip
#                                           crossings of those entries
#                                           (DeliveryStats.delivered,
#                                           .hops); 0 on other backends.
POOL_COUNTERS = (
    "steps", "lane_steps", "occupied_lane_steps", "events_in", "input_bytes",
    "readback_bytes", "admitted", "evicted", "decided", "forced", "errored",
    "queue_dropped", "link_dropped", "queued_sources", "delivered", "mesh_hops",
)


def session_from_meta(
    sm: dict, models: dict, source_factory=None, slot: int | None = None
) -> DvsSession:
    """Rebuild a :class:`DvsSession` from its checkpoint meta blob entry.

    Shared by :meth:`AerSessionPool.load_snapshot_tree` and the fleet
    restore path (serve/sharded.py), which redistributes a lost shard's
    sessions onto surviving shards from the same per-slot meta entries.
    ``models`` is the restoring pool's resident-model dict (names checked);
    sources that are not a :class:`DvsStreamSource` need ``source_factory``.
    """
    src_meta = sm["source"]
    if src_meta.get("kind") == "dvs_stream":
        source = DvsStreamSource(
            DvsStreamConfig(**src_meta["cfg"]),
            session_id=src_meta["session_id"],
        )
    elif source_factory is not None:
        source = source_factory(sm)
    else:
        raise TypeError(
            f"slot {slot}'s source kind {src_meta.get('kind')!r} is not "
            "serializable — pass source_factory to rebuild it"
        )
    model = sm.get("model")
    if model is None and len(models) == 1:
        model = next(iter(models))
    if model not in models:
        raise CheckpointMismatchError(
            f"slot {slot}'s session ran on model {model!r}, which is "
            f"not resident in the restoring pool ({list(models)})"
        )
    return DvsSession(
        session_id=sm["session_id"],
        source=source,
        label=sm["label"],
        model=model,
        tenant=sm.get("tenant"),
        step=int(sm["step"]),
        counts=None
        if sm["counts"] is None
        else np.asarray(sm["counts"], dtype=np.float64),
        dropped=int(sm["dropped"]),
        link_dropped=int(sm["link_dropped"]),
        error=sm["error"],
    )


class PoolFullError(RuntimeError):
    """``admit`` beyond capacity: no free (non-quarantined) slot remains."""


class SlotError(ValueError):
    """A slot operation addressed an invalid target: index out of range,
    eviction of an unoccupied slot, or quarantine of an occupied one."""


class CheckpointMismatchError(ValueError):
    """A checkpoint's geometry / resident-model fingerprint does not match
    the pool restoring it. Raised *before* any carry state is spliced, so a
    failed restore never corrupts the pool (DESIGN.md §16)."""


def build_poker_engine(
    tables,
    backend: str = "reference",
    donate_carry: bool = True,
    faults=None,
    fabric_options: dict | None = None,
) -> EventEngine:
    """Event engine at the §V serving operating point for a dispatch backend.

    ``backend`` is a built-in backend name (reference / pallas / fused /
    sharded) or ``"fabric"`` for executable-mesh delivery on the default
    3x3-chip board geometry. The AER queue is sized lossless for this
    workload.
    Every served pool builds its engine here (``AerSessionPool.from_models``),
    so examples, chip scripts and the benchmark run the same engine.

    Serving flips the engine's conservative ``donate_carry`` default to
    ``True``: the pool always threads the returned carry and never re-reads
    a stepped one, so on accelerators the pool-sized neuron-state buffers
    are reused in place every step instead of reallocated. On CPU donation
    silently no-ops (results are bit-identical either way — the opt-out is
    for debuggers that want to inspect a pre-step carry after stepping).
    """
    params = poker_neuron_params()
    if not isinstance(tables, RoutingTables) and hasattr(tables, "tables"):
        tables = tables.tables
    q_cap = tables.n_neurons
    if backend == "fabric":
        from repro.core.routing import Fabric

        opts = dict(fabric_options or {})
        if faults is not None:
            opts["faults"] = faults
        return EventEngine(
            tables, params, queue_capacity=q_cap, fabric=Fabric(),
            donate_carry=donate_carry, fabric_options=opts,
        )
    if faults is not None:
        raise ValueError(
            f"fault injection needs the fabric backend, got {backend!r}"
        )
    if fabric_options is not None:
        raise ValueError(
            f"fabric_options need the fabric backend, got {backend!r}"
        )
    return EventEngine(
        tables, params, backend=backend, queue_capacity=q_cap,
        donate_carry=donate_carry,
    )


def table_v_models(rng: np.random.Generator) -> dict[str, CompiledCnn]:
    """The served Table-V network, resident twice (DESIGN.md §16).

    The readout is tuned by one offline-Hebbian calibration run (paper §V)
    on streams drawn from ``rng``. ``"tableV-3x3"`` keeps the 3x3-chip board
    placement; ``"tableV-2x2"`` is the same network bound to a 2x2-chip
    geometry (2 cores per chip, the smallest mesh its 6 cores fit). That
    retarget only re-places the tables — the CNN's spliced input taps live
    in the CAM words — and the resident copy is stripped back to the
    fabric default, since placements compose all-or-none across residents
    (DESIGN.md §18).
    """
    from repro.core.cnn import CnnConfig, compile_poker_cnn, hebbian_readout_select
    from repro.core.compiler import Geometry, artifact_from_tables
    from repro.data.pipeline import symbol_dvs_events

    cc = compile_poker_cnn()
    eng = EventEngine(cc.tables, poker_neuron_params())
    t_steps, reps = 40, 3
    streams = [
        symbol_dvs_events(sym, 400, rng) for sym in range(4) for _ in range(reps)
    ]
    act = cc.input_activity_batch(streams) / t_steps * 10.0
    inp = np.broadcast_to(act[None], (t_steps, *act.shape))
    _, spikes = eng.run(eng.init_state(batch=len(streams)), inp)
    pool_rates = (
        np.asarray(spikes)[:, :, cc.pool[0] : cc.pool[1]]
        .sum(0)
        .reshape(4, reps, -1)
        .sum(1)
    )
    cc = compile_poker_cnn(CnnConfig(), fc_select=hebbian_readout_select(pool_rates))
    geo2 = Geometry(grid_x=2, grid_y=2, cores_per_tile=2, neurons_per_core=256)
    art2 = artifact_from_tables(cc.tables, geo2, optimize=False)
    cc2 = dataclasses.replace(
        cc, tables=dataclasses.replace(art2.tables, tile_of_cluster=None)
    )
    return {"tableV-3x3": cc, "tableV-2x2": cc2}


@dataclasses.dataclass(frozen=True)
class AerServeConfig:
    pool_size: int = 8
    drive: float = 8.0  # event count -> tag-activity gain
    decision_threshold: float = 3.0  # cumulative winning-population spikes
    min_steps: int = 2  # never decide before this many steps
    max_steps: int = 60  # forced argmax decision after this many steps
    on_invalid: str = "raise"  # malformed-packet policy (see CompiledCnn)
    # fairness: at most this many of one tenant's sessions resident at once;
    # the serve() backfill skips over a capped tenant's queued sessions so a
    # burst cannot monopolize freed slots (None = unlimited)
    max_inflight_per_tenant: int | None = None


@dataclasses.dataclass
class DvsSession:
    """One tenant: an event-stream source plus its readout accumulator."""

    session_id: int
    source: DvsStreamSource
    label: int | None = None  # ground truth when known (synthetic streams)
    # which resident model serves this tenant — DATA, never shape: admitting
    # a session on a different model recompiles nothing (DESIGN.md §16).
    # ``None`` resolves to the pool's sole resident model at admission.
    model: str | None = None
    # fairness identity for max_inflight_per_tenant: many sessions may share
    # one tenant (an account / sensor fleet). None = the session is its own
    # tenant, which makes the cap a no-op for anonymous traffic.
    tenant: int | str | None = None
    # runtime state, owned by the pool
    step: int = 0  # steps since admission (= the source's cursor)
    counts: np.ndarray | None = None  # [n_classes] cumulative output spikes
    dropped: int = 0  # cumulative AER-queue drops
    link_dropped: int = 0  # cumulative fabric link-FIFO drops
    error: str | None = None  # input fault: the session failed, not the pool


def _tenant_of(sess: DvsSession):
    return sess.session_id if sess.tenant is None else sess.tenant


@dataclasses.dataclass(frozen=True)
class SessionResult:
    session_id: int
    label: int | None
    prediction: int
    decided: bool  # True: threshold crossed; False: forced at max_steps
    latency_steps: int  # steps from admission to decision
    counts: np.ndarray  # [n_classes] final cumulative output spikes
    dropped: int
    link_dropped: int
    error: str | None = None  # set when the session was terminated on a fault

    @property
    def correct(self) -> bool | None:
        return None if self.label is None else self.prediction == self.label


class AerSessionPool:
    """Fixed-slot continuous batching over the batched event engine.

    ``engine`` may be any :class:`EventEngine` over the compiled CNN's
    tables — queued, fused, sharded or fabric-mode; the pool only assumes
    the batch-native step contract. The carry is allocated once at
    ``pool_size`` and surgically reset per slot on eviction.
    """

    def __init__(
        self,
        cc: CompiledCnn,
        engine: EventEngine,
        cfg: AerServeConfig,
        *,
        models: dict[str, CompiledCnn] | None = None,
        engine_kw: dict | None = None,
    ):
        if cfg.pool_size <= 0:
            raise ValueError(f"pool_size must be positive, got {cfg.pool_size}")
        # registry-of-one by default: the single-model constructor is the
        # degenerate case of multi-model residency (DESIGN.md §16)
        self.models: dict[str, CompiledCnn] = (
            dict(models) if models else {"default": cc}
        )
        self.registry = ModelRegistry(
            {name: m.tables for name, m in self.models.items()}
        )
        combined, self.slabs = self.registry.combined()
        if engine.n_neurons != combined.n_neurons:
            raise ValueError(
                f"engine serves {engine.n_neurons} neurons, compiled CNN has "
                f"{combined.n_neurons}"
            )
        self.cc = cc
        self.engine = engine
        self.cfg = cfg
        self.n_classes = cc.cfg.n_classes
        self._engine_kw = engine_kw  # set by from_models: enables hot-swap
        self.carry = engine.init_state(batch=cfg.pool_size)
        self.slots: list[DvsSession | None] = [None] * cfg.pool_size
        self.n_steps = 0  # engine steps taken (all slots advance together)
        self.quarantined: set[int] = set()  # slots withdrawn from admission
        self.last_stats = None  # DeliveryStats of the most recent step()
        self.counts = dict.fromkeys(POOL_COUNTERS, 0)
        self._zero_act = np.zeros(
            (engine.n_clusters, engine.k_tags), dtype=np.float32
        )
        # observed-traffic feedback (DESIGN.md §18): a fabric engine built
        # with per_link_stats feeds every step's per-pair delivered counts
        # and per-link drops into a TrafficProfile — the empirical traffic
        # matrix live re-placement recompiles against
        self.profile = self._fresh_profile(engine)

    @staticmethod
    def _fresh_profile(engine: EventEngine):
        fb = engine.fabric_backend
        if fb is None or not getattr(fb, "per_link_stats", False):
            return None
        from repro.core.compiler import TrafficProfile

        return TrafficProfile.empty(
            engine.n_clusters, engine.fabric_model.n_tiles
        )

    # -- multi-model residency (DESIGN.md §16) -----------------------------
    @staticmethod
    def _engine_for(models: dict[str, CompiledCnn], engine_kw: dict) -> EventEngine:
        """One engine over the concatenated slabs of every resident model."""
        registry = ModelRegistry(
            {name: m.tables for name, m in models.items()}
        )
        combined, _ = registry.combined()
        return build_poker_engine(combined, **engine_kw)

    @classmethod
    def from_models(
        cls,
        models: dict[str, CompiledCnn],
        cfg: AerServeConfig,
        *,
        backend: str = "reference",
        donate_carry: bool = True,
        faults=None,
        fabric_options: dict | None = None,
    ) -> "AerSessionPool":
        """Pool with N resident models sharing one engine, hot-swap enabled.

        Sessions pick their model by name at admission (``DvsSession.model``)
        — model identity is per-slot data, so serving a mix of tenants on
        different models is one jitted step, no recompile. Pools built this
        way own their engine recipe and support :meth:`load_model` /
        :meth:`unload_model` on a live pool.

        ``fabric_options`` configures the fabric backend (e.g.
        ``{"per_link_stats": True, "link_capacity": k}`` for the observed-
        traffic feedback loop of DESIGN.md §18).
        """
        if not models:
            raise ValueError("from_models needs at least one resident model")
        engine_kw = {
            "backend": backend,
            "donate_carry": donate_carry,
            "faults": faults,
            "fabric_options": fabric_options,
        }
        engine = cls._engine_for(models, engine_kw)
        first = next(iter(models.values()))
        return cls(first, engine, cfg, models=models, engine_kw=engine_kw)

    def fingerprint(self) -> str:
        """Identity of this pool's serving geometry: resident models (tables
        + slab order) × delivery mode × pool size. Checkpoints carry it;
        restore refuses a mismatch (:class:`CheckpointMismatchError`)."""
        mode = (
            "ring"
            if self.engine.fabric_ring
            else "fabric"
            if self.engine.fabric_backend is not None
            else "queued"
        )
        h = hashlib.sha256()
        h.update(self.registry.fingerprint().encode())
        h.update(f"|{mode}|P{self.cfg.pool_size}".encode())
        return h.hexdigest()

    def _resolve_model(self, session: DvsSession) -> str:
        name = session.model
        if name is None:
            if len(self.models) > 1:
                raise ValueError(
                    "session must name its model when several are resident "
                    f"(have {list(self.models)})"
                )
            name = next(iter(self.models))
            session.model = name
        elif name not in self.models:
            raise KeyError(
                f"model {name!r} is not resident (have {list(self.models)})"
            )
        return name

    def load_model(self, name: str, cc: CompiledCnn) -> None:
        """Make ``cc`` resident under ``name`` on the LIVE pool.

        In-flight sessions keep running: their slots are migrated onto the
        rebuilt engine (slab slice -> fresh-init embed -> splice), readout
        accumulators untouched. The rebuild recompiles once; steady-state
        serving of the grown pool never recompiles again.
        """
        if self._engine_kw is None:
            raise RuntimeError(
                "this pool wraps a caller-built engine and cannot rebuild it;"
                " construct with AerSessionPool.from_models to enable hot-swap"
            )
        if name in self.models:
            raise ValueError(f"model {name!r} already resident")
        self._rebind({**self.models, name: cc})

    def unload_model(self, name: str) -> None:
        """Remove a resident model from the LIVE pool (hot-swap ladder's
        final rung: load the replacement, drain its predecessor's sessions,
        unload). Refuses while sessions still run on it."""
        if self._engine_kw is None:
            raise RuntimeError(
                "this pool wraps a caller-built engine and cannot rebuild it;"
                " construct with AerSessionPool.from_models to enable hot-swap"
            )
        if name not in self.models:
            raise KeyError(f"model {name!r} is not resident")
        if len(self.models) == 1:
            raise ValueError("cannot unload the last resident model")
        live = [
            i
            for i, s in enumerate(self.slots)
            if s is not None and s.model == name
        ]
        if live:
            raise RuntimeError(
                f"model {name!r} has live sessions in slots {live}; drain "
                "them before unloading"
            )
        self._rebind(
            {n: m for n, m in self.models.items() if n != name}
        )

    def _rebind(self, new_models: dict[str, CompiledCnn]) -> None:
        """Swap the pool onto a rebuilt engine for ``new_models``, migrating
        every occupied slot's runtime state across the slab re-layout."""
        new_engine = self._engine_for(new_models, self._engine_kw)
        new_registry = ModelRegistry(
            {name: m.tables for name, m in new_models.items()}
        )
        new_slabs = new_registry.slabs()
        new_carry = new_engine.init_state(batch=self.cfg.pool_size)
        occ = self.occupied
        if occ:
            sc = self.engine.extract_slots(self.carry, occ)
            for j, slot in enumerate(occ):
                sess = self.slots[slot]
                row = SlotCarry(
                    state=jax.tree_util.tree_map(
                        lambda x: np.asarray(x)[j : j + 1], sc.state
                    ),
                    spikes=np.asarray(sc.spikes)[j : j + 1],
                    inflight=None
                    if sc.inflight is None
                    else np.asarray(sc.inflight)[j : j + 1],
                )
                part = slice_slot_carry(row, self.slabs[sess.model])
                emb = embed_slot_carry(part, new_engine, new_slabs[sess.model])
                new_carry = new_engine.splice_slots(new_carry, [slot], emb)
        self.models = dict(new_models)
        self.registry = new_registry
        self.slabs = new_slabs
        self.engine = new_engine
        self.carry = new_carry
        self._zero_act = np.zeros(
            (new_engine.n_clusters, new_engine.k_tags), dtype=np.float32
        )
        # measurements made under the old geometry/placement don't describe
        # the new one — restart the observation window
        self.profile = self._fresh_profile(new_engine)

    def clone_onto(
        self, new_engine: EventEngine, cfg: AerServeConfig | None = None
    ) -> "AerSessionPool":
        """New pool on ``new_engine`` (same slab geometry) with every live
        session migrated — the repair path of serve/health.migrate_pool,
        kept here so it preserves multi-model residency."""
        new_pool = AerSessionPool(
            self.cc,
            new_engine,
            cfg or self.cfg,
            models=self.models,
            engine_kw=self._engine_kw,
        )
        occ = self.occupied
        if occ:
            sc = self.engine.extract_slots(self.carry, occ)
            target = [new_pool.admit_restored(self.slots[i]) for i in occ]
            new_pool.carry = new_engine.splice_slots(new_pool.carry, target, sc)
        new_pool.n_steps = self.n_steps
        new_pool.counts = dict(self.counts)
        return new_pool

    def counters(self) -> dict[str, int]:
        """Snapshot of the cumulative counters (:data:`POOL_COUNTERS`) and
        the engine's ``step_traces``/``reset_traces``: the compilations of
        its jitted step and slot reset."""
        return {
            **self.counts,
            "step_traces": self.engine.step_traces,
            "reset_traces": self.engine.reset_traces,
        }

    # -- lifecycle ---------------------------------------------------------
    @property
    def occupied(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    @property
    def free_slots(self) -> list[int]:
        return [
            i
            for i, s in enumerate(self.slots)
            if s is None and i not in self.quarantined
        ]

    def quarantine_slot(self, slot: int) -> None:
        """Withdraw a free slot from admission (suspected-faulty lane).

        The watchdog (serve/health.py) quarantines a slot whose successive
        tenants keep faulting — a lane-correlated symptom the per-session
        retry path cannot fix. Only free slots can be quarantined: evict
        the tenant first so its result (and the slot reset) happen on the
        normal path.
        """
        if not 0 <= slot < self.cfg.pool_size:
            raise SlotError(f"slot {slot} out of range")
        if self.slots[slot] is not None:
            raise SlotError(f"slot {slot} is occupied; evict before quarantine")
        self.quarantined.add(slot)

    def admit(self, session: DvsSession) -> int:
        """Claim a free slot for ``session``; raises :class:`PoolFullError`
        when no admissible slot remains (all occupied or quarantined).

        The slot's fabric state was wiped at the previous tenant's eviction
        (and is all-zero at construction), so the new tenant starts from
        exactly the freshly-initialized state a solo run would see.
        """
        free = self.free_slots
        if not free:
            raise PoolFullError(
                "session pool is full; evict before admitting"
                if len(self.occupied) == self.cfg.pool_size
                else "no admissible slot: the pool's free slots are all "
                "quarantined"
            )
        slot = free[0]
        name = self._resolve_model(session)
        session.step = 0
        session.counts = np.zeros(
            self.models[name].cfg.n_classes, dtype=np.float64
        )
        session.dropped = 0
        session.link_dropped = 0
        session.error = None  # a re-admitted session retries with a clean slate
        self.slots[slot] = session
        self.counts["admitted"] += 1
        return slot

    def admit_restored(self, session: DvsSession) -> int:
        """Claim a free slot for a *mid-flight* session without resetting its
        runtime accumulators — the restore/migration path (DESIGN.md §15).

        The caller owns the matching carry surgery: ``splice_slots`` the
        session's serialized fabric state into the slot this returns
        (restore does; a fresh admit must never take this path).
        """
        free = self.free_slots
        if not free:
            raise PoolFullError("session pool is full; evict before admitting")
        if session.counts is None:
            raise ValueError(
                "admit_restored needs a session with live runtime state — "
                "use admit() for new sessions"
            )
        self._resolve_model(session)
        slot = free[0]
        self.slots[slot] = session
        return slot

    def evict(self, slot: int) -> SessionResult:
        """Finalize and remove the tenant in ``slot``; wipe the slot's state.

        The reset covers the neuron state, the previous-step spike vector,
        and — in fabric mode — the slot's in-flight delay-line buffer:
        cross-tile events the departing tenant still has on the mesh are
        tenant state and must never arrive in the next occupant's network.
        """
        return self.evict_many([slot])[0]

    @partial(annotate_function, name="repro.pool.evict")
    def evict_many(self, slots: list[int]) -> list[SessionResult]:
        """Evict several tenants with ONE masked carry reset.

        ``reset_slots`` rewrites every leaf of the whole pool-sized carry
        regardless of how many slots the mask selects, so evictions that
        land on the same step (synchronized admissions deciding together)
        are folded into a single jitted pass instead of one per tenant.
        """
        slots = list(dict.fromkeys(slots))  # dedupe, preserve order
        # validate before mutating: a bad id must not leave earlier slots
        # freed-but-unreset (the next admit would land on dirty tenant state)
        for slot in slots:
            if not 0 <= slot < self.cfg.pool_size:
                raise SlotError(f"slot {slot} out of range")
            if self.slots[slot] is None:
                raise SlotError(f"slot {slot} is not occupied")
        results = []
        mask = np.zeros(self.cfg.pool_size, dtype=bool)
        for slot in slots:
            sess = self.slots[slot]
            decided, _ = self._decision(sess)
            results.append(
                SessionResult(
                    session_id=sess.session_id,
                    label=sess.label,
                    prediction=int(np.argmax(sess.counts)),
                    decided=decided,
                    latency_steps=sess.step,
                    counts=sess.counts.copy(),
                    dropped=sess.dropped,
                    link_dropped=sess.link_dropped,
                    error=sess.error,
                )
            )
            outcome = (
                "errored" if sess.error is not None
                else "decided" if decided else "forced"
            )
            self.counts[outcome] += 1
            self.slots[slot] = None
            mask[slot] = True
        self.counts["evicted"] += len(slots)
        if mask.any():
            self.carry = self.engine.reset_slots(self.carry, mask)
        return results

    # -- cross-pool migration (DESIGN.md §17) ------------------------------
    def extract_session(self, slot: int) -> tuple[DvsSession, SlotCarry]:
        """Remove the tenant in ``slot`` mid-flight WITH its fabric state.

        The source half of live migration: the returned ``(session,
        SlotCarry)`` pair is the complete transferable unit — readout
        accumulators and stream cursor ride on the session, neuron state /
        previous-step spikes / phase-normalized delay-line contents in the
        :class:`~repro.core.event_engine.SlotCarry`. The vacated slot is
        wiped exactly like an eviction, so the departing tenant leaks
        nothing to the slot's next occupant.
        """
        if not 0 <= slot < self.cfg.pool_size:
            raise SlotError(f"slot {slot} out of range")
        sess = self.slots[slot]
        if sess is None:
            raise SlotError(f"slot {slot} is not occupied")
        sc = self.engine.extract_slots(self.carry, [slot])
        self.slots[slot] = None
        mask = np.zeros(self.cfg.pool_size, dtype=bool)
        mask[slot] = True
        self.carry = self.engine.reset_slots(self.carry, mask)
        return sess, sc

    def inject_session(self, sess: DvsSession, sc: SlotCarry) -> int:
        """Admit a mid-flight session WITH its serialized fabric state.

        The destination half of live migration, inverse of
        :meth:`extract_session` — the destination pool may run on a
        different device mesh and a different delivery mode; ``splice_slots``
        re-buckets the delay horizon and re-rotates the ring phase, so the
        transfer is bit-exact whenever the two engines share tables and
        ``max_delay`` (DESIGN.md §15's ladder, extended to fleet moves in
        §17). Returns the destination slot.
        """
        slot = self.admit_restored(sess)
        self.carry = self.engine.splice_slots(self.carry, [slot], sc)
        return slot

    # -- stepping ----------------------------------------------------------
    def step(self) -> np.ndarray:
        """Advance every slot one engine timestep; returns spikes ``[P, N]``.

        Occupied slots are driven by their session's stream events for the
        session's own step counter; vacant slots see zero input on zeroed
        state (they stay silent — vacancy costs batch lanes, not
        correctness). One jitted engine step serves the whole pool.

        A malformed packet under ``on_invalid="raise"`` faults *its
        session* — the tenant is marked errored (terminated at the next
        eviction sweep) and sees zero input, while every other tenant's
        step proceeds. One bad sensor never takes down the pool.

        Split as :meth:`begin_step` (host-side input gather + engine
        dispatch, returns without blocking on the device) and
        :meth:`finish_step` (reads the results back and applies them to the
        sessions): a multi-shard fleet dispatches every shard's step before
        collecting any, so the shards' device work overlaps
        (serve/sharded.py, DESIGN.md §17).
        """
        with StepTraceAnnotation("repro.pool.step", step_num=self.n_steps):
            return self.finish_step(self.begin_step())

    def begin_step(self):
        """Gather this step's inputs and dispatch the engine step.

        Returns an opaque handle for :meth:`finish_step`. JAX dispatch is
        asynchronous, so this returns as soon as the step is enqueued on the
        device — nothing here blocks on the result.
        """
        inputs = self.gather_inputs()
        with TraceAnnotation("repro.pool.dispatch"):
            self.carry, out = self.engine.step(self.carry, inputs)
        self.counts["input_bytes"] += inputs.nbytes
        return out

    @partial(annotate_function, name="repro.pool.gather")
    def gather_inputs(self) -> np.ndarray:
        """This step's external tag activity ``[P, nc_total, K_max]`` (host).

        Each occupied slot's stream events at the session's own step,
        placed in its model's slab; vacant and faulted slots get zeros.
        """
        multi = len(self.models) > 1
        acts = []
        n_events = 0
        for sess in self.slots:
            if sess is None:
                acts.append(self._zero_act)
                continue
            cc_m = self.models[sess.model]
            events = sess.source.events(sess.step)
            n_events += len(events)
            try:
                a = cc_m.input_activity(events, on_invalid=self.cfg.on_invalid)
            except ValueError as e:
                sess.error = str(e)
                a = None
            if a is None:
                acts.append(self._zero_act)
            elif not multi:
                acts.append(a * self.cfg.drive)
            else:
                # place the model's [nc_m, K_m] activity into its slab of
                # the combined [nc_total, K_max] grid — input addressing is
                # per-slot data, exactly like the model id itself
                slab = self.slabs[sess.model]
                full = np.zeros_like(self._zero_act)
                full[
                    slab.cluster_lo : slab.cluster_hi, : slab.k_tags
                ] = a * self.cfg.drive
                acts.append(full)
        self.counts["events_in"] += n_events
        return np.stack(acts)

    def finish_step(self, out) -> np.ndarray:
        """Block on a dispatched step's results and apply them per session."""
        with TraceAnnotation("repro.pool.readback"):
            spikes, stats = out if isinstance(out, tuple) else (out, None)
            spikes = np.asarray(spikes)
            dropped = None if stats is None else np.asarray(stats.dropped)
            link_dropped = (
                None
                if stats is None or stats.link_dropped is None
                else np.asarray(stats.link_dropped)
            )
            mesh = (
                None
                if stats is None or stats.delivered is None
                else (np.asarray(stats.delivered), np.asarray(stats.hops))
            )
        return self._readout(spikes, stats, dropped, link_dropped, mesh)

    @partial(annotate_function, name="repro.pool.readout")
    def _readout(self, spikes, stats, dropped, link_dropped, mesh) -> np.ndarray:
        """Count the step and add its output spikes and drops to each
        session's accumulators."""
        self.last_stats = stats  # watchdog raw material (serve/health.py)
        self.n_steps += 1
        counts = self.counts
        counts["steps"] += 1
        counts["lane_steps"] += self.cfg.pool_size
        counts["occupied_lane_steps"] += self.cfg.pool_size - self.slots.count(None)
        counts["readback_bytes"] += spikes.nbytes
        counts["queued_sources"] += int(np.count_nonzero(spikes))
        if dropped is not None:
            counts["readback_bytes"] += dropped.nbytes
            counts["queue_dropped"] += int(dropped.sum())
        if link_dropped is not None:
            counts["readback_bytes"] += link_dropped.nbytes
            counts["link_dropped"] += int(link_dropped.sum())
        if mesh is not None:
            delivered, hops = mesh
            counts["readback_bytes"] += delivered.nbytes + hops.nbytes
            counts["delivered"] += int(delivered.sum())
            counts["mesh_hops"] += int(hops.sum())

        if self.profile is not None and stats is not None:
            self.profile.observe(stats)
        if link_dropped is not None and link_dropped.ndim > 1:
            # per_link_stats mode: collapse the [P, T*T] attribution axis for
            # the per-session counters (the profile keeps the full matrix)
            link_dropped = link_dropped.sum(-1)
        for i, sess in enumerate(self.slots):
            if sess is None:
                continue
            # readout at the session's model's slab offset: output population
            # neurons live at slab.neuron_lo + the model's own out range
            cc_m = self.models[sess.model]
            base = self.slabs[sess.model].neuron_lo
            o0, o1 = cc_m.out
            sess.counts += (
                spikes[i, base + o0 : base + o1]
                .reshape(cc_m.cfg.n_classes, -1)
                .sum(-1)
            )
            sess.step += 1
            if dropped is not None:
                sess.dropped += int(dropped[i])
            if link_dropped is not None:
                sess.link_dropped += int(link_dropped[i])
        return spikes

    def _decision(self, sess: DvsSession) -> tuple[bool, bool]:
        """(threshold crossed, finished) for one session."""
        decided = (
            sess.error is None
            and sess.step >= self.cfg.min_steps
            and float(sess.counts.max()) >= self.cfg.decision_threshold
        )
        finished = decided or sess.step >= self.cfg.max_steps or sess.error is not None
        return decided, finished

    @partial(annotate_function, name="repro.pool.decide")
    def finished_slots(self) -> list[int]:
        """Slots whose tenant has reached a decision (or the step cap)."""
        return [
            i
            for i, s in enumerate(self.slots)
            if s is not None and self._decision(s)[1]
        ]

    # -- checkpoint / restore (DESIGN.md §15) ------------------------------
    def _session_meta(self, sess: DvsSession) -> dict:
        src = sess.source
        if isinstance(src, DvsStreamSource):
            source = {
                "kind": "dvs_stream",
                "cfg": dataclasses.asdict(src.cfg),
                "session_id": src.session_id,
            }
        else:
            # restore() rebuilds unknown sources via its source_factory
            source = {"kind": type(src).__name__}
        return {
            "session_id": sess.session_id,
            "label": sess.label,
            "model": sess.model,
            "tenant": sess.tenant,
            "step": sess.step,
            "counts": None if sess.counts is None else sess.counts.tolist(),
            "dropped": sess.dropped,
            "link_dropped": sess.link_dropped,
            "error": sess.error,
            "source": source,
        }

    def snapshot_tree(self) -> dict:
        """The pool's complete checkpointable state as ONE pytree.

        ``{"carry": <engine carry>, "session_meta": <uint8 JSON blob>}`` —
        the raw engine carry (neuron state, previous-step spikes, and the
        complete fabric delay-line state: ring + cursor, or the roll
        in-flight buffer) plus every live session's readout accumulators and
        stream descriptor. :meth:`checkpoint` saves exactly this tree; a
        sharded fleet nests one per shard under its fleet tree
        (serve/sharded.py, DESIGN.md §17).
        """
        meta = {
            "n_steps": self.n_steps,
            "pool_size": self.cfg.pool_size,
            "fingerprint": self.fingerprint(),
            "models": list(self.models),
            "quarantined": sorted(self.quarantined),
            "slots": [
                None if s is None else self._session_meta(s) for s in self.slots
            ],
        }
        blob = np.frombuffer(json.dumps(meta).encode(), dtype=np.uint8).copy()
        return {"carry": self.carry, "session_meta": blob}

    def load_snapshot_tree(self, tree, source_factory=None) -> None:
        """Apply a :meth:`snapshot_tree` onto THIS (freshly built) pool.

        Validates pool size and the serving-geometry fingerprint before any
        state is spliced (:class:`CheckpointMismatchError` on mismatch — a
        failed restore never corrupts the pool), then installs the carry and
        rebuilds every live session from its meta blob.
        """
        meta = json.loads(
            np.asarray(tree["session_meta"]).astype(np.uint8).tobytes().decode()
        )
        if int(meta["pool_size"]) != self.cfg.pool_size:
            raise CheckpointMismatchError(
                f"checkpoint was taken at pool_size={meta['pool_size']}, "
                f"restoring into pool_size={self.cfg.pool_size}"
            )
        want = meta.get("fingerprint")
        if want is not None and want != self.fingerprint():
            raise CheckpointMismatchError(
                f"checkpoint fingerprint {want[:12]}... does not match the "
                f"restoring pool's {self.fingerprint()[:12]}... — the engine "
                "geometry, delivery mode, or resident model set changed "
                "since the snapshot (restore into the matching pool, or "
                "migrate with clone_onto after a bit-exact restore)"
            )
        self.carry = tree["carry"]
        self.n_steps = int(meta["n_steps"])
        self.quarantined = set(int(i) for i in meta["quarantined"])
        for i, sm in enumerate(meta["slots"]):
            if sm is None:
                continue
            self.slots[i] = session_from_meta(
                sm, self.models, source_factory=source_factory, slot=i
            )

    def checkpoint(self, ckptr, step: int | None = None, blocking: bool = False):
        """Snapshot the pool into ``ckptr`` (checkpoint/checkpointer.py).

        One atomic tree (:meth:`snapshot_tree`). A :class:`DvsStreamSource`
        is pure in its step counter, so storing ``(cfg, session_id, step)``
        replays the exact event stream on restore; a restored pool therefore
        resumes *bit-exactly* on an engine of the same geometry. ``step``
        defaults to ``n_steps``.
        """
        ckptr.save(
            self.n_steps if step is None else step,
            self.snapshot_tree(),
            blocking=blocking,
        )

    @classmethod
    def restore(
        cls,
        cc: CompiledCnn,
        engine: EventEngine,
        cfg: AerServeConfig,
        ckptr,
        step: int | None = None,
        source_factory=None,
        models: dict[str, CompiledCnn] | None = None,
    ) -> "AerSessionPool":
        """Rebuild a pool from a :meth:`checkpoint` snapshot.

        ``engine`` must have the checkpointed carry's geometry (same
        neuron/cluster counts and delivery mode — typically the same
        constructor call as the original); resuming is then bit-exact: the
        restored pool's future decisions and decision steps match an
        uninterrupted run. ``step`` defaults to the latest complete
        checkpoint. Sessions whose source was not a
        :class:`DvsStreamSource` need ``source_factory(slot_meta) ->
        source`` to rebuild their stream, otherwise restore raises
        ``TypeError``.
        """
        if step is None:
            step = ckptr.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no complete checkpoint under {ckptr.dir}"
                )
        pool = cls(cc, engine, cfg, models=models)
        like = {"carry": pool.carry, "session_meta": np.zeros(0, np.uint8)}
        try:
            tree = ckptr.restore(step, like)
        except CheckpointMismatchError:
            raise
        except ValueError as e:
            # the checkpointed carry does not even FIT this engine — e.g. a
            # retargeted geometry changed a leaf shape. Refuse before any
            # state is spliced: a failed restore must raise, not corrupt.
            raise CheckpointMismatchError(
                f"checkpoint at step {step} does not fit the restoring "
                f"engine's carry: {e}"
            ) from e
        pool.load_snapshot_tree(tree, source_factory=source_factory)
        return pool

    # -- drain loop --------------------------------------------------------
    @partial(annotate_function, name="repro.pool.admit")
    def admit_next(self, pending: deque) -> DvsSession | None:
        """Admit the first admissible session from the ``pending`` queue.

        FIFO except for fairness: with ``max_inflight_per_tenant`` set, a
        session whose tenant already holds that many slots is skipped (it
        keeps its queue position) and the first under-cap session behind it
        is admitted instead — one tenant submitting a burst can never
        monopolize backfilled slots (DESIGN.md §17). Returns the admitted
        session, or ``None`` when nothing is admissible (queue empty, no
        free slot, or every queued tenant at cap — slots then stay free for
        this step rather than violate the cap).
        """
        if not pending or not self.free_slots:
            return None
        cap = self.cfg.max_inflight_per_tenant
        pick = 0
        if cap is not None:
            inflight: dict = {}
            for s in self.slots:
                if s is not None:
                    t = _tenant_of(s)
                    inflight[t] = inflight.get(t, 0) + 1
            pick = next(
                (
                    i
                    for i, s in enumerate(pending)
                    if inflight.get(_tenant_of(s), 0) < cap
                ),
                None,
            )
            if pick is None:
                return None
        sess = pending[pick]
        del pending[pick]
        self.admit(sess)
        return sess

    def serve(self, sessions) -> list[SessionResult]:
        """Serve ``sessions`` to completion with continuous batching.

        Admissions backfill free slots every step (FIFO, modulo the
        per-tenant in-flight cap — see :meth:`admit_next`), evictions happen
        the step a tenant decides — the pool never drains between users,
        which is what keeps utilization (and sessions/s) flat under
        sustained load. Results are returned in completion order.
        """
        pending = deque(sessions)
        results: list[SessionResult] = []
        while pending or self.occupied:
            while self.admit_next(pending) is not None:
                pass
            self.step()
            finished = self.finished_slots()
            if finished:
                results.extend(self.evict_many(finished))
        return results
