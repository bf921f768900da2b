"""Drive one benchmark cell: build, warm up, measure a window, check it.

The system under test is the program's serving stack, driven through the
same public calls that its ``serve()`` loops make, one iteration at a time,
with sessions released into the pending queue when they are due:

* one pool: ``AerSessionPool.admit_next``, ``step``, ``finished_slots``,
  ``evict_many``;
* a fleet: ``ShardedSessionPool.submit``, ``step``, ``evict_finished``.

With tracing on, host spans (``jax.profiler.TraceAnnotation``) are put
around the program's calls on the instances, so they share the device
trace's clock, and the window is traced by the JAX profiler.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import correct
from bench.spec import ROOT, Cell

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

DRAIN_LIMIT_S = 60.0  # an answer due in the window is waited for this long


@dataclasses.dataclass
class Decided:
    sid: int
    due: float  # seconds from window open
    released: float
    admitted: float | None
    done: float
    shard: int
    result: object  # the program's SessionResult


@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    setup_s: float = 0.0
    window_s: float = 0.0
    steps: int = 0
    decided: list = dataclasses.field(default_factory=list)  # in the window
    window_sessions: int = 0  # sessions due in the window (open loop)
    undecided: int = 0  # of those, never decided
    spans: dict = dataclasses.field(default_factory=lambda: collections.defaultdict(list))
    compiles_in_window: int = 0
    device: dict = dataclasses.field(default_factory=dict)
    trace_data: object = None  # bench.metrics._trace.Trace, traced runs
    checks: dict = dataclasses.field(default_factory=dict)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------
def serve_config(cfg: dict):
    from repro.serve.aer import AerServeConfig

    s = cfg["serve"]
    return AerServeConfig(
        pool_size=cfg["pool_size"], drive=s["drive"],
        decision_threshold=s["threshold"], min_steps=s["min_steps"],
        max_steps=s["max_steps"],
    )


def program_session(sess: dict):
    from repro.data.pipeline import DvsStreamConfig, DvsStreamSource
    from repro.serve.aer import DvsSession

    src = DvsStreamSource(DvsStreamConfig(**sess["stream"]), session_id=sess["stream_id"])
    return DvsSession(sess["id"], src, label=sess["label"], model=sess["model"])


class PoolSystem:
    """One ``AerSessionPool`` on one chip."""

    def __init__(self, cfg: dict, models: dict):
        from repro.serve.aer import AerSessionPool

        self.pool = AerSessionPool.from_models(models, serve_config(cfg), backend=cfg["backend"])
        self.pools = [self.pool]
        self.slots = self.capacity = cfg["pool_size"]

    def admit(self, pending: collections.deque) -> list:
        admitted = []
        while (s := self.pool.admit_next(pending)) is not None:
            admitted.append(s.session_id)
        return admitted

    def step(self) -> None:
        self.pool.step()

    def evict(self) -> list:
        fin = self.pool.finished_slots()
        return [(r, 0) for r in self.pool.evict_many(fin)] if fin else []

    def devices(self) -> list:
        return list(self.pool.carry[1].devices())

    def warm_reset(self) -> None:
        self.pool.carry = self.pool.engine.reset_slots(
            self.pool.carry, np.zeros(self.slots, bool))

    def spans(self, wrap) -> None:
        pool = self.pool
        wrap(pool, "admit_next", "admit")
        wrap(pool, "gather_inputs", "gather_inputs")
        wrap(pool.engine, "step", "engine_step")
        wrap(pool, "finish_step", "finish_step")
        wrap(pool, "finished_slots", "finished_slots")
        wrap(pool, "evict_many", "evict_many")


class FleetSystem:
    """A ``ShardedSessionPool``: one shard on each chip."""

    def __init__(self, cfg: dict, models: dict):
        import jax

        from repro.serve.sharded import ShardConfig, ShardedSessionPool

        fl = cfg["fleet"]
        shards = ShardConfig(n_shards=fl["n_shards"], queue_depth=fl["queue_depth"],
                             backend=cfg["backend"])
        first = next(iter(models.values()))
        self.fleet = ShardedSessionPool(first, serve_config(cfg), shards, models=models,
                                        devices=jax.devices()[: fl["n_shards"]])
        self.pools = list(self.fleet.pools)
        ids = [tuple(d.id for d in p.engine.mesh.devices.flat) for p in self.pools]
        flat = [d for ds in ids for d in ds]
        if len(set(flat)) != len(flat) or len(flat) != fl["n_shards"]:
            raise RuntimeError(f"shards share devices: {ids}")
        self.slots = fl["n_shards"] * cfg["pool_size"]
        self.capacity = fl["n_shards"] * (cfg["pool_size"] + fl["queue_depth"])
        self.shard_of: dict[int, int] = {}

    def admit(self, pending: collections.deque) -> list:
        from repro.serve.sharded import AdmissionError

        admitted = []
        while pending:
            try:
                shard = self.fleet.submit(pending[0])
            except AdmissionError:
                break
            sid = pending.popleft().session_id
            self.shard_of[sid] = shard
            admitted.append(sid)
        return admitted

    def step(self) -> None:
        self.fleet.step()

    def evict(self) -> list:
        return [(r, self.shard_of.pop(r.session_id)) for r in self.fleet.evict_finished()]

    def devices(self) -> list:
        return [d for p in self.pools for d in p.carry[1].devices()]

    def warm_reset(self) -> None:
        for p in self.pools:
            p.carry = p.engine.reset_slots(p.carry, np.zeros(p.cfg.pool_size, bool))

    def spans(self, wrap) -> None:
        wrap(self.fleet, "submit", "admit")
        for p in self.pools:
            wrap(p, "gather_inputs", "gather_inputs")
            wrap(p.engine, "step", "engine_step")
            wrap(p, "finish_step", "finish_step")
            wrap(p, "finished_slots", "finished_slots")
            wrap(p, "evict_many", "evict_many")


def build_system(cfg: dict, models: dict):
    return FleetSystem(cfg, models) if cfg.get("fleet") else PoolSystem(cfg, models)


def resident_models(cfg: dict) -> dict:
    from repro.serve.aer import table_v_models

    built = table_v_models(np.random.default_rng(cfg["network_seed"]))
    return {name: built[name] for name in cfg["models"]}


def snapshot(system) -> list:
    """Each pool's neuron state (an on-device copy, taken without waiting)
    and its in-flight sessions, as the window closes."""
    import jax

    out = []
    for p in system.pools:
        state = jax.tree.map(lambda x: x.copy(), p.carry[0])
        live = [
            (slot, s.session_id, s.model, s.step, s.counts.copy())
            for slot, s in enumerate(p.slots) if s is not None
        ]
        out.append((state, live))
    return out


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------
def span_wrapper(record: dict):
    import jax

    def wrap(obj, attr: str, name: str) -> None:
        fn = getattr(obj, attr)
        label = f"bench.{name}"

        def wrapped(*args, **kwargs):
            t0 = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(label):
                out = fn(*args, **kwargs)
            record[name].append(time.perf_counter_ns() - t0)
            return out

        setattr(obj, attr, wrapped)

    return wrap


class CompileCounter:
    """Counts XLA compilations while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0

        def listener(event: str, duration: float, **kw) -> None:
            if self.active and "backend_compile" in event:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
class Driver:
    """Releases due sessions, admits, steps and evicts, and stamps times."""

    def __init__(self, system, traffic, to_program):
        self.system = system
        self.traffic = traffic
        self.to_program = to_program
        self.pending: collections.deque = collections.deque()
        self.next_id = 0
        self.t0 = 0.0  # window open, perf_counter seconds
        self.info: dict[int, list] = {}  # sid -> [due, released, admitted]
        self.done: list = []  # (sid, done time, result, shard)

    def release(self, now: float, until: float | None = None, most: int | None = None) -> None:
        """Queue the sessions due by ``now`` (closed loop: keep the queue
        full, or add ``most`` sessions)."""
        tr = self.traffic
        if tr.closed:
            n = self.system.capacity - len(self.pending) if most is None else most
            for _ in range(n):
                self._queue(self.next_id, now - self.t0, now)
            return
        limit = (now - self.t0) if until is None else until
        while self.next_id < len(tr.due) and tr.due[self.next_id] <= limit:
            self._queue(self.next_id, float(tr.due[self.next_id]), now)

    def _queue(self, sid: int, due: float, now: float) -> None:
        self.pending.append(self.to_program(self.traffic.session(sid)))
        self.info[sid] = [due, now - self.t0, None]
        self.next_id = sid + 1

    def iterate(self) -> None:
        sys_ = self.system
        admitted = sys_.admit(self.pending)
        now = time.perf_counter() - self.t0
        for sid in admitted:
            self.info[sid][2] = now
        sys_.step()
        out = sys_.evict()
        now = time.perf_counter() - self.t0
        for r, shard in out:
            self.done.append((r.session_id, now, r, shard))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             hook=None) -> Run:
    """Build, warm up, measure and check one cell; returns the run's record.

    ``t_start`` is when the process started (``perf_counter``): set-up is
    counted from it. ``hook(system)`` is called once the system is built.
    """
    import jax

    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()
    run = Run(cell, seed, seconds, trace)
    cfg, mix = cell.cfg, cell.mix
    counter = CompileCounter()
    models = resident_models(cfg)
    system = build_system(cfg, models)
    if hook is not None:
        hook(system)
    traffic = cell.generator().build(mix, cfg["models"], seed, seconds)
    drv = Driver(system, traffic, program_session)

    # -- warm-up: every shape the window uses, then a steady pool --------
    snapshot(system)
    system.warm_reset()
    if traffic.closed:
        # fill the slots over ``fill_steps`` steps: their ages are then
        # spread as in a steady state, and decisions do not come in waves
        drv.t0 = time.perf_counter()
        fill = int(mix["fill_steps"])
        per_step = -(-system.slots // fill)
        for _ in range(fill):
            drv.release(time.perf_counter(), most=per_step)
            drv.iterate()
        drv.done.clear()
    else:
        system.step()  # an empty pool: compiles the step with every slot vacant
        drv.t0 = time.perf_counter() + float(mix["lead_s"])
        while time.perf_counter() < drv.t0:
            drv.release(time.perf_counter())
            drv.iterate()
        drv.done.clear()

    # -- the window -----------------------------------------------------
    tmp = None
    if trace:
        system.spans(span_wrapper(run.spans))
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
    t_open = time.perf_counter()
    run.setup_s = t_open - t_start
    if traffic.closed:
        drv.t0 = t_open
    t_end = t_open + seconds
    counter.active = True
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            drv.release(now)
            drv.iterate()
            run.steps += 1
    t_close = time.perf_counter()
    counter.active = False
    run.compiles_in_window = counter.count
    if trace:
        jax.profiler.stop_trace()
    run.window_s = t_close - t_open
    snap = snapshot(system)

    # -- the answers due in the window ----------------------------------
    window_done = [d for d in drv.done if d[1] <= run.window_s]
    if traffic.closed:
        chosen = window_done
    else:
        # every session due in the window is waited for, a minute at most
        drv.release(time.perf_counter(), until=run.window_s)
        due_ids = {sid for sid, info in drv.info.items() if 0.0 <= info[0] < run.window_s}
        limit = time.perf_counter() + DRAIN_LIMIT_S
        got = {d[0] for d in drv.done}
        while not due_ids <= got and time.perf_counter() < limit:
            drv.iterate()
            got = {d[0] for d in drv.done}
        chosen = [d for d in drv.done if d[0] in due_ids]
        run.window_sessions = len(due_ids)
        run.undecided = len(due_ids - got)
    run.decided = [
        Decided(sid, drv.info[sid][0], drv.info[sid][1], drv.info[sid][2], t, shard, r)
        for sid, t, r, shard in chosen
    ]
    run.device = device_record(system)

    # -- free the program, then check against the reference --------------
    snap = [(jax.device_get(state), live) for state, live in snap]
    pool_meta = [dict(p.slabs) for p in system.pools]
    del system, drv, models
    gc.collect()
    jax.clear_caches()
    run.checks = correct.check(cell, run, snap, pool_meta)

    if trace:
        from bench.metrics import _trace

        try:
            run.trace_data = _trace.load(tmp, run.device["ids"])
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return run


def device_record(system) -> dict:
    devs = system.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d0 = devs[0]
    return {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
        "ids": [d.id for d in devs],
    }
