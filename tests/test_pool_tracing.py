"""The serving pool's spans, device scopes and counters.

The pool and the fleet write host spans (``repro.*``) into the profiler's
trace, the jitted step names its stages with ``jax.named_scope``, and the
pool keeps cumulative counters. These tests check that the counters add up
over a served workload, that every span is present and nested under the
step that holds it, and that the compiled programs carry the scopes.
"""

import collections
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core.cnn import compile_poker_cnn
from repro.data.pipeline import DvsStreamConfig, DvsStreamSource
from repro.serve.aer import (
    POOL_COUNTERS,
    AerServeConfig,
    AerSessionPool,
    DvsSession,
    build_poker_engine,
)
from repro.serve.sharded import AdmissionError, ShardConfig, ShardedSessionPool
from test_serving import _BadPacketSource

POOL_STEP_SPANS = ("repro.pool.gather", "repro.pool.dispatch",
                   "repro.pool.readback", "repro.pool.readout")


def _session(i: int, events_per_step: int = 16) -> DvsSession:
    cfg = DvsStreamConfig(symbol=i % 4, events_per_step=events_per_step, seed=9)
    return DvsSession(i, DvsStreamSource(cfg, session_id=i), label=i % 4)


@pytest.fixture(scope="module")
def cc():
    return compile_poker_cnn()


def _pool(cc, pool_size=4, max_steps=25, backend="reference"):
    return AerSessionPool(cc, build_poker_engine(cc.tables, backend=backend),
                          AerServeConfig(pool_size=pool_size, max_steps=max_steps))


def test_pool_counters_add_up(cc):
    """A 4-slot pool serving 20 sessions, one of them faulted: every
    admission is evicted or in flight, every eviction has one outcome, every
    slot-step is occupied or vacant, and every source event is counted."""
    pool = _pool(cc, max_steps=20)
    # 4 events a step never decide in 20 steps; 32 and 64 decide in 15-19
    sessions = [_session(i, events_per_step=(4, 32, 64)[i % 3]) for i in range(19)]
    sessions.append(DvsSession(19, _BadPacketSource(bad_at=2), label=1))
    pending = collections.deque(sessions)
    served = collections.Counter()  # session id -> steps it was served
    vacant = 0
    results = []
    while pending or pool.occupied:
        while pool.admit_next(pending) is not None:
            pass
        c = pool.counters()
        assert c["admitted"] == c["evicted"] + len(pool.occupied)
        vacant += pool.cfg.pool_size - len(pool.occupied)
        for s in pool.slots:
            if s is not None:
                served[s.session_id] += 1
        pool.step()
        fin = pool.finished_slots()
        if fin:
            results.extend(pool.evict_many(fin))
    c = pool.counters()
    assert set(POOL_COUNTERS) <= set(c)
    assert c["admitted"] == c["evicted"] == len(sessions) == len(results)
    assert c["decided"] + c["forced"] + c["errored"] == c["evicted"]
    assert c["decided"] == sum(r.decided for r in results) > 0
    assert c["errored"] == sum(r.error is not None for r in results) == 1
    assert c["forced"] == sum(not r.decided and r.error is None for r in results) > 0
    assert c["occupied_lane_steps"] + vacant == c["steps"] * pool.cfg.pool_size
    assert c["lane_steps"] == c["steps"] * pool.cfg.pool_size
    assert c["steps"] == pool.n_steps
    events = sum(len(s.source.events(k)) for s in sessions for k in range(served[s.session_id]))
    assert c["events_in"] == events
    assert c["queue_dropped"] == sum(r.dropped for r in results)
    assert c["link_dropped"] == 0
    n, nc, k = pool.engine.n_neurons, pool.engine.n_clusters, pool.engine.k_tags
    assert c["input_bytes"] == c["steps"] * pool.cfg.pool_size * nc * k * 4
    assert c["readback_bytes"] == c["steps"] * pool.cfg.pool_size * (n * 4 + 4)
    # one compilation of the step and one of the slot reset, however many
    # sessions came and went
    assert c["step_traces"] == 1 and c["reset_traces"] == 1


def test_fleet_counters_sum_shards_and_count_refusals(cc):
    fleet = ShardedSessionPool(cc, AerServeConfig(pool_size=2, max_steps=25),
                               ShardConfig(n_shards=2, queue_depth=1))
    sessions = [_session(i) for i in range(8)]
    accepted = 0
    for s in sessions:
        try:
            fleet.submit(s)
            accepted += 1
        except AdmissionError:
            pass
    c = fleet.counters()
    assert c["submitted"] == accepted == 6 and c["refused"] == 2
    fleet.step()
    results = fleet.evict_finished()
    c = fleet.counters()
    shards = [p.counters() for p in fleet.pools]
    for k in POOL_COUNTERS:
        assert c[k] == sum(s[k] for s in shards), k
    assert c["admitted"] == 4 and c["steps"] == 2
    assert c["admitted"] == c["evicted"] + sum(len(p.occupied) for p in fleet.pools)
    assert c["evicted"] == len(results)


def test_queued_sources_counts_the_spikes_read_back(cc):
    """``queued_sources`` adds up the non-zero spikes every step reads back
    (the sources the next step's queue holds), and a fleet sums it."""
    pool = _pool(cc)
    for i in range(4):
        pool.admit(_session(i, events_per_step=64))
    spiked = sum(int(np.count_nonzero(pool.step())) for _ in range(6))
    assert pool.counters()["queued_sources"] == spiked > 0

    fleet = ShardedSessionPool(cc, AerServeConfig(pool_size=2, max_steps=25),
                               ShardConfig(n_shards=2, queue_depth=2))
    for i in range(4):
        fleet.submit(_session(i, events_per_step=64))
    for _ in range(6):
        fleet.step()
    shards = [p.counters()["queued_sources"] for p in fleet.pools]
    assert fleet.counters()["queued_sources"] == sum(shards)
    assert all(q > 0 for q in shards)


def _step_mesh_stats(pool) -> tuple[int, int]:
    """One step's delivered SRAM entries and their chip crossings."""
    pool.step()
    stats = pool.last_stats
    return int(np.asarray(stats.delivered).sum()), int(np.asarray(stats.hops).sum())


def test_mesh_counters_sum_the_steps_delivery_stats(cc):
    """A fabric pool counts the SRAM entries it delivered and their chip
    crossings, as each step's ``DeliveryStats`` give them; under the 3x3
    board's placement (cores 0-3 on one chip, 4-5 on the next) the conv
    layer's events cross. A fused pool reads back no mesh counts."""
    pool = _pool(cc, backend="fabric")
    assert list(pool.engine.fabric_model.tile_of_cluster) == [0, 0, 0, 0, 1, 1]
    for i in range(4):
        pool.admit(_session(i, events_per_step=64))
    steps = [_step_mesh_stats(pool) for _ in range(6)]
    c = pool.counters()
    assert c["delivered"] == sum(d for d, _ in steps) > 0
    assert c["mesh_hops"] == sum(h for _, h in steps) > 0

    fused = _pool(cc, backend="fused")
    for i in range(4):
        fused.admit(_session(i, events_per_step=64))
    for _ in range(6):
        fused.step()
    c = fused.counters()
    assert c["queued_sources"] > 0
    assert c["delivered"] == c["mesh_hops"] == 0


def test_fleet_sums_the_mesh_counters_over_its_shards(cc):
    fleet = ShardedSessionPool(cc, AerServeConfig(pool_size=2, max_steps=25),
                               ShardConfig(n_shards=2, queue_depth=2, backend="fabric"))
    for i in range(4):
        fleet.submit(_session(i, events_per_step=64))
    for _ in range(6):
        fleet.step()
    shards = [p.counters() for p in fleet.pools]
    c = fleet.counters()
    for k in ("delivered", "mesh_hops"):
        assert c[k] == sum(s[k] for s in shards), k
        assert all(s[k] > 0 for s in shards), k


def _host_events(log_dir: str) -> list:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                        for e in line.events if e.name.startswith("repro.")]
    return out


def _within(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_pool_spans_nest_under_their_step(cc, tmp_path):
    """A profiled pool serves a few steps: each step's gather, dispatch,
    readback and readout lie inside the ``repro.pool.step`` span that
    carries the step's number, and admission, decisions and eviction have
    spans of their own."""
    pool = _pool(cc, pool_size=2)
    pending = collections.deque(_session(i) for i in range(3))
    first = pool.n_steps
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            while pool.admit_next(pending) is not None:
                pass
            pool.step()
            pool.evict_many(pool.finished_slots())
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    names = collections.Counter(e[0] for e in events)
    for name in ("repro.pool.admit", "repro.pool.decide", "repro.pool.evict"):
        assert names[name] >= 3, name
    steps = sorted((e for e in events if e[0] == "repro.pool.step"), key=lambda e: e[1])
    assert [int(e[3]["step_num"]) for e in steps] == [first, first + 1, first + 2]
    for name in POOL_STEP_SPANS:
        spans = [e for e in events if e[0] == name]
        assert len(spans) == 3, name
        for step in steps:
            assert sum(_within(s, step) for s in spans) == 1, (name, step[3])
    # the blocking reads come before the readout, inside the same step
    for step in steps:
        inside = sorted((e for e in events if _within(e, step) and e is not step),
                        key=lambda e: e[1])
        assert [e[0] for e in inside] == list(POOL_STEP_SPANS)


def test_fleet_spans_nest_under_the_fleet_step(cc, tmp_path):
    fleet = ShardedSessionPool(cc, AerServeConfig(pool_size=2, max_steps=25),
                               ShardConfig(n_shards=2, queue_depth=2))
    fleet.step()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            fleet.submit(_session(i))
        fleet.step()
        fleet.step()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    assert sum(e[0] == "repro.fleet.submit" for e in events) == 3
    steps = sorted((e for e in events if e[0] == "repro.fleet.step"), key=lambda e: e[1])
    assert [int(e[3]["step_num"]) for e in steps] == [1, 2]
    for name in POOL_STEP_SPANS:
        for step in steps:  # one span of each shard inside each fleet step
            assert sum(_within(e, step) for e in events if e[0] == name) == 2, name
    assert not any(e[0] == "repro.pool.step" for e in events)


def _scopes(text: str) -> set:
    return {part for path in re.findall(r'op_name="([^"]*)"', text)
            for part in re.split(r"[/;]", path)}


@pytest.mark.parametrize("backend,want", [
    ("fused", {"deliver", "compact", "stage1", "stage2", "neuron_update"}),
    ("reference", {"deliver", "stage1", "stage2", "neuron_update"}),
    ("fabric", {"deliver", "compact", "link_arbitration", "stage2", "neuron_update"}),
])
def test_compiled_step_carries_the_scopes(cc, backend, want):
    pool = _pool(cc, pool_size=2, backend=backend)
    eng = pool.engine
    inputs = jax.ShapeDtypeStruct((2, eng.n_clusters, eng.k_tags), np.float32)
    text = eng.compiled_step_text(pool.carry, inputs)
    assert want <= _scopes(text)
    assert eng.step_traces == 1


def test_compiled_reset_carries_its_scope(cc):
    pool = _pool(cc, pool_size=2)
    pool.admit(_session(0))
    pool.step()
    pool.evict_many([0])
    text = pool.engine._jit_reset.lower(pool.carry, np.zeros(2, bool)).compile().as_text()
    assert "reset_slots" in _scopes(text)
    assert pool.counters()["reset_traces"] == 1
