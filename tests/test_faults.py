"""Fault injection, degraded-mode repair, and checkpointed recovery (§15).

The chaos harness for the robustness PR: declarative fault loads
(core/faults.py) must degrade the executable fabric *identically* on both
delivery paths (ring fast path vs roll oracle), the repair pipeline
(compiler.repair_placement -> EventEngine.extract/splice_slots ->
serve/health.migrate_pool) must bring the Table-V poker workload back to
100% accuracy around 25% failed mesh links, and a pool killed mid-serve
must resume bit-exactly from its checkpoint.
"""

import dataclasses
from collections import deque

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from tests._hypothesis_compat import given, settings, st

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.cnn import (
    CnnConfig,
    compile_poker_cnn,
    hebbian_readout_select,
    poker_neuron_params,
)
from repro.core.compiler import repair_placement
from repro.core.event_engine import EventEngine
from repro.core.faults import (
    FaultSpec,
    apply_table_faults,
    entry_alive_mask,
    fault_blast_radius,
    mesh_links,
    pair_fault_matrices,
    tile_fault_matrices,
    xy_path,
)
from repro.core.neuron import NeuronParams
from repro.core.routing import ChipConstants, Fabric
from repro.core.tags import NetworkSpec, compile_network
from repro.data.pipeline import DvsStreamConfig, DvsStreamSource, symbol_dvs_events
from repro.serve.aer import (
    AerServeConfig,
    AerSessionPool,
    DvsSession,
    PoolFullError,
    SlotError,
    build_poker_engine,
)
from repro.serve.health import (
    FaultEvent,
    Watchdog,
    WatchdogConfig,
    migrate_pool,
    serve_resilient,
)

DT = 1e-3
# 25% of the default 3x3 board's 24 directed links, chosen to sever the
# compiled poker placement's tile-0 -> tile-1 forward path in both directions
DEAD25 = ((0, 1), (1, 0), (0, 3), (3, 0), (1, 2), (2, 1))


# ---------------------------------------------------------------------------
# topology model: XY routes vs the fault set
# ---------------------------------------------------------------------------
def test_mesh_links_and_xy_path():
    fab = Fabric()  # 3x3
    links = mesh_links(fab)
    assert len(links) == 24 and len(set(links)) == 24
    assert xy_path(fab, 0, 0) == []
    assert xy_path(fab, 0, 2) == [(0, 1), (1, 2)]  # X first
    assert xy_path(fab, 0, 8) == [(0, 1), (1, 2), (2, 5), (5, 8)]  # then Y
    assert xy_path(fab, 8, 0) == [(8, 7), (7, 6), (6, 3), (3, 0)]
    for path in (xy_path(fab, 0, 8), xy_path(fab, 8, 0)):
        assert all(link in set(links) for link in path)


def test_fault_spec_validation():
    fab = Fabric()
    with pytest.raises(ValueError, match="out of range"):
        FaultSpec(dead_tiles=(9,)).validate(fab)
    with pytest.raises(ValueError, match="not a directed adjacent"):
        FaultSpec(dead_links=((0, 2),)).validate(fab)  # not adjacent
    with pytest.raises(ValueError, match="outside"):
        FaultSpec(link_drop_rate=1.5)
    with pytest.raises(ValueError, match="outside"):
        FaultSpec(link_drop_rate={(0, 1): -0.1})
    assert not FaultSpec().routes_faulted
    assert FaultSpec(dead_links=((0, 1),)).routes_faulted
    assert FaultSpec(link_drop_rate=0.1).routes_faulted


def test_tile_fault_matrices_dead_link_and_tile():
    fab = Fabric()
    alive, rate = tile_fault_matrices(fab, FaultSpec(dead_links=((0, 1),)))
    assert not alive[0, 1] and not alive[0, 2]  # route 0->2 crosses 0->1
    assert alive[1, 0] and alive[2, 0]  # reverse direction untouched
    assert not alive[0, 4]  # 0->4 = X to 1 then Y: crosses the dead link
    assert alive[0, 3] and alive[3, 4]
    # dead tile kills endpoints AND pass-through routes
    alive, _ = tile_fault_matrices(fab, FaultSpec(dead_tiles=(1,)))
    assert not alive[1, 1] and not alive[0, 1] and not alive[1, 2]
    assert not alive[0, 2]  # XY route 0->2 passes through tile 1
    assert alive[0, 3]
    # stochastic rates compound along the path
    _, rate = tile_fault_matrices(fab, FaultSpec(link_drop_rate=0.1))
    np.testing.assert_allclose(rate[0, 2], 1 - 0.9**2)
    np.testing.assert_allclose(rate[0, 8], 1 - 0.9**4)
    assert rate[0, 0] == 0.0


def test_pair_fault_matrices_stuck_cluster_severs_outbound_only():
    fab = Fabric()
    tiles = np.array([0, 1], dtype=np.int32)
    alive, _ = pair_fault_matrices(fab, tiles, FaultSpec(stuck_clusters=(0,)))
    assert not alive[0, 1] and not alive[0, 0]  # nothing leaves cluster 0
    assert alive[1, 0]  # delivery TO it still works
    with pytest.raises(ValueError, match="out of range"):
        pair_fault_matrices(fab, tiles, FaultSpec(stuck_clusters=(5,)))


# ---------------------------------------------------------------------------
# fabric engines under faults: ring/roll parity, drop accounting
# ---------------------------------------------------------------------------
def _two_tile_tables():
    """8-neuron, 2-cluster net on a 1x2 mesh with heavy cross-tile traffic."""
    const = ChipConstants(latency_across_chip_s=2 * DT)
    fab = Fabric(grid_x=2, grid_y=1, cores_per_tile=1, constants=const)
    spec = NetworkSpec(n_neurons=8, cluster_size=4, k_tags=8, max_cam_words=64)
    spec.connect_group([0], [(4, 0)], shared_tag=False, copies=32)
    spec.connect_group([1], [(5, 0)], shared_tag=False, copies=32)
    spec.connect_group([2], [(3, 1)], shared_tag=False, copies=2)  # same-tile
    return compile_network(spec, fabric=fab), fab


def _run_faulted(tables, fab, faults, ring, steps=8, seed=0):
    eng = EventEngine(
        tables,
        NeuronParams(input_gain=3.0, dt=DT),
        fabric=fab,
        queue_capacity=8,
        fabric_options={"dt": DT, "ring": ring, **({"faults": faults} if faults else {})},
    )
    carry = eng.init_state(batch=2)
    rng = np.random.default_rng(seed)
    link_dropped = delivered = n_spikes = 0
    for _ in range(steps):
        i_ext = jnp.asarray((rng.random((2, 8)) < 0.5) * 5e3, jnp.float32)
        carry, (spikes, stats) = eng.step(carry, jnp.zeros((2, 2, 8)), i_ext)
        link_dropped += int(np.asarray(stats.link_dropped).sum())
        delivered += int(np.asarray(stats.delivered).sum())
        n_spikes += int(np.asarray(spikes).sum())
    return link_dropped, delivered, n_spikes


@pytest.mark.parametrize(
    "faults",
    [
        FaultSpec(dead_links=((0, 1),)),
        FaultSpec(link_drop_rate=0.5, seed=3),
        FaultSpec(stuck_clusters=(0,)),
    ],
    ids=["dead-link", "lossy-link", "stuck-cluster"],
)
def test_ring_roll_fault_parity(faults):
    """Both delivery paths consume the same fault mask: identical drop
    counts, delivered counts and spike totals under every fault class."""
    tables, fab = _two_tile_tables()
    ring = _run_faulted(tables, fab, faults, ring=True)
    roll = _run_faulted(tables, fab, faults, ring=False)
    assert ring == roll
    healthy = _run_faulted(tables, fab, None, ring=True)
    assert ring[0] > healthy[0] == 0  # fault drops counted as link drops


def test_dead_link_severs_only_crossing_routes():
    tables, fab = _two_tile_tables()
    ld_dead, delivered_dead, _ = _run_faulted(
        tables, fab, FaultSpec(dead_links=((0, 1),)), ring=True
    )
    _, delivered_healthy, _ = _run_faulted(tables, fab, None, ring=True)
    assert ld_dead > 0
    # same-tile route (2 -> cluster 0's neuron 3) still delivers
    assert delivered_dead > 0
    assert delivered_dead + ld_dead == delivered_healthy


def test_stochastic_erasure_is_deterministic():
    tables, fab = _two_tile_tables()
    fs = FaultSpec(link_drop_rate=0.5, seed=11)
    a = _run_faulted(tables, fab, fs, ring=True)
    b = _run_faulted(tables, fab, fs, ring=True)
    assert a == b  # same seed -> bit-identical fault load
    c = _run_faulted(tables, fab, FaultSpec(link_drop_rate=0.5, seed=12), ring=True)
    assert a != c  # the seed actually drives the draw
    assert 0 < a[0]  # some loss at p=0.5


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_entry_alive_mask_properties(seed):
    """Empty entries stay alive; dead pairs are always severed; the draw is
    a pure function of the spec seed."""
    from repro.core.routing import build_delivery_model

    tables, fab = _two_tile_tables()
    fs = FaultSpec(dead_links=((1, 0),), link_drop_rate=0.3, seed=seed)
    model = build_delivery_model(
        fab, 2, DT, tile_of_cluster=tables.tile_of_cluster, faults=fs
    )
    m1 = entry_alive_mask(tables.src_tag, tables.src_dest, 4, model)
    m2 = entry_alive_mask(tables.src_tag, tables.src_dest, 4, model)
    np.testing.assert_array_equal(m1, m2)
    assert m1[np.asarray(tables.src_tag) < 0].all()  # empty entries alive
    occ = np.asarray(tables.src_tag) >= 0
    dead_pair = occ & (np.arange(8)[:, None] // 4 == 1) & (tables.src_dest == 0)
    assert not m1[dead_pair].any()  # cluster1 -> cluster0 rides the dead link


def test_sharded_step_rejects_faults():
    tables, fab = _two_tile_tables()
    eng = EventEngine(
        tables,
        NeuronParams(input_gain=3.0, dt=DT),
        fabric=fab,
        fabric_options={"dt": DT, "faults": FaultSpec(dead_links=((0, 1),))},
    )
    mesh = jax.sharding.Mesh(np.array(jax.devices("cpu")[:1]), ("data",))
    with pytest.raises(NotImplementedError, match="fault injection"):
        eng.make_sharded_step(mesh, axis="data")


# ---------------------------------------------------------------------------
# memory faults: table corruption + blast radius
# ---------------------------------------------------------------------------
def test_apply_table_faults_blast_radius():
    tables, _ = _two_tile_tables()
    spec = FaultSpec(cam_bit_flips=4, sram_bit_flips=4, seed=5)
    corrupted, report = apply_table_faults(tables, spec)
    assert len(report) == 8
    for f in report:
        assert f["table"] in {"cam_tag", "src_tag", "src_dest"}
        assert f["old"] >= 0  # only programmed words are corrupted
    # fields stay loadable after clipping
    assert np.asarray(corrupted.cam_tag).max() < tables.k_tags
    assert np.asarray(corrupted.src_dest).max() < tables.n_clusters
    radius = fault_blast_radius(tables, corrupted)
    assert radius["connections_before"] > 0
    assert radius["connections_lost"] + radius["connections_kept"] == (
        radius["connections_before"]
    )
    assert radius["blast_fraction"] > 0  # 8 flips on this net must show up
    # same seed -> same corruption (bit-reproducible chaos)
    corrupted2, report2 = apply_table_faults(tables, spec)
    assert report == report2
    np.testing.assert_array_equal(
        np.asarray(corrupted.cam_tag), np.asarray(corrupted2.cam_tag)
    )


def test_apply_table_faults_zero_flips_is_identity():
    tables, _ = _two_tile_tables()
    corrupted, report = apply_table_faults(tables, FaultSpec())
    assert report == []
    np.testing.assert_array_equal(
        np.asarray(corrupted.cam_tag), np.asarray(tables.cam_tag)
    )
    assert fault_blast_radius(tables, corrupted)["blast_fraction"] == 0.0


# ---------------------------------------------------------------------------
# degraded-mode routing repair
# ---------------------------------------------------------------------------
def test_repair_placement_routes_around_25pct_dead_links():
    cc = compile_poker_cnn()
    fs = FaultSpec(dead_links=DEAD25)
    placement, report = repair_placement(cc.tables, Fabric(), fs, seed=0)
    assert report["feasible"]
    assert report["unreachable_traffic"] == 0.0
    alive, _ = tile_fault_matrices(Fabric(), fs)
    from repro.core.compiler import traffic_matrix

    traffic = traffic_matrix(cc.tables)
    src, dst = np.nonzero(traffic > 0)
    for a, b in zip(src, dst):
        if placement[a] != placement[b]:
            assert alive[placement[a], placement[b]]


def test_repair_placement_avoids_dead_tiles():
    cc = compile_poker_cnn()
    fs = FaultSpec(dead_tiles=(0, 1))
    placement, report = repair_placement(cc.tables, Fabric(), fs, seed=0)
    assert report["feasible"]
    assert not set(placement.tolist()) & {0, 1}
    assert report["moved_clusters"]  # default placement used tiles 0 and 1


def test_repair_placement_capacity_error():
    cc = compile_poker_cnn()  # 6 clusters, 4 cores/tile
    fs = FaultSpec(dead_tiles=tuple(range(1, 9)))  # one 4-core tile left
    with pytest.raises(ValueError, match="cannot fit|spare capacity"):
        repair_placement(cc.tables, Fabric(), fs)


# ---------------------------------------------------------------------------
# slot migration: extract_slots / splice_slots
# ---------------------------------------------------------------------------
def _engines_pair():
    tables, fab = _two_tile_tables()
    params = NeuronParams(input_gain=3.0, dt=DT)
    mk = lambda ring: EventEngine(
        tables, params, fabric=fab, queue_capacity=8,
        fabric_options={"dt": DT, "ring": ring},
    )
    return mk(True), mk(False)


@pytest.mark.parametrize("src_ring,dst_ring", [(True, True), (True, False),
                                               (False, True), (False, False)])
def test_extract_splice_cross_mode_bit_exact(src_ring, dst_ring):
    """A slot extracted mid-run (events genuinely in flight, ring cursor at
    an arbitrary phase) and spliced into a fresh engine of either delivery
    mode continues bit-exactly."""
    eng_r, eng_l = _engines_pair()
    src = eng_r if src_ring else eng_l
    dst = eng_r if dst_ring else eng_l
    rng = np.random.default_rng(1)
    carry = src.init_state(batch=2)
    for _ in range(5):  # 5 % (max_delay + 1) != 0: cursor mid-phase
        i_ext = jnp.asarray((rng.random((2, 8)) < 0.5) * 5e3, jnp.float32)
        carry, _ = src.step(carry, jnp.zeros((2, 2, 8)), i_ext)
    moved = dst.splice_slots(
        dst.init_state(batch=2), [0, 1], src.extract_slots(carry, [0, 1])
    )
    rng_a, rng_b = np.random.default_rng(7), np.random.default_rng(7)
    for _ in range(6):
        ia = jnp.asarray((rng_a.random((2, 8)) < 0.5) * 5e3, jnp.float32)
        ib = jnp.asarray((rng_b.random((2, 8)) < 0.5) * 5e3, jnp.float32)
        carry, (sa, _) = src.step(carry, jnp.zeros((2, 2, 8)), ia)
        moved, (sb, _) = dst.step(moved, jnp.zeros((2, 2, 8)), ib)
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))


def test_extract_splice_partial_slots_leave_others_untouched():
    eng, _ = _engines_pair()
    rng = np.random.default_rng(2)
    carry = eng.init_state(batch=3)
    for _ in range(4):
        i_ext = jnp.asarray((rng.random((3, 8)) < 0.5) * 5e3, jnp.float32)
        carry, _ = eng.step(carry, jnp.zeros((3, 2, 8)), i_ext)
    sc = eng.extract_slots(carry, [1])
    target = eng.splice_slots(carry, [2], sc)  # copy slot 1 onto slot 2
    for cur, new in zip(
        jax.tree_util.tree_leaves(carry), jax.tree_util.tree_leaves(target)
    ):
        cur, new = np.asarray(cur), np.asarray(new)
        if cur.ndim == 0:  # shared ring cursor
            np.testing.assert_array_equal(cur, new)
            continue
        np.testing.assert_array_equal(cur[0], new[0])  # untouched, bit-exact
        np.testing.assert_array_equal(cur[1], new[1])
        np.testing.assert_array_equal(cur[1], new[2])  # spliced copy


def test_extract_splice_validation():
    eng, _ = _engines_pair()
    carry = eng.init_state(batch=2)
    with pytest.raises(ValueError, match="unique"):
        eng.extract_slots(carry, [0, 0])
    with pytest.raises(ValueError, match="out of range"):
        eng.extract_slots(carry, [5])
    with pytest.raises(ValueError, match="leading batch dim"):
        eng.extract_slots(eng.init_state(), [0])
    sc = eng.extract_slots(carry, [0])
    with pytest.raises(ValueError, match="slots but SlotCarry"):
        eng.splice_slots(carry, [0, 1], sc)
    other = EventEngine(compile_poker_cnn().tables, poker_neuron_params())
    with pytest.raises(ValueError, match="neurons"):
        other.splice_slots(other.init_state(batch=2), [0], sc)


# ---------------------------------------------------------------------------
# checkpointed pool recovery: kill mid-serve, restore, bit-exact resume
# ---------------------------------------------------------------------------
def _poker_sessions(n, seed=11):
    return [
        DvsSession(
            i,
            DvsStreamSource(
                DvsStreamConfig(symbol=i % 4, events_per_step=16, seed=seed),
                session_id=i,
            ),
            label=i % 4,
        )
        for i in range(n)
    ]


def _result_key(results):
    return sorted(
        (r.session_id, r.prediction, r.latency_steps, r.decided, tuple(r.counts))
        for r in results
    )


@pytest.mark.parametrize("mode", ["queued", "fabric"])
def test_kill_mid_serve_restore_resumes_bit_exact(mode, tmp_path):
    """The §15 acceptance bar: checkpoint at an arbitrary mid-serve step,
    "crash" (rebuild engine + pool from disk), and every surviving
    session's decision AND decision step match the uninterrupted run — in
    queued mode and in fabric-ring mode (the time-wheel ring slab and its
    cursor are part of the checkpoint)."""
    backend = "fabric" if mode == "fabric" else "reference"
    cc = compile_poker_cnn()
    cfg = AerServeConfig(pool_size=2, max_steps=20)
    eng = build_poker_engine(cc.tables, backend=backend, donate_carry=False)
    baseline = AerSessionPool(cc, eng, cfg).serve(_poker_sessions(4))

    ck = Checkpointer(str(tmp_path))
    pool = AerSessionPool(cc, eng, cfg)
    pending = deque(_poker_sessions(4))
    results, killed, k = [], False, 0
    while pending or pool.occupied:
        while pending and pool.free_slots:
            pool.admit(pending.popleft())
        pool.step()
        k += 1
        if k == 5 and not killed:
            pool.checkpoint(ck, blocking=True)
            rest = list(pending)  # the un-admitted backlog outlives the pool
            del pool
            eng2 = build_poker_engine(cc.tables, backend=backend, donate_carry=False)
            pool = AerSessionPool.restore(cc, eng2, cfg, ck)
            assert pool.n_steps == 5 and len(pool.occupied) == 2
            pending = deque(rest)
            killed = True
            continue
        finished = pool.finished_slots()
        if finished:
            results.extend(pool.evict_many(finished))
    assert killed
    assert _result_key(results) == _result_key(baseline)


def test_restore_unknown_source_requires_factory(tmp_path):
    cc = compile_poker_cnn()
    cfg = AerServeConfig(pool_size=2, max_steps=20)
    eng = build_poker_engine(cc.tables, donate_carry=False)
    pool = AerSessionPool(cc, eng, cfg)

    class _Opaque:
        def events(self, step):
            return np.array([[15, 15]])

    pool.admit(DvsSession(0, _Opaque(), label=1))
    pool.step()
    ck = Checkpointer(str(tmp_path))
    pool.checkpoint(ck, blocking=True)
    with pytest.raises(TypeError, match="source_factory"):
        AerSessionPool.restore(cc, eng, cfg, ck)
    rebuilt = AerSessionPool.restore(
        cc, eng, cfg, ck, source_factory=lambda meta: _Opaque()
    )
    assert rebuilt.slots[0].session_id == 0 and rebuilt.slots[0].step == 1


def test_restore_without_checkpoint_raises(tmp_path):
    cc = compile_poker_cnn()
    cfg = AerServeConfig(pool_size=2)
    eng = build_poker_engine(cc.tables, donate_carry=False)
    with pytest.raises(FileNotFoundError, match="no complete checkpoint"):
        AerSessionPool.restore(cc, eng, cfg, Checkpointer(str(tmp_path)))


# ---------------------------------------------------------------------------
# pool typed errors + quarantine (satellite: typed lifecycle errors)
# ---------------------------------------------------------------------------
def test_pool_typed_errors_and_quarantine():
    cc = compile_poker_cnn()
    eng = build_poker_engine(cc.tables, donate_carry=False)
    pool = AerSessionPool(cc, eng, AerServeConfig(pool_size=2, max_steps=20))
    sessions = _poker_sessions(3)
    pool.admit(sessions[0])
    pool.admit(sessions[1])
    with pytest.raises(PoolFullError):
        pool.admit(sessions[2])
    assert issubclass(PoolFullError, RuntimeError)  # legacy handlers survive
    assert issubclass(SlotError, ValueError)
    with pytest.raises(SlotError, match="out of range"):
        pool.evict(99)
    with pytest.raises(SlotError, match="occupied; evict"):
        pool.quarantine_slot(0)
    pool.evict(0)
    with pytest.raises(SlotError, match="not occupied"):
        pool.evict(0)
    pool.quarantine_slot(0)
    assert pool.free_slots == []  # slot 0 quarantined, slot 1 occupied
    with pytest.raises(PoolFullError, match="quarantined"):
        pool.admit(sessions[2])
    with pytest.raises(SlotError, match="out of range"):
        pool.quarantine_slot(-1)


# ---------------------------------------------------------------------------
# watchdog + resilient serve loop
# ---------------------------------------------------------------------------
class _AlwaysBadSource:
    def events(self, step):
        return np.array([[5, -1]])  # malformed on every step


def test_serve_resilient_retries_then_quarantines():
    """Escalation ladder: a faulting tenant retries with backoff through the
    admission queue; when its slot keeps faulting the slot is quarantined;
    with every lane quarantined the backlog fails explicitly."""
    cc = compile_poker_cnn()
    eng = build_poker_engine(cc.tables, donate_carry=False)
    pool = AerSessionPool(cc, eng, AerServeConfig(pool_size=1, max_steps=20))
    wd = Watchdog(WatchdogConfig(max_retries=1, backoff_base=1, quarantine_after=2))
    bad = DvsSession(0, _AlwaysBadSource(), label=1)
    results, events = serve_resilient(pool, [bad], watchdog=wd)
    assert len(results) == 1 and results[0].error is not None
    kinds = [e.kind for e in events]
    assert kinds.count("session-error") == 2  # original + one retry
    assert "slot-quarantined" in kinds
    assert pool.quarantined == {0}
    # the pool is now lane-dead: new work fails fast instead of spinning
    results2, _ = serve_resilient(pool, _poker_sessions(1), watchdog=wd)
    assert results2[0].error == "pool exhausted: all slots quarantined"


def test_serve_resilient_healthy_path_matches_serve():
    cc = compile_poker_cnn()
    cfg = AerServeConfig(pool_size=2, max_steps=20)
    eng = build_poker_engine(cc.tables, donate_carry=False)
    baseline = AerSessionPool(cc, eng, cfg).serve(_poker_sessions(4))
    # silence threshold above the net's readout warm-up horizon: healthy
    # tenants must not be timed out while spikes propagate to the readout
    wd = Watchdog(WatchdogConfig(silence_steps=30))
    results, events = serve_resilient(
        AerSessionPool(cc, eng, cfg), _poker_sessions(4), watchdog=wd
    )
    assert _result_key(results) == _result_key(baseline)
    assert events == []


def test_watchdog_flags_silent_sessions():
    """A fully-severed forward path gives zero readout progress: the
    watchdog times the session out, the loop converts that into a session
    fault, and (retries exhausted) the error result surfaces."""
    cc = compile_poker_cnn()
    fs = FaultSpec(dead_links=((0, 1), (1, 0)))  # severs conv -> pool/out
    eng = build_poker_engine(cc.tables, backend="fabric", donate_carry=False,
                             faults=fs)
    pool = AerSessionPool(cc, eng, AerServeConfig(pool_size=2, max_steps=40))
    wd = Watchdog(WatchdogConfig(silence_steps=6, max_retries=0,
                                 link_drop_threshold=2.0))  # isolate silence
    results, events = serve_resilient(pool, _poker_sessions(2), watchdog=wd)
    assert any(e.kind == "session-silent" for e in events)
    assert all(r.error and "no readout progress" in r.error for r in results)
    assert all(r.latency_steps < 40 for r in results)  # faster than max_steps


# ---------------------------------------------------------------------------
# the degradation acceptance bar: 25% failed links, repair, 100% accuracy
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tuned_cc():
    """Table-V poker CNN with the offline-Hebbian readout calibration the
    §V example uses — the configuration that actually hits 100% accuracy."""
    rng = np.random.default_rng(7)
    cc0 = compile_poker_cnn()
    eng = EventEngine(cc0.tables, poker_neuron_params())
    streams = [symbol_dvs_events(s, 400, rng) for s in range(4) for _ in range(3)]
    act = cc0.input_activity_batch(streams) / 40 * 10.0
    inp = jnp.broadcast_to(jnp.asarray(act)[None], (40, *act.shape))
    _, spikes = eng.run(eng.init_state(batch=len(streams)), inp)
    rates = (
        np.asarray(spikes)[:, :, cc0.pool[0]: cc0.pool[1]]
        .sum(0).reshape(4, 3, -1).sum(1)
    )
    return compile_poker_cnn(CnnConfig(), fc_select=hebbian_readout_select(rates))


def _serve_poker(cc, faults=None, n=8, pool_size=4):
    eng = build_poker_engine(cc.tables, backend="fabric", donate_carry=False,
                             faults=faults)
    results = AerSessionPool(cc, eng, AerServeConfig(pool_size=pool_size)).serve(
        _poker_sessions(n)
    )
    acc = float(np.mean([r.correct for r in results]))
    return acc, sum(r.link_dropped for r in results)


def test_degraded_repair_restores_full_accuracy(tuned_cc):
    """25% of mesh links dead: the unrepaired fabric visibly degrades;
    repair_placement routes around the faults and the same workload is back
    to 100% accuracy with strictly fewer measured link drops."""
    cc = tuned_cc
    fs = FaultSpec(dead_links=DEAD25)
    acc_healthy, ld_healthy = _serve_poker(cc, None)
    assert acc_healthy == 1.0 and ld_healthy == 0
    acc_faulted, ld_faulted = _serve_poker(cc, fs)
    assert acc_faulted < 1.0 and ld_faulted > 0

    placement, report = repair_placement(cc.tables, Fabric(), fs, seed=0)
    assert report["feasible"]
    tables_r = dataclasses.replace(cc.tables, tile_of_cluster=placement)
    cc_r = dataclasses.replace(cc, tables=tables_r)
    eng_r = build_poker_engine(tables_r, backend="fabric", donate_carry=False,
                               faults=fs)
    results = AerSessionPool(cc_r, eng_r, AerServeConfig(pool_size=4)).serve(
        _poker_sessions(8)
    )
    acc_repaired = float(np.mean([r.correct for r in results]))
    ld_repaired = sum(r.link_dropped for r in results)
    assert acc_repaired == 1.0
    assert ld_repaired < ld_faulted


def test_one_dead_link_drops_events_and_repair_reduces_the_drops():
    """One dead mesh link on a pool of 2: the served fabric measures link
    drops, and serving the same sessions on repair_placement's placement
    measures fewer."""
    cc = compile_poker_cnn()
    fs = FaultSpec(dead_links=((0, 1),))
    placement, report = repair_placement(cc.tables, Fabric(), fs, seed=0)
    assert report["feasible"]
    cc_r = dataclasses.replace(
        cc, tables=dataclasses.replace(cc.tables, tile_of_cluster=placement)
    )

    def drops(c):
        eng = build_poker_engine(c.tables, backend="fabric", donate_carry=False,
                                 faults=fs)
        pool = AerSessionPool(c, eng, AerServeConfig(pool_size=2, max_steps=12))
        return sum(r.link_dropped for r in pool.serve(_poker_sessions(4)))

    d_fault = drops(cc)
    assert d_fault > 0
    assert drops(cc_r) < d_fault


def test_degraded_pool_migrates_mid_flight_to_repaired_engine(tuned_cc):
    """Full escalation: watchdog detects the sustained link-drop rate,
    serve_resilient hands the pool to on_degraded, the sessions migrate via
    extract/splice onto an engine with the repaired placement, and the
    workload finishes at 100% accuracy without restarting anyone."""
    cc = tuned_cc
    fs = FaultSpec(dead_links=DEAD25)
    eng_f = build_poker_engine(cc.tables, backend="fabric", donate_carry=False,
                               faults=fs)
    pool = AerSessionPool(cc, eng_f, AerServeConfig(pool_size=4))
    migrations = []

    def on_degraded(p, ev):
        placement, report = repair_placement(cc.tables, Fabric(), fs, seed=0)
        assert report["feasible"]
        tables_r = dataclasses.replace(cc.tables, tile_of_cluster=placement)
        eng_r = build_poker_engine(tables_r, backend="fabric",
                                   donate_carry=False, faults=fs)
        migrations.append(ev.value)
        return migrate_pool(p, eng_r)

    wd = Watchdog(WatchdogConfig(window=4, link_drop_threshold=0.2,
                                 silence_steps=30))
    results, events = serve_resilient(pool, _poker_sessions(8), watchdog=wd,
                                      on_degraded=on_degraded)
    assert len(migrations) == 1 and migrations[0] >= 0.2
    assert [e.kind for e in events].count("pool-degraded") == 1
    assert len(results) == 8
    assert float(np.mean([r.correct for r in results])) == 1.0
