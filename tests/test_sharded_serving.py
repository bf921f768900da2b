"""Sharded session fleet (serve/sharded.py, DESIGN.md §17).

In-process tests run every shard on a ``(1, 1)`` mesh — the
:class:`ShardedEventEngine` code path is identical with or without real
devices, so admission, migration and elastic-restart semantics are covered
at full speed. Multi-device placement (disjoint device sets per shard,
cluster-axis sharding under ``device_slab_placement``, cross-mesh
migration) runs in subprocesses with fake CPU devices, same pattern as
tests/test_distributed.py.
"""

import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.cnn import compile_poker_cnn
from repro.data.pipeline import DvsStreamConfig, DvsStreamSource
from repro.serve.aer import (
    AerServeConfig,
    AerSessionPool,
    CheckpointMismatchError,
    DvsSession,
    build_poker_engine,
)
from repro.serve.health import FleetWatchdog
from repro.serve.sharded import (
    AdmissionError,
    ShardConfig,
    ShardedSessionPool,
)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    # fake CPU devices; the child never reaches for an accelerator, which
    # the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, text=True, env=env, cwd=_ROOT, timeout=timeout,
    )
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


@pytest.fixture(scope="module")
def cc():
    return compile_poker_cnn()


def _session(i, symbol, tenant=None):
    return DvsSession(
        i,
        DvsStreamSource(
            DvsStreamConfig(symbol=symbol, events_per_step=16, seed=9),
            session_id=i,
        ),
        label=symbol,
        tenant=tenant,
    )


def _drain(fleet, res=None):
    res = {} if res is None else res
    while fleet.busy:
        fleet.step()
        for r in fleet.evict_finished():
            res[r.session_id] = r
    return res


def _fleet(cc, n_shards=2, pool_size=2, queue_depth=2, backend="reference",
           max_steps=25):
    return ShardedSessionPool(
        cc,
        AerServeConfig(pool_size=pool_size, max_steps=max_steps),
        ShardConfig(n_shards=n_shards, queue_depth=queue_depth,
                    backend=backend),
    )


# ---------------------------------------------------------------------------
# layer 1+2: fleet stepping and admission control
# ---------------------------------------------------------------------------
def test_admission_balances_by_traffic_score(cc):
    fleet = _fleet(cc, n_shards=2)
    picks = [fleet.submit(_session(i, i % 4)) for i in range(4)]
    # least-loaded routing alternates on an empty symmetric fleet
    assert sorted(picks) == [0, 0, 1, 1]
    occ = fleet.occupancy()
    assert occ[0][1] + occ[1][1] == 4  # all queued until the first backfill
    fleet.step()
    occ = fleet.occupancy()
    assert occ[0] == (2, 0) and occ[1] == (2, 0)


def test_admission_bounded_queue_raises_typed_error(cc):
    fleet = _fleet(cc, n_shards=2, pool_size=2, queue_depth=2)
    # capacity before any step: per shard 2 slot-bound + 2 overflow
    for i in range(8):
        fleet.submit(_session(i, i % 4))
    with pytest.raises(AdmissionError, match="queue_depth"):
        fleet.submit(_session(99, 0))
    # serving drains the backlog; everything completes
    res = _drain(fleet)
    assert set(res) == set(range(8))


def test_admission_rejects_unknown_model(cc):
    fleet = _fleet(cc, n_shards=2)
    sess = _session(0, 0)
    sess.model = "nope"
    with pytest.raises(KeyError, match="not resident"):
        fleet.submit(sess)


def test_fleet_serve_matches_solo_pool_bit_exact(cc):
    fleet = _fleet(cc, n_shards=2, pool_size=2)
    res = {r.session_id: r
           for r in fleet.serve([_session(i, i % 4) for i in range(8)])}
    solo = AerSessionPool(
        cc, build_poker_engine(cc.tables),
        AerServeConfig(pool_size=2, max_steps=25),
    )
    ref = {r.session_id: r
           for r in solo.serve([_session(i, i % 4) for i in range(8)])}
    assert set(res) == set(ref) == set(range(8))
    for sid in ref:
        assert np.array_equal(res[sid].counts, ref[sid].counts), sid
        assert res[sid].prediction == ref[sid].prediction
        assert res[sid].latency_steps == ref[sid].latency_steps


def test_fleet_stats_sums_shards(cc):
    fleet = _fleet(cc, n_shards=2, backend="fabric")
    assert fleet.fleet_stats() is None  # nothing stepped yet
    for i in range(4):
        fleet.submit(_session(i, i % 4))
    for _ in range(6):
        fleet.step()
    stats = fleet.fleet_stats()
    assert stats is not None and int(stats.delivered) > 0
    per_shard = sum(
        int(np.asarray(fleet.pools[i].last_stats.delivered).sum())
        for i in fleet.live_shards()
    )
    assert int(stats.delivered) == per_shard


def test_fleet_watchdog_scans_every_shard(cc):
    fleet = _fleet(cc, n_shards=2, backend="fabric")
    wd = FleetWatchdog()
    for i in range(4):
        fleet.submit(_session(i, i % 4))
    for _ in range(4):
        fleet.step()
        events = wd.observe(fleet)
        assert all(shard in (0, 1) for shard, _ in events)
    assert set(wd._per_shard) == {0, 1}
    assert wd.link_drop_rate() >= 0.0


# ---------------------------------------------------------------------------
# layer 3: live migration and drain
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["reference", "fabric"])
def test_migration_mid_flight_is_invariant(cc, backend):
    """A tenant migrated between shards mid-serve finishes with results
    byte-equal to the undisturbed run — neuron state, queued spikes and the
    phase-normalized in-flight fabric slab all survive the move."""

    def run(migrate):
        fleet = _fleet(cc, n_shards=2, backend=backend)
        fleet.submit(_session(10, 2))
        fleet.submit(_session(11, 1))
        for _ in range(4):
            fleet.step()
        if migrate:
            shard, _ = fleet.locate(10)
            fleet.migrate(10, 1 - shard)
            assert fleet.locate(10)[0] == 1 - shard
        return _drain(fleet)

    ref, moved = run(False), run(True)
    for sid in (10, 11):
        assert np.array_equal(ref[sid].counts, moved[sid].counts), sid
        assert ref[sid].prediction == moved[sid].prediction
        assert ref[sid].latency_steps == moved[sid].latency_steps


def test_migrate_validates_destination(cc):
    fleet = _fleet(cc, n_shards=2)
    fleet.submit(_session(0, 0))
    fleet.step()
    with pytest.raises(KeyError, match="not resident"):
        fleet.locate(77)
    fleet.kill_shard(1)
    with pytest.raises(ValueError, match="not live"):
        fleet.migrate(0, 1)


def test_drain_shard_moves_everything(cc):
    fleet = _fleet(cc, n_shards=2, pool_size=4)
    for i in range(4):
        fleet.submit(_session(i, i % 4))
    for _ in range(3):
        fleet.step()
    moved = fleet.drain_shard(0)
    assert moved == 2
    assert fleet.occupancy()[0] == (0, 0)
    res = _drain(fleet)
    assert set(res) == set(range(4))


def test_drain_shard_raises_when_no_room(cc):
    fleet = _fleet(cc, n_shards=2, pool_size=2)
    for i in range(4):
        fleet.submit(_session(i, i % 4))
    fleet.step()  # both shards full
    with pytest.raises(AdmissionError, match="cannot drain"):
        fleet.drain_shard(0)


# ---------------------------------------------------------------------------
# layer 4: fleet checkpoint, elastic restore, kill + recover
# ---------------------------------------------------------------------------
def _baseline(cc, backend, n_shards=4, pool_size=4):
    fleet = _fleet(cc, n_shards=n_shards, pool_size=pool_size,
                   queue_depth=4, backend=backend)
    for i in range(8):
        fleet.submit(_session(i, i % 4))
    for _ in range(5):
        fleet.step()
    return _drain(fleet, {r.session_id: r for r in fleet.evict_finished()})


@pytest.mark.parametrize("backend", ["reference", "fabric"])
def test_restore_onto_fewer_shards_bit_exact(cc, backend):
    """Save a 4-shard fleet mid-serve, restore at 2 shards: surviving shards
    restore in place, lost shards' sessions redistribute into free slots;
    every session finishes byte-equal to the undisturbed 4-shard run."""
    ref = _baseline(cc, backend)
    fleet = _fleet(cc, n_shards=4, pool_size=4, queue_depth=4,
                   backend=backend)
    for i in range(8):
        fleet.submit(_session(i, i % 4))
    for _ in range(5):
        fleet.step()
    cfg = AerServeConfig(pool_size=4, max_steps=25)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        fleet.checkpoint(ck, blocking=True)
        small = ShardedSessionPool.restore(
            cc, cfg,
            ShardConfig(n_shards=2, queue_depth=4, backend=backend), ck,
        )
    assert small.n_steps == fleet.n_steps
    assert sum(o for o, _ in small.occupancy().values()) == 8
    res = _drain(small)
    assert set(res) == set(ref)
    for sid in ref:
        assert np.array_equal(res[sid].counts, ref[sid].counts), sid
        assert res[sid].prediction == ref[sid].prediction


def test_restore_impossible_raises_typed_mismatch(cc):
    fleet = _fleet(cc, n_shards=4, pool_size=4, queue_depth=4)
    for i in range(8):
        fleet.submit(_session(i, i % 4))
    for _ in range(3):
        fleet.step()
    cfg = AerServeConfig(pool_size=4, max_steps=25)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        fleet.checkpoint(ck, blocking=True)
        # 1 shard x 4 slots cannot hold 8 mid-flight sessions
        with pytest.raises(CheckpointMismatchError, match="redistribute"):
            ShardedSessionPool.restore(
                cc, cfg, ShardConfig(n_shards=1, queue_depth=0), ck,
            )
        # wrong per-shard pool geometry is also typed
        with pytest.raises(CheckpointMismatchError, match="pool_size"):
            ShardedSessionPool.restore(
                cc, AerServeConfig(pool_size=2, max_steps=25),
                ShardConfig(n_shards=4, queue_depth=4), ck,
            )


@pytest.mark.parametrize("backend", ["reference", "fabric"])
def test_kill_shard_recover_from_checkpoint_bit_exact(cc, backend):
    """Kill a shard mid-serve; its sessions roll back to the checkpoint and
    splice into survivors (whose current state keeps serving untouched).
    Deterministic replay makes every result — including the recovered
    tenants' — byte-equal to the run where nothing died. Covers both the
    queued and fabric-ring carry layouts."""
    ref = _baseline(cc, backend)
    fleet = _fleet(cc, n_shards=4, pool_size=4, queue_depth=4,
                   backend=backend)
    for i in range(8):
        fleet.submit(_session(i, i % 4))
    for _ in range(3):
        fleet.step()
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        fleet.checkpoint(ck, blocking=True)
        for _ in range(2):
            fleet.step()
        victim = 2
        held = [s.session_id for s in fleet.pools[victim].slots
                if s is not None]
        assert held  # the scenario is real: the dead shard held tenants
        fleet.kill_shard(victim)
        with pytest.raises(ValueError, match="already dead"):
            fleet.kill_shard(victim)
        assert fleet.recover_shard(ck, victim) == len(held)
    res = _drain(fleet, {r.session_id: r for r in fleet.evict_finished()})
    assert set(res) == set(ref)
    for sid in ref:
        assert np.array_equal(res[sid].counts, ref[sid].counts), sid
        assert res[sid].prediction == ref[sid].prediction
        assert res[sid].latency_steps == ref[sid].latency_steps


def test_recover_shard_guards(cc):
    fleet = _fleet(cc, n_shards=2)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        with pytest.raises(ValueError, match="is live"):
            fleet.recover_shard(ck, 0)
        fleet.kill_shard(0)
        with pytest.raises(FileNotFoundError):
            fleet.recover_shard(ck, 0)


# ---------------------------------------------------------------------------
# multi-device placement (subprocess: fake CPU devices)
# ---------------------------------------------------------------------------
def test_fleet_disjoint_devices_matches_single_device():
    """2 shards x (1 batch x 2 cluster) disjoint device meshes, fabric-ring
    backend under device_slab_placement: fleet results match the
    single-device fleet bit-for-bit."""
    _run("""
        import numpy as np
        from repro.core.cnn import compile_poker_cnn
        from repro.data.pipeline import DvsStreamConfig, DvsStreamSource
        from repro.serve.aer import AerServeConfig, DvsSession
        from repro.serve.sharded import (ShardConfig, ShardedSessionPool,
                                         retile_for_slabs)
        # both fleets on the SAME slab-compliant placement (retiling is
        # idempotent) so only the mesh differs between the two runs
        cc = retile_for_slabs(compile_poker_cnn(), 2)
        def sess(i, symbol):
            return DvsSession(i, DvsStreamSource(
                DvsStreamConfig(symbol=symbol, events_per_step=16, seed=9),
                session_id=i), label=symbol)
        def serve(cluster_devices):
            fleet = ShardedSessionPool(
                cc, AerServeConfig(pool_size=2, max_steps=25),
                ShardConfig(n_shards=2, queue_depth=4, backend="fabric",
                            cluster_devices=cluster_devices))
            return {r.session_id: r
                    for r in fleet.serve([sess(i, i % 4) for i in range(6)])}
        multi = serve(2)   # 2 shards x 2 devices, disjoint
        single = serve(1)
        assert set(multi) == set(single) == set(range(6))
        for sid in single:
            assert np.array_equal(multi[sid].counts, single[sid].counts), sid
            assert multi[sid].latency_steps == single[sid].latency_steps
        print("OK")
    """)


def test_cross_mesh_migration_bit_exact():
    """The cross-host move: a tenant starts on a single-device shard and
    migrates mid-flight onto a shard whose clusters span 2 devices (same
    retiled tables, different mesh). It finishes byte-equal to the solo
    local-engine run — migration is a placement move, never a value move."""
    _run("""
        import numpy as np
        from repro.core.cnn import compile_poker_cnn
        from repro.data.pipeline import DvsStreamConfig, DvsStreamSource
        from repro.serve.aer import (AerServeConfig, AerSessionPool,
                                     DvsSession, build_poker_engine)
        from repro.serve.sharded import (ShardConfig, ShardedSessionPool,
                                         build_poker_shard_engine,
                                         retile_for_slabs)
        import jax
        cc = retile_for_slabs(compile_poker_cnn(), 2)
        def sess(i, symbol):
            return DvsSession(i, DvsStreamSource(
                DvsStreamConfig(symbol=symbol, events_per_step=16, seed=9),
                session_id=i), label=symbol)
        devs = jax.devices()
        def factory(shard_id, devices):
            if shard_id == 0:  # single-device shard
                return build_poker_shard_engine(
                    cc.tables, "fabric", cluster_devices=1,
                    batch_devices=1, devices=devs[:1])
            return build_poker_shard_engine(  # 2-device cluster shard
                cc.tables, "fabric", cluster_devices=2,
                batch_devices=1, devices=devs[1:3])
        fleet = ShardedSessionPool(
            cc, AerServeConfig(pool_size=2, max_steps=25),
            ShardConfig(n_shards=2, queue_depth=4, backend="fabric"),
            engine_factory=factory)
        fleet.submit(sess(10, 2))
        fleet.step()  # backfill: the session becomes resident
        if fleet.locate(10)[0] != 0:
            fleet.migrate(10, 0)
        for _ in range(3):
            fleet.step()
        assert fleet.locate(10)[0] == 0
        fleet.migrate(10, 1)  # 1-device mesh -> 2-device mesh, mid-flight
        assert fleet.locate(10)[0] == 1
        res = {}
        while fleet.busy:
            fleet.step()
            for r in fleet.evict_finished():
                res[r.session_id] = r
        solo = AerSessionPool(
            cc, build_poker_engine(cc.tables),
            AerServeConfig(pool_size=2, max_steps=25))
        ref = {r.session_id: r for r in solo.serve([sess(10, 2)])}
        assert np.array_equal(res[10].counts, ref[10].counts)
        assert res[10].prediction == ref[10].prediction
        assert res[10].latency_steps == ref[10].latency_steps
        print("OK")
    """)


def test_elastic_restore_across_mesh_shapes():
    """Fleet checkpointed with shards on (1 x 2) cluster meshes restores
    onto (2 x 2) meshes — surviving a mesh-shape change, bit-exact (carry
    values are global; elasticity is placement-only). The cluster extent is
    kept so both fleets run the same device-slab placement."""
    _run("""
        import numpy as np, tempfile
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.core.cnn import compile_poker_cnn
        from repro.data.pipeline import DvsStreamConfig, DvsStreamSource
        from repro.serve.aer import AerServeConfig, DvsSession
        from repro.serve.sharded import ShardConfig, ShardedSessionPool
        cc = compile_poker_cnn()
        def sess(i, symbol):
            return DvsSession(i, DvsStreamSource(
                DvsStreamConfig(symbol=symbol, events_per_step=16, seed=9),
                session_id=i), label=symbol)
        cfg = AerServeConfig(pool_size=2, max_steps=25)
        def drain(fleet, res):
            while fleet.busy:
                fleet.step()
                for r in fleet.evict_finished():
                    res[r.session_id] = r
            return res
        base = ShardedSessionPool(cc, cfg, ShardConfig(
            n_shards=2, queue_depth=4, backend="fabric", cluster_devices=2))
        for i in range(4):
            base.submit(sess(i, i % 4))
        for _ in range(5):
            base.step()
        ref = drain(base, {r.session_id: r for r in base.evict_finished()})
        f = ShardedSessionPool(cc, cfg, ShardConfig(
            n_shards=2, queue_depth=4, backend="fabric", cluster_devices=2))
        for i in range(4):
            f.submit(sess(i, i % 4))
        for _ in range(5):
            f.step()
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d, keep=2)
            f.checkpoint(ck, blocking=True)
            g = ShardedSessionPool.restore(
                cc, cfg,
                ShardConfig(n_shards=2, queue_depth=4, backend="fabric",
                            cluster_devices=2, batch_devices=2), ck)
        res = drain(g, {})
        assert set(res) == set(ref) == set(range(4))
        for sid in ref:
            assert np.array_equal(res[sid].counts, ref[sid].counts), sid
            assert res[sid].latency_steps == ref[sid].latency_steps
        print("OK")
    """)
