"""Profile-guided live re-placement (DESIGN.md §18).

:class:`ReplacementController` closes the measure -> optimize -> recompile
loop on a live pool. The claims under test:

  * the controller refuses a pool that cannot feed it (no traffic profile)
    and an absent model — typed errors, not silent no-ops;
  * the drift / min_steps / cooldown gates actually gate: no judgement on
    thin evidence, no thrash after a swap, no swap below threshold;
  * a swap registers a fresh model *version* on previously-unoccupied
    tiles and mid-flight sessions are BYTE-EQUAL to an unswapped control
    pool through it — the bit-exact rung of the §15/§16 ladder;
  * when no free tiles exist the bit-exact rung raises and points at
    :func:`migrate_pool` (the best-effort rung) instead of silently
    degrading;
  * retarget + drain complete the version lifecycle: new admissions land
    on the new version, the old one unloads only once its tenants left.
"""

import dataclasses
import functools

import numpy as np
import pytest

from repro.core.cnn import compile_poker_cnn
from repro.data.pipeline import DvsStreamConfig, DvsStreamSource
from repro.serve.aer import AerServeConfig, AerSessionPool, DvsSession
from repro.serve.health import ReplacementConfig, ReplacementController


@functools.lru_cache(maxsize=1)
def _poker_cc():
    return compile_poker_cnn()


def _session(i, model=None, seed=9):
    return DvsSession(
        session_id=i,
        source=DvsStreamSource(
            DvsStreamConfig(symbol=i % 4, events_per_step=16, seed=seed),
            session_id=i,
        ),
        label=i % 4,
        model=model,
    )


def _pool(models=None, per_link=True, backend="fabric", pool_size=2):
    cc = _poker_cc()
    cfg = AerServeConfig(pool_size=pool_size, max_steps=10**6)
    fo = None
    if backend == "fabric":
        fo = {"per_link_stats": True} if per_link else {}
    return AerSessionPool.from_models(
        models or {"poker": cc}, cfg, backend=backend, fabric_options=fo)


def _fill(pool, model=None, seed=9):
    for i in range(pool.cfg.pool_size):
        pool.admit(_session(i, model=model, seed=seed))


# ---------------------------------------------------------------------------
# typed refusal
# ---------------------------------------------------------------------------
def test_controller_requires_traffic_profile():
    with pytest.raises(ValueError, match="per_link_stats"):
        ReplacementController(_pool(backend="reference"))
    with pytest.raises(ValueError, match="per_link_stats"):
        ReplacementController(_pool(per_link=False))


def test_controller_requires_resident_model():
    pool = _pool()
    with pytest.raises(ValueError, match="not resident"):
        ReplacementController(pool, model="nope")


# ---------------------------------------------------------------------------
# gates
# ---------------------------------------------------------------------------
def test_min_steps_threshold_and_cooldown_gates():
    pool = _pool()
    ctl = ReplacementController(pool, cfg=ReplacementConfig(
        drift_threshold=0.05, min_steps=6, cooldown_steps=50))
    assert ctl.maybe_replace() is None  # nothing observed yet
    _fill(pool)
    for _ in range(3):
        pool.step()
    assert ctl.maybe_replace() is None  # below min_steps: evidence too thin
    for _ in range(3):
        pool.step()
    assert ctl.drift() >= 0.05  # poker traffic is far from uniform
    report = ctl.maybe_replace()
    assert report is not None and report["name"] == "poker@r1"
    assert ctl.current == "poker@r1" and ctl.retired == ["poker"]
    assert "poker@r1" in pool.models and ctl.history == [report]
    # the new version lives on tiles the old one does not occupy
    old_tiles = set(np.asarray(pool.models["poker"].tables.tile_of_cluster))
    assert not old_tiles & set(report["placement"])
    # the swap reset the observation window, then the cooldown holds even
    # after min_steps of fresh evidence accumulates again
    assert pool.profile.steps == 0
    assert ctl.maybe_replace() is None
    for _ in range(6):
        pool.step()
    assert ctl.maybe_replace() is None  # cooldown_steps=50 not yet elapsed


def test_below_threshold_never_swaps():
    pool = _pool()
    ctl = ReplacementController(pool, cfg=ReplacementConfig(
        drift_threshold=0.99, min_steps=2, cooldown_steps=0))
    _fill(pool)
    for _ in range(8):
        pool.step()
    assert 0.0 < ctl.drift() < 0.99
    assert ctl.maybe_replace() is None
    assert ctl.version == 0 and list(pool.models) == ["poker"]


# ---------------------------------------------------------------------------
# the bit-exact rung
# ---------------------------------------------------------------------------
def test_forced_swap_is_byte_equal_for_mid_flight_sessions():
    pool_a, pool_b = _pool(), _pool()  # B is the unswapped control
    _fill(pool_a, seed=23)
    _fill(pool_b, seed=23)
    for _ in range(10):
        pool_a.step()
        pool_b.step()
    ctl = ReplacementController(pool_a, cfg=ReplacementConfig(
        min_steps=1, cooldown_steps=0))
    report = ctl.maybe_replace(force=True)
    assert report is not None
    # same observed matrix in -> lower observed cost out
    assert report["cost_observed_new"] <= report["cost_observed_old"]
    for _ in range(6):
        pool_a.step()
        pool_b.step()
    for sa, sb in zip(pool_a.slots, pool_b.slots):
        assert sa.step == sb.step
        np.testing.assert_array_equal(np.asarray(sa.counts),
                                      np.asarray(sb.counts))
        assert sa.dropped == sb.dropped and sa.link_dropped == sb.link_dropped


def test_replacement_never_increases_measured_link_drops():
    """A scattered ("stale") placement under 2-event link FIFOs drops
    events. Re-placing from the observed traffic and serving the same cohort
    for the same window on the new version measures no more link drops."""
    cc = _poker_cc()
    stale = np.array([0, 8, 2, 6, 4, 5], np.int32)  # corners first
    cc_stale = dataclasses.replace(
        cc, tables=dataclasses.replace(cc.tables, tile_of_cluster=stale))
    pool = AerSessionPool.from_models(
        {"m": cc_stale}, AerServeConfig(pool_size=2, max_steps=10**6),
        backend="fabric",
        fabric_options={"link_capacity": 2, "per_link_stats": True})
    window = 8
    _fill(pool, model="m", seed=23)
    for _ in range(window):
        pool.step()
    drops_pre = pool.profile.total_link_dropped
    ctl = ReplacementController(pool, cfg=ReplacementConfig(
        min_steps=window // 2, cooldown_steps=0))
    assert ctl.maybe_replace(force=True) is not None
    for i in range(pool.cfg.pool_size):
        pool.evict(i)
    ctl.drain_retired()  # the rebind restarts the observation window
    for i in range(pool.cfg.pool_size):
        pool.admit(ctl.retarget(_session(i, model="m", seed=23)))
    for _ in range(window):
        pool.step()
    assert drops_pre > 0
    assert pool.profile.total_link_dropped <= drops_pre


def test_versioned_swap_byte_equal_in_queued_mode():
    """The controller itself needs fabric per-link stats, but the swap
    primitive it rides — a versioned ``load_model`` rebind — is
    backend-agnostic: registering a re-placed version under live sessions
    leaves a queued reference pool byte-equal to an unswapped control."""
    cc = _poker_cc()

    def placed(tiles):
        # concat is all-or-none on placement: stamp both versions explicitly
        return dataclasses.replace(cc, tables=dataclasses.replace(
            cc.tables, tile_of_cluster=np.asarray(tiles, np.int32)))

    base = placed([0, 0, 1, 1, 2, 2])
    pool_a = _pool(models={"poker": base}, backend="reference")
    pool_b = _pool(models={"poker": base}, backend="reference")
    _fill(pool_a, seed=31)
    _fill(pool_b, seed=31)
    for _ in range(8):
        pool_a.step()
        pool_b.step()
    pool_a.load_model("poker@r1", placed([3, 4, 5, 6, 7, 8]))
    for _ in range(6):
        pool_a.step()
        pool_b.step()
    for sa, sb in zip(pool_a.slots, pool_b.slots):
        assert sa.step == sb.step
        np.testing.assert_array_equal(np.asarray(sa.counts),
                                      np.asarray(sb.counts))
        assert sa.dropped == sb.dropped


def test_no_free_tiles_raises_toward_best_effort_rung():
    cc = _poker_cc()

    def placed(tiles):
        t = dataclasses.replace(
            cc.tables, tile_of_cluster=np.asarray(tiles, np.int32))
        return dataclasses.replace(cc, tables=t)

    # two residents between them occupy every tile of the 3x3 mesh
    pool = _pool(models={"a": placed([0, 1, 2, 3, 4, 5]),
                         "b": placed([3, 4, 5, 6, 7, 8])})
    _fill(pool, model="a")
    for _ in range(4):
        pool.step()
    ctl = ReplacementController(pool, model="a")
    with pytest.raises(RuntimeError, match="migrate_pool"):
        ctl.maybe_replace(force=True)


# ---------------------------------------------------------------------------
# version lifecycle
# ---------------------------------------------------------------------------
def test_retarget_and_drain_retire_the_old_version():
    pool = _pool()
    _fill(pool)
    for _ in range(4):
        pool.step()
    ctl = ReplacementController(pool)
    assert ctl.maybe_replace(force=True) is not None
    # the old version still has live tenants: drain must refuse to unload
    assert ctl.drain_retired() == []
    assert set(pool.models) == {"poker", "poker@r1"}
    # a new admission retargets to the new version and serves alongside
    pool.evict(0)
    s_new = ctl.retarget(_session(7))
    assert s_new.model == "poker@r1"
    pool.admit(s_new)
    for _ in range(3):
        pool.step()
    assert pool.slots[0].step == 3  # the retargeted session is serving
    # once the last old-version tenant leaves, drain frees the slab
    pool.evict(1)
    assert ctl.drain_retired() == ["poker"]
    assert set(pool.models) == {"poker@r1"} and ctl.retired == []
