"""Multi-model fabric serving (DESIGN.md §16).

Locks the load-bearing claims of multi-tenant multi-model residency:

  * **Slab layout** — resident models' tables concatenate back to back,
    each slab keeping its solo connectivity at its neuron offset.
  * **Serving isolation** — a session served from an N-model pool is
    bit-identical (queued mode) to the same session served solo, through
    admits, hot model loads under live sessions, and checkpoint restore;
    and the whole mixed pool runs on ONE compiled step (model id is data).
  * **Typed refusal** — a checkpoint restored into a retargeted or
    re-provisioned pool raises :class:`CheckpointMismatchError` before any
    carry state is spliced; mis-sized slot masks and mismatched SlotCarry
    leaves raise instead of broadcasting.
"""

import functools

import numpy as np
import pytest

from repro.core.cnn import compile_poker_cnn
from repro.core.compiler import Geometry, artifact_from_tables
from repro.core.event_engine import EventEngine, ModelRegistry, reset_slots
from repro.core.neuron import NeuronParams
from repro.core.tags import NetworkSpec, compile_network, concat_tables
from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import DvsStreamConfig, DvsStreamSource
from repro.serve.aer import (
    AerServeConfig,
    AerSessionPool,
    CheckpointMismatchError,
    DvsSession,
    build_poker_engine,
)


@functools.lru_cache(maxsize=1)
def _poker_cc():
    return compile_poker_cnn()


def _session(i, symbol, model=None, seed=9):
    return DvsSession(
        session_id=i,
        source=DvsStreamSource(
            DvsStreamConfig(symbol=symbol, events_per_step=16, seed=seed),
            session_id=i,
        ),
        label=symbol,
        model=model,
    )


def _cfg(pool_size=2, **kw):
    kw.setdefault("max_steps", 12)
    return AerServeConfig(pool_size=pool_size, **kw)


def _random_tables(seed, n=32, cluster=8, k=24, edges=48):
    rng = np.random.default_rng(seed)
    spec = NetworkSpec(n_neurons=n, cluster_size=cluster, k_tags=k)
    for _ in range(edges):
        spec.connect(int(rng.integers(n)), int(rng.integers(n)), int(rng.integers(4)))
    return compile_network(spec)


def test_concat_tables_dense_equivalents_stack():
    """Each slab's dense connectivity is the solo table's, offset intact."""
    parts = [_random_tables(3), _random_tables(4, n=48, k=40), _random_tables(5)]
    combined, slabs = concat_tables(parts)
    assert [s.neuron_lo for s in slabs] == [0, 32, 80]
    assert combined.k_tags == 40  # padded to the widest resident model
    got = np.asarray(combined.dense_equivalent())
    rows = []
    for t, s in zip(parts, slabs):
        solo = np.asarray(t.dense_equivalent())
        if solo.size:
            solo = solo + np.array([[s.neuron_lo, s.neuron_lo, 0]])
        rows.append(solo)
    want = np.concatenate([r for r in rows if r.size], axis=0)
    got_sorted = got[np.lexsort(got.T[::-1])]
    want_sorted = want[np.lexsort(want.T[::-1])]
    np.testing.assert_array_equal(got_sorted, want_sorted)


def test_registry_rejects_mixed_cluster_size_and_duplicates():
    reg = ModelRegistry({"a": _random_tables(0)})
    with pytest.raises(ValueError, match="already resident"):
        reg.load("a", _random_tables(1))
    with pytest.raises(ValueError, match="cluster_size"):
        reg.load("b", _random_tables(1, cluster=16, k=64))
    reg.load("b", _random_tables(1))
    assert reg.names == ["a", "b"]
    reg.unload("a")
    assert reg.names == ["b"]
    combined, slabs = reg.combined()
    assert combined.n_neurons == 32 and slabs["b"].neuron_lo == 0


# ---------------------------------------------------------------------------
# Serving isolation
# ---------------------------------------------------------------------------
def test_two_model_pool_bit_identical_to_solo_queued():
    cc = _poker_cc()
    solo = AerSessionPool(cc, build_poker_engine(cc.tables), _cfg())
    r_solo = {r.session_id: r
              for r in solo.serve([_session(0, 1), _session(1, 2)])}

    pool = AerSessionPool.from_models({"a": cc, "b": cc}, _cfg())
    r_multi = {r.session_id: r
               for r in pool.serve([_session(0, 1, "a"), _session(1, 2, "b")])}

    for sid in r_solo:
        np.testing.assert_array_equal(r_solo[sid].counts, r_multi[sid].counts)
        assert r_solo[sid].latency_steps == r_multi[sid].latency_steps
        assert r_solo[sid].prediction == r_multi[sid].prediction


def test_two_model_pool_compiles_once():
    """Tier-1 gate: a mixed 2-model pool is ONE compiled step — admitting
    sessions on either model never recompiles (model id is data)."""
    cc = _poker_cc()
    pool = AerSessionPool.from_models({"a": cc, "b": cc}, _cfg())
    pool.serve([_session(0, 1, "a"), _session(1, 2, "b"),
                _session(2, 3, "b"), _session(3, 0, "a")])
    assert pool.engine._jit_step._cache_size() == 1


def test_fabric_multimodel_prediction_parity():
    cc = _poker_cc()
    solo = AerSessionPool(cc, build_poker_engine(cc.tables, backend="fabric"),
                          _cfg())
    r_solo = {r.session_id: r
              for r in solo.serve([_session(0, 1), _session(1, 2)])}
    pool = AerSessionPool.from_models({"a": cc, "b": cc}, _cfg(),
                                      backend="fabric")
    r_multi = {r.session_id: r
               for r in pool.serve([_session(0, 1, "a"), _session(1, 2, "b")])}
    for sid in r_solo:
        assert r_solo[sid].prediction == r_multi[sid].prediction


def test_fabric_roll_multimodel_pool_matches_ring():
    """The roll-carried fabric path has no static entry table: a 2-model
    pool on it builds and decides exactly as the ring fast path does."""
    cc = _poker_cc()
    got = {}
    for ring in (True, False):
        pool = AerSessionPool.from_models(
            {"a": cc, "b": cc}, _cfg(), backend="fabric",
            fabric_options={"ring": ring},
        )
        assert pool.engine.fabric_ring is ring
        got[ring] = {
            r.session_id: (r.prediction, r.latency_steps, r.counts.tolist())
            for r in pool.serve([_session(0, 1, "a"), _session(1, 2, "b")])
        }
    assert got[True] == got[False]


def test_admit_requires_model_name_when_ambiguous():
    cc = _poker_cc()
    pool = AerSessionPool.from_models({"a": cc, "b": cc}, _cfg())
    with pytest.raises(ValueError, match="must name its model"):
        pool.admit(_session(0, 1))
    with pytest.raises(KeyError, match="not resident"):
        pool.admit(_session(0, 1, "zebra"))
    # single-model pools keep the old contract: no name needed
    solo = AerSessionPool.from_models({"a": cc}, _cfg())
    solo.admit(_session(0, 1))
    assert solo.slots[0].model == "a"


@pytest.mark.parametrize("backend", ["reference", "fabric"])
def test_hot_load_under_live_sessions(backend):
    """load_model on a live pool: in-flight sessions finish with counts
    identical to an undisturbed run (queued mode is bit-exact; fabric
    migration re-buckets delays on the grown mesh placement)."""
    cc = _poker_cc()
    pool = AerSessionPool.from_models({"a": cc}, _cfg(), backend=backend)
    pool.admit(_session(0, 1, "a"))
    pool.admit(_session(1, 2, "a"))
    for _ in range(4):
        pool.step()
    pool.load_model("b", cc)  # live: slots migrate across the slab re-layout
    assert list(pool.models) == ["a", "b"]
    results = []
    while pool.occupied:
        pool.step()
        done = pool.finished_slots()
        if done:
            results.extend(pool.evict_many(done))
    assert len(results) == 2 and all(r.error is None for r in results)

    if backend == "reference":
        undisturbed = AerSessionPool.from_models({"a": cc}, _cfg())
        r_ref = {r.session_id: r
                 for r in undisturbed.serve([_session(0, 1, "a"),
                                             _session(1, 2, "a")])}
        for r in results:
            np.testing.assert_array_equal(r.counts, r_ref[r.session_id].counts)

    # the hot-swap ladder's last rung: drain, then unload the old model
    pool.unload_model("a")
    assert list(pool.models) == ["b"]
    pool.serve([_session(9, 3, "b")])  # the survivor still serves


def test_unload_refuses_live_sessions_and_last_model():
    cc = _poker_cc()
    pool = AerSessionPool.from_models({"a": cc, "b": cc}, _cfg())
    pool.admit(_session(0, 1, "a"))
    with pytest.raises(RuntimeError, match="live sessions"):
        pool.unload_model("a")
    pool.evict(0)
    pool.unload_model("a")
    with pytest.raises(ValueError, match="last resident"):
        pool.unload_model("b")
    with pytest.raises(KeyError, match="not resident"):
        pool.unload_model("a")


def test_hot_swap_pool_wraps_fixed_engine_refuses():
    cc = _poker_cc()
    pool = AerSessionPool(cc, build_poker_engine(cc.tables), _cfg())
    with pytest.raises(RuntimeError, match="from_models"):
        pool.load_model("b", cc)


# ---------------------------------------------------------------------------
# Checkpoint fingerprinting (satellite: restore must raise, not corrupt)
# ---------------------------------------------------------------------------
def test_restore_into_retargeted_engine_raises(tmp_path):
    cc = _poker_cc()
    pool = AerSessionPool(cc, build_poker_engine(cc.tables), _cfg())
    pool.admit(_session(0, 1))
    pool.step()
    ck = Checkpointer(str(tmp_path))
    pool.checkpoint(ck, blocking=True)

    art = artifact_from_tables(
        cc.tables,
        Geometry(grid_x=2, grid_y=2, cores_per_tile=2, neurons_per_core=256),
        optimize=False,
    )
    retargeted = build_poker_engine(art.tables, backend="fabric")
    with pytest.raises(CheckpointMismatchError):
        AerSessionPool.restore(cc, retargeted, _cfg(), ck)

    # the matching engine still restores bit-exactly, models intact
    back = AerSessionPool.restore(cc, build_poker_engine(cc.tables), _cfg(), ck)
    assert back.n_steps == 1 and back.slots[0].model == "default"
    np.testing.assert_array_equal(back.slots[0].counts, pool.slots[0].counts)


def test_restore_model_set_mismatch_raises(tmp_path):
    cc = _poker_cc()
    pool = AerSessionPool.from_models({"a": cc, "b": cc}, _cfg())
    pool.admit(_session(0, 1, "a"))
    pool.step()
    ck = Checkpointer(str(tmp_path))
    pool.checkpoint(ck, blocking=True)
    with pytest.raises(CheckpointMismatchError):
        AerSessionPool.restore(cc, build_poker_engine(cc.tables), _cfg(), ck)


def test_multimodel_checkpoint_roundtrip_bit_exact(tmp_path):
    cc = _poker_cc()
    pool = AerSessionPool.from_models({"a": cc, "b": cc}, _cfg(),
                                      donate_carry=False)
    pool.admit(_session(0, 1, "a"))
    pool.admit(_session(1, 2, "b"))
    for _ in range(3):
        pool.step()
    ck = Checkpointer(str(tmp_path))
    pool.checkpoint(ck, blocking=True)

    engine = AerSessionPool._engine_for(
        {"a": cc, "b": cc},
        {"backend": "reference", "donate_carry": False, "faults": None},
    )
    back = AerSessionPool.restore(cc, engine, _cfg(), ck,
                                  models={"a": cc, "b": cc})
    assert [s.model for s in back.slots if s is not None] == ["a", "b"]
    for _ in range(3):
        pool.step()
        back.step()
    for i in range(2):
        np.testing.assert_array_equal(pool.slots[i].counts,
                                      back.slots[i].counts)


# ---------------------------------------------------------------------------
# Slot-surgery validation (satellite: raise, never broadcast)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=1)
def _tiny_engine():
    return EventEngine(_random_tables(7, n=16, cluster=8, k=16, edges=12),
                       NeuronParams(), queue_capacity=16)


def test_reset_slots_rejects_mismatched_mask():
    eng = _tiny_engine()
    carry = eng.init_state(batch=4)
    with pytest.raises(ValueError, match="mask"):
        eng.reset_slots(carry, np.zeros(3, dtype=bool))  # length mismatch
    with pytest.raises(ValueError, match="mask"):
        eng.reset_slots(carry, np.zeros((2, 2), dtype=bool))  # rank mismatch
    # the functional core refuses too (custom serving loops use it directly)
    import jax.numpy as jnp
    fresh = eng.init_state(batch=4)
    with pytest.raises(ValueError, match="mask"):
        reset_slots(carry, jnp.zeros(5, dtype=bool), fresh)
    # and the well-formed mask still works
    out = eng.reset_slots(carry, np.array([True, False, False, True]))
    assert np.asarray(out[1]).shape == np.asarray(carry[1]).shape


def test_splice_slots_rejects_mismatched_state_leaf():
    eng = _tiny_engine()
    carry = eng.init_state(batch=4)
    sc = eng.extract_slots(carry, [0, 1])
    import dataclasses as dc
    import jax
    bad = dc.replace(
        sc,
        state=jax.tree_util.tree_map(lambda x: x[:, :-1], sc.state),
    )
    with pytest.raises(ValueError, match="leaf"):
        eng.splice_slots(carry, [0, 1], bad)
    # wrong slot count in the carry vs the index list
    with pytest.raises(ValueError, match="SlotCarry holds"):
        eng.splice_slots(carry, [0, 1, 2], sc)
    # out-of-range and duplicate slot ids keep raising
    with pytest.raises(ValueError, match="out of range"):
        eng.extract_slots(carry, [0, 99])
    with pytest.raises(ValueError, match="unique"):
        eng.extract_slots(carry, [1, 1])
