"""AER event-queue compaction and overflow semantics (DESIGN.md §10).

The contract under test:
  * below capacity the queued path is lossless — bit-parity with the dense
    delivery path and the dense [N, N, 4] oracle;
  * above capacity the overflow is deterministic: the first ``capacity``
    active sources (lowest ids — the arbiter scan order) win the bus, the
    drop counter equals ``n_active - capacity``, and the delivered drive is
    exactly the oracle applied to the kept subset (no NaNs/garbage);
  * the property holds across random sparsity levels (hypothesis, skipped
    cleanly when the extra isn't installed);
  * EventEngine threads capacity + drop stats through step/run and the
    stats stack over the scan's time axis.
"""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from tests._hypothesis_compat import HAS_HYPOTHESIS, given, settings, st

from repro.core.dispatch import available_backends, get_backend, two_stage_deliver
from repro.core.event_engine import EventEngine, dense_weights_from_tables
from repro.core.tags import NetworkSpec, compile_network
from repro.core.two_stage import (
    _accumulate_activity,
    compact_events,
    stage1_route,
    stage1_route_events,
)


def _tables(seed, n=48, cluster=16, k=48, edges=70):
    rng = np.random.default_rng(seed)
    spec = NetworkSpec(n_neurons=n, cluster_size=cluster, k_tags=k,
                       max_cam_words=24, max_sram_entries=16)
    seen = set()
    for _ in range(edges):
        s, d = int(rng.integers(n)), int(rng.integers(n))
        if (s, d) in seen:
            continue
        seen.add((s, d))
        spec.connect(s, d, int(rng.integers(4)))
    return compile_network(spec)


def _deliver_args(tables):
    return (
        jnp.asarray(tables.src_tag), jnp.asarray(tables.src_dest),
        jnp.asarray(tables.cam_tag), jnp.asarray(tables.cam_syn),
        tables.cluster_size, tables.k_tags,
    )


# ---------------------------------------------------------------------------
# compaction primitive
# ---------------------------------------------------------------------------
def test_compact_picks_lowest_ids_in_order():
    spikes = jnp.zeros((12,)).at[jnp.asarray([1, 4, 7, 9])].set(
        jnp.asarray([0.5, 2.0, 1.5, 3.0])
    )
    q = compact_events(spikes, 8)
    np.testing.assert_array_equal(np.asarray(q.src)[:4], [1, 4, 7, 9])
    np.testing.assert_array_equal(np.asarray(q.src)[4:], [-1] * 4)
    np.testing.assert_allclose(np.asarray(q.weight)[:4], [0.5, 2.0, 1.5, 3.0])
    np.testing.assert_allclose(np.asarray(q.weight)[4:], 0.0)
    assert int(q.dropped) == 0


def test_compact_overflow_drops_highest_ids_deterministically():
    spikes = jnp.zeros((16,)).at[jnp.asarray([2, 3, 5, 11, 13, 14])].set(1.0)
    q = compact_events(spikes, 4)
    np.testing.assert_array_equal(np.asarray(q.src), [2, 3, 5, 11])
    assert int(q.dropped) == 2
    # deterministic: identical input -> identical queue
    q2 = compact_events(spikes, 4)
    np.testing.assert_array_equal(np.asarray(q.src), np.asarray(q2.src))


def test_compact_batched_counts_per_stream():
    rng = np.random.default_rng(5)
    spikes = jnp.asarray(rng.random((3, 40)) < 0.5, jnp.float32)
    q = compact_events(spikes, 8)
    n_active = np.asarray((spikes != 0).sum(-1))
    np.testing.assert_array_equal(
        np.asarray(q.dropped), np.maximum(n_active - 8, 0)
    )
    assert q.src.shape == (3, 8)


def test_compact_rejects_nonpositive_capacity():
    with pytest.raises(ValueError, match="capacity"):
        compact_events(jnp.zeros((8,)), 0)


def test_compact_rejects_queues_past_int32_indexing():
    """All streams' queues share one int32-indexed scatter; a batch whose
    queues hold more slots than int32 counts is refused, not wrapped."""
    spikes = jax.ShapeDtypeStruct((2**16, 2**15), jnp.float32)
    with pytest.raises(ValueError, match="int32"):
        jax.eval_shape(lambda s: compact_events(s, 2**15), spikes)


def _compact_oracle(spikes: np.ndarray, capacity: int):
    """The queue contract in plain numpy: the first ``capacity`` active ids of
    each stream in ascending order, their weights, and the rest dropped."""
    n = spikes.shape[-1]
    q = min(capacity, n)
    rows = spikes.reshape(-1, n)
    src = np.full((len(rows), q), -1, np.int32)
    weight = np.zeros((len(rows), q), spikes.dtype)
    dropped = np.zeros(len(rows), np.int32)
    for r, row in enumerate(rows):
        active = np.flatnonzero(row != 0)
        kept = active[:q]
        src[r, : len(kept)] = kept
        weight[r, : len(kept)] = row[kept]
        dropped[r] = len(active) - len(kept)
    batch = spikes.shape[:-1]
    return src.reshape(*batch, q), weight.reshape(*batch, q), dropped.reshape(batch)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("batch", [(), (3,), (2, 3)], ids=["N", "BxN", "B1xB2xN"])
@pytest.mark.parametrize("activity", ["none", "all", "mixed"])
@pytest.mark.parametrize("capacity", ["one", "below_active", "n", "above_n"])
def test_compact_matches_numpy_oracle_bit_for_bit(capacity, activity, batch, dtype):
    """``src``, ``weight`` and ``dropped`` equal the oracle's bits for every
    capacity, batch shape and dtype, with weights that are not 1."""
    n = 24
    rng = np.random.default_rng(11)
    values = rng.choice(np.asarray([0.5, 2.0, -1.5, 3.25, 7.0]), (*batch, n))
    mask = {
        "none": np.zeros((*batch, n), bool),
        "all": np.ones((*batch, n), bool),
        "mixed": rng.random((*batch, n)) < 0.5,
    }[activity]
    if activity == "mixed" and batch:  # one silent and one saturated stream
        mask.reshape(-1, n)[0] = False
        mask.reshape(-1, n)[-1] = True
    spikes = np.asarray(jnp.asarray(np.where(mask, values, 0.0), dtype))
    cap = {"one": 1, "below_active": 5, "n": n, "above_n": n + 7}[capacity]

    q = compact_events(jnp.asarray(spikes), cap)
    src, weight, dropped = _compact_oracle(spikes, cap)
    assert q.src.dtype == jnp.int32 and q.dropped.dtype == jnp.int32
    assert q.weight.dtype == spikes.dtype
    np.testing.assert_array_equal(np.asarray(q.src), src)
    np.testing.assert_array_equal(
        np.asarray(q.weight).view(np.uint8), weight.view(np.uint8)
    )
    np.testing.assert_array_equal(np.asarray(q.dropped), dropped)


# ---------------------------------------------------------------------------
# parity below capacity; deterministic drops above
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b", [None, 4])
def test_below_capacity_queued_equals_dense_path(b):
    tables = _tables(0)
    rng = np.random.default_rng(1)
    shape = (tables.n_neurons,) if b is None else (b, tables.n_neurons)
    spikes = jnp.asarray(rng.random(shape) < 0.25, jnp.float32)
    args = _deliver_args(tables)
    dense_drive = two_stage_deliver(spikes, *args)
    queued_drive, stats = two_stage_deliver(
        spikes, *args, queue_capacity=tables.n_neurons, with_stats=True
    )
    np.testing.assert_allclose(
        np.asarray(queued_drive), np.asarray(dense_drive), rtol=1e-6
    )
    assert int(np.asarray(stats.dropped).max()) == 0


def test_overflow_drive_equals_oracle_of_kept_subset():
    tables = _tables(2)
    dense = jnp.asarray(dense_weights_from_tables(tables))
    rng = np.random.default_rng(3)
    spikes = jnp.asarray(rng.random((2, tables.n_neurons)) < 0.6, jnp.float32)
    cap = 8
    drive, stats = two_stage_deliver(
        spikes, *_deliver_args(tables), queue_capacity=cap, with_stats=True
    )
    # the kept subset is the first `cap` active sources of each stream
    kept = np.zeros_like(np.asarray(spikes))
    for i, row in enumerate(np.asarray(spikes)):
        active = np.flatnonzero(row)
        kept[i, active[:cap]] = row[active[:cap]]
        assert int(stats.dropped[i]) == max(0, len(active) - cap)
    ref = jnp.einsum("dst,bs->bdt", dense, jnp.asarray(kept))
    np.testing.assert_allclose(np.asarray(drive), np.asarray(ref), rtol=1e-5, atol=1e-6)
    assert np.isfinite(np.asarray(drive)).all()


def test_overflow_stats_consistent_across_backends():
    """Every backend reports the same total drop count for the same input."""
    tables = _tables(4)
    rng = np.random.default_rng(6)
    spikes = jnp.asarray(rng.random((2, tables.n_neurons)) < 0.7, jnp.float32)
    args = _deliver_args(tables)
    counts = {}
    for name in available_backends():
        _, stats = two_stage_deliver(
            spikes, *args, backend=name, queue_capacity=16, with_stats=True
        )
        counts[name] = np.asarray(stats.dropped)
        assert (counts[name] >= 0).all()
    # reference defines the contract; single-device sharded and fused agree
    for name, c in counts.items():
        np.testing.assert_array_equal(c, counts["reference"], err_msg=name)


if HAS_HYPOTHESIS:
    _sparsity = st.floats(min_value=0.0, max_value=1.0)
    _caps = st.integers(min_value=1, max_value=64)

    @settings(max_examples=25, deadline=None)
    @given(sparsity=_sparsity, cap=_caps, seed=st.integers(0, 2**16))
    def test_property_queue_semantics_random_sparsity(sparsity, cap, seed):
        tables = _tables(7)
        rng = np.random.default_rng(seed)
        spikes = jnp.asarray(
            rng.random(tables.n_neurons) < sparsity, jnp.float32
        )
        drive, stats = two_stage_deliver(
            spikes, *_deliver_args(tables), queue_capacity=cap, with_stats=True
        )
        n_active = int(np.asarray((spikes != 0).sum()))
        assert int(stats.dropped) == max(0, n_active - cap)
        assert np.isfinite(np.asarray(drive)).all()
        if n_active <= cap:  # lossless regime: parity with the dense path
            dense_drive = two_stage_deliver(spikes, *_deliver_args(tables))
            np.testing.assert_allclose(
                np.asarray(drive), np.asarray(dense_drive), rtol=1e-6
            )
else:  # keep the suite honest about what was skipped
    @given()
    def test_property_queue_semantics_random_sparsity():
        pass  # pragma: no cover


# ---------------------------------------------------------------------------
# stage-1 primitives: queued scatter == dense scatter of the kept subset
# ---------------------------------------------------------------------------
def test_stage1_route_events_matches_dense_on_kept():
    tables = _tables(8)
    rng = np.random.default_rng(9)
    spikes = jnp.asarray(rng.random((3, tables.n_neurons)) < 0.5, jnp.float32)
    q = compact_events(spikes, 12)
    kept = jnp.zeros_like(spikes)
    bidx = jnp.arange(3)[:, None]
    kept = kept.at[bidx, jnp.clip(q.src, 0)].add(q.weight)
    a_q = stage1_route_events(
        q, jnp.asarray(tables.src_tag), jnp.asarray(tables.src_dest),
        tables.n_clusters, tables.k_tags,
    )
    a_d = stage1_route(
        kept, jnp.asarray(tables.src_tag), jnp.asarray(tables.src_dest),
        tables.n_clusters, tables.k_tags,
    )
    np.testing.assert_allclose(np.asarray(a_q), np.asarray(a_d), rtol=1e-6)


def test_accumulate_activity_paths_agree():
    """The int32-overflow fallbacks (int64 offsets / 2-D scatter) compute the
    same activity as the flat int32 fast path."""
    rng = np.random.default_rng(10)
    size = 17
    flat = jnp.asarray(rng.integers(0, size + 1, (6, 30)), jnp.int32)  # incl. sentinel
    w = jnp.asarray(rng.random((6, 30)), jnp.float32)
    base = np.asarray(_accumulate_activity(flat, w, size, _force_path="flat32"))
    np.testing.assert_allclose(
        np.asarray(_accumulate_activity(flat, w, size, _force_path="2d")), base,
        rtol=1e-6,
    )


# ---------------------------------------------------------------------------
# engine threading: capacity + stats through step/run
# ---------------------------------------------------------------------------
def test_engine_queue_step_and_run_emit_stats():
    tables = _tables(11)
    eng = EventEngine(tables, queue_capacity=8)
    b, t = 3, 12
    inp = jnp.zeros((t, b, tables.n_clusters, tables.k_tags)).at[:, :, :, :4].set(3.0)
    carry = eng.init_state(batch=b)
    carry, (spikes, stats) = eng.run(carry, inp)
    assert spikes.shape == (t, b, tables.n_neurons)
    assert stats.dropped.shape == (t, b)
    assert not bool(jnp.isnan(spikes).any())
    assert int(np.asarray(stats.dropped).min()) >= 0


def test_engine_lossless_queue_matches_dense_engine():
    tables = _tables(12)
    eng_dense = EventEngine(tables)
    eng_queue = EventEngine(tables, queue_capacity=tables.n_neurons)
    inp = jnp.zeros((tables.n_clusters, tables.k_tags)).at[:, 0].set(4.0)
    c_d, c_q = eng_dense.init_state(), eng_queue.init_state()
    for _ in range(15):
        c_d, s_d = eng_dense.step(c_d, inp)
        c_q, (s_q, stats) = eng_queue.step(c_q, inp)
        np.testing.assert_allclose(np.asarray(s_q), np.asarray(s_d), atol=1e-6)
        assert int(stats.dropped) == 0


def test_engine_overflowing_queue_stays_finite_and_counts():
    tables = _tables(13)
    eng = EventEngine(tables, queue_capacity=2)
    inp = jnp.zeros((tables.n_clusters, tables.k_tags)).at[:, :8].set(6.0)
    carry = eng.init_state()
    saw_drop = False
    for _ in range(25):
        carry, (spikes, stats) = eng.step(carry, inp)
        assert np.isfinite(np.asarray(spikes)).all()
        saw_drop |= int(stats.dropped) > 0
    assert saw_drop  # the stimulus drives far more than 2 neurons active


def test_engine_rejects_bad_capacity():
    with pytest.raises(ValueError, match="queue_capacity"):
        EventEngine(_tables(14), queue_capacity=0)


def test_engine_donate_carry_threads_correctly():
    """donate_carry=True matches the default engine when the carry is
    properly threaded (donation is a no-op on CPU; the flag path and the
    thread-the-carry contract are what's under test)."""
    tables = _tables(16)
    eng = EventEngine(tables, queue_capacity=16, donate_carry=True)
    eng_ref = EventEngine(tables, queue_capacity=16)
    inp = jnp.zeros((tables.n_clusters, tables.k_tags)).at[:, 0].set(4.0)
    c_d, c_r = eng.init_state(), eng_ref.init_state()
    for _ in range(10):
        c_d, (s_d, _) = eng.step(c_d, inp)
        c_r, (s_r, _) = eng_ref.step(c_r, inp)
        np.testing.assert_allclose(np.asarray(s_d), np.asarray(s_r), atol=1e-6)


@pytest.mark.parametrize("lossless", [True, False])
def test_queue_compacts_only_below_capacity_n(lossless):
    """A queue of capacity >= N is lossless, so the step skips compaction and
    routes dense: no op of the compiled step lies under scope ``compact``. A
    smaller queue can drop, so its step compacts."""
    tables = _tables(19)
    n = tables.n_neurons
    eng = EventEngine(tables, queue_capacity=n if lossless else n // 4)
    b = 2
    inp = jax.ShapeDtypeStruct((b, tables.n_clusters, tables.k_tags), jnp.float32)
    text = eng.compiled_step_text(eng.init_state(batch=b), inp)
    scoped = [path for path in re.findall(r'op_name="([^"]*)"', text)
              if "compact" in re.split(r"[/;]", path)]
    assert not scoped if lossless else scoped


def test_engine_sharded_backend_queue_single_device():
    """The sharded backend's per-core FIFO path on the default 1x1 mesh."""
    tables = _tables(15)
    eng = EventEngine(tables, backend="sharded", queue_capacity=tables.n_neurons)
    eng_ref = EventEngine(tables, queue_capacity=tables.n_neurons)
    b = 2
    inp = jnp.zeros((b, tables.n_clusters, tables.k_tags)).at[:, :, 1].set(4.0)
    c_s, c_r = eng.init_state(batch=b), eng_ref.init_state(batch=b)
    for _ in range(10):
        c_s, (s_s, st_s) = eng.step(c_s, inp)
        c_r, (s_r, st_r) = eng_ref.step(c_r, inp)
        np.testing.assert_allclose(np.asarray(s_s), np.asarray(s_r), atol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(st_s.dropped), np.asarray(st_r.dropped)
        )
