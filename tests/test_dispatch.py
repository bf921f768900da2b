"""Batched, backend-pluggable event dispatch (core/dispatch.py).

Covers the acceptance criteria of the batched-dispatch refactor and the
event-sparse delivery layer:
  * batched step/run == independent single runs (B=3 vs 3x B=1)
  * every built-in backend (reference / pallas / sharded / fused / fabric,
    the last in its collapsed single-step mode on a 2x2 mesh) matches the
    dense oracle for B in {1, 4} at activity levels {1%, 10%, 100%},
    dense and event-queued (queue below capacity)
  * the batched Pallas kernels match the batched jnp reference
  * lookup ergonomics (unknown names, instance pass-through)
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.dispatch import (
    DispatchBackend,
    FabricBackend,
    FusedBackend,
    PallasBackend,
    available_backends,
    get_backend,
    two_stage_deliver,
)
from repro.core.event_engine import EventEngine, dense_weights_from_tables
from repro.core.routing import Fabric
from repro.core.tags import NetworkSpec, compile_network
from repro.core.two_stage import stage1_route, stage2_cam_match
from repro.kernels.cam_match.cam_match import cam_match_pallas
from repro.kernels.cam_match.ref import cam_match_ref


ALL_BACKENDS = ["reference", "pallas", "sharded", "fused", "fabric"]


def _bk(name):
    """'pallas'/'fused' with the platform default would fall back to the jnp
    reference on CPU; force interpret mode so CI exercises the real kernels.
    'fabric' puts the test networks' 3 clusters on 3 tiles of a 2x2 mesh, so
    events cross links."""
    if name == "pallas":
        return PallasBackend(interpret=True)
    if name == "fused":
        return FusedBackend(interpret=True)
    if name == "fabric":
        return FabricBackend(fabric=Fabric(grid_x=2, grid_y=2, cores_per_tile=1))
    return name


def _tables(seed, n=48, cluster=16, k=48, edges=60):
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


# ---------------------------------------------------------------------------
# backend lookup
# ---------------------------------------------------------------------------
def test_registry_lists_all_builtin_backends():
    assert set(available_backends()) == {"reference", "pallas", "sharded", "fused", "fabric"}


@pytest.mark.parametrize("name", ["no-such-backend", "auto"])
def test_unknown_backend_raises_with_choices(name):
    with pytest.raises(ValueError, match="unknown dispatch backend"):
        EventEngine(_tables(0), backend=name)


def test_instance_passes_through_and_options_construct():
    inst = PallasBackend(block_c=8)
    assert get_backend(inst) is inst
    assert get_backend("pallas", block_c=8) == inst
    assert isinstance(get_backend(None), DispatchBackend)  # default
    with pytest.raises(ValueError, match="passed as an instance"):
        get_backend(inst, block_c=4)  # options + instance = caller confusion


# ---------------------------------------------------------------------------
# batched primitives == per-element single calls
# ---------------------------------------------------------------------------
def test_batched_stage1_equals_stacked_single():
    tables = _tables(0)
    rng = np.random.default_rng(1)
    spikes = jnp.asarray(rng.random((5, tables.n_neurons)), jnp.float32)
    src_tag, src_dest = jnp.asarray(tables.src_tag), jnp.asarray(tables.src_dest)
    batched = stage1_route(spikes, src_tag, src_dest, tables.n_clusters, tables.k_tags)
    singles = jnp.stack([
        stage1_route(spikes[i], src_tag, src_dest, tables.n_clusters, tables.k_tags)
        for i in range(5)
    ])
    np.testing.assert_allclose(np.asarray(batched), np.asarray(singles), rtol=1e-6)


def test_batched_stage2_equals_stacked_single():
    tables = _tables(2)
    rng = np.random.default_rng(3)
    act = jnp.asarray(rng.random((4, tables.n_clusters, tables.k_tags)), jnp.float32)
    cam_tag, cam_syn = jnp.asarray(tables.cam_tag), jnp.asarray(tables.cam_syn)
    batched = stage2_cam_match(act, cam_tag, cam_syn, tables.cluster_size)
    singles = jnp.stack([
        stage2_cam_match(act[i], cam_tag, cam_syn, tables.cluster_size) for i in range(4)
    ])
    np.testing.assert_allclose(np.asarray(batched), np.asarray(singles), rtol=1e-6)


# ---------------------------------------------------------------------------
# backend parity vs the dense oracle, B in {1, 4}
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("b", [1, 4])
def test_backend_matches_dense_oracle(backend, b):
    tables = _tables(7)
    dense = jnp.asarray(dense_weights_from_tables(tables))
    rng = np.random.default_rng(b * 100 + 9)
    spikes = jnp.asarray(rng.random((b, tables.n_neurons)) < 0.3, jnp.float32)
    drive = two_stage_deliver(
        spikes,
        jnp.asarray(tables.src_tag), jnp.asarray(tables.src_dest),
        jnp.asarray(tables.cam_tag), jnp.asarray(tables.cam_syn),
        tables.cluster_size, tables.k_tags, backend=_bk(backend),
    )
    ref = jnp.einsum("dst,bs->bdt", dense, spikes)
    assert drive.shape == (b, tables.n_neurons, 4)
    np.testing.assert_allclose(np.asarray(drive), np.asarray(ref), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("b", [1, 4])
@pytest.mark.parametrize("activity", [0.01, 0.1, 1.0])
def test_backend_event_queued_matches_dense_oracle(backend, b, activity):
    """Event-sparse delivery == dense oracle at every sparsity level, for
    every backend, while the AER queue is below capacity (DESIGN.md §10)."""
    tables = _tables(31)
    dense = jnp.asarray(dense_weights_from_tables(tables))
    rng = np.random.default_rng(int(activity * 100) + b)
    spikes = jnp.asarray(rng.random((b, tables.n_neurons)) < activity, jnp.float32)
    drive, stats = two_stage_deliver(
        spikes,
        jnp.asarray(tables.src_tag), jnp.asarray(tables.src_dest),
        jnp.asarray(tables.cam_tag), jnp.asarray(tables.cam_syn),
        tables.cluster_size, tables.k_tags, backend=_bk(backend),
        queue_capacity=tables.n_neurons, with_stats=True,
    )
    ref = jnp.einsum("dst,bs->bdt", dense, spikes)
    np.testing.assert_allclose(np.asarray(drive), np.asarray(ref), rtol=1e-5, atol=1e-6)
    assert stats.dropped.shape == (b,)
    assert int(np.asarray(stats.dropped).max()) == 0  # below capacity: lossless


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_backend_multidim_batch_shape(backend):
    """The [..., N] contract holds for >1 leading batch dims on every backend."""
    tables = _tables(23)
    rng = np.random.default_rng(24)
    spikes = jnp.asarray(rng.random((2, 3, tables.n_neurons)) < 0.3, jnp.float32)
    drive = two_stage_deliver(
        spikes,
        jnp.asarray(tables.src_tag), jnp.asarray(tables.src_dest),
        jnp.asarray(tables.cam_tag), jnp.asarray(tables.cam_syn),
        tables.cluster_size, tables.k_tags, backend=_bk(backend),
    )
    dense = jnp.asarray(dense_weights_from_tables(tables))
    ref = jnp.einsum("dst,bcs->bcdt", dense, spikes)
    assert drive.shape == (2, 3, tables.n_neurons, 4)
    np.testing.assert_allclose(np.asarray(drive), np.asarray(ref), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_backend_unbatched_shape_preserved(backend):
    """B-less inputs keep the original [N, 4] contract on every backend."""
    tables = _tables(5)
    rng = np.random.default_rng(6)
    spikes = jnp.asarray(rng.random(tables.n_neurons) < 0.3, jnp.float32)
    drive = two_stage_deliver(
        spikes,
        jnp.asarray(tables.src_tag), jnp.asarray(tables.src_dest),
        jnp.asarray(tables.cam_tag), jnp.asarray(tables.cam_syn),
        tables.cluster_size, tables.k_tags, backend=_bk(backend),
    )
    dense = jnp.asarray(dense_weights_from_tables(tables))
    ref = jnp.einsum("dst,s->dt", dense, spikes)
    assert drive.shape == (tables.n_neurons, 4)
    np.testing.assert_allclose(np.asarray(drive), np.asarray(ref), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# batched Pallas kernel vs batched reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b", [1, 4])
def test_cam_match_pallas_batched_matches_ref(b):
    rng = np.random.default_rng(b)
    ncl, c, s, k = 3, 16, 8, 32
    n = ncl * c
    act = jnp.asarray(rng.random((b, ncl, k)), jnp.float32)
    tag = jnp.asarray(rng.integers(-1, k, (n, s)), jnp.int32)
    syn = jnp.asarray(rng.integers(0, 4, (n, s)), jnp.int32)
    out_k = cam_match_pallas(act, tag, syn, c, block_c=8)
    out_r = cam_match_ref(act, tag, syn, c)
    assert out_k.shape == (b, n, 4)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fused Pallas kernel vs the jnp event-sparse reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b", [1, 3])
def test_fused_deliver_pallas_matches_ref(b):
    from repro.core.two_stage import compact_events
    from repro.kernels.fused_deliver import fused_deliver, fused_deliver_ref

    rng = np.random.default_rng(b + 40)
    ncl, c, s, k, e = 3, 16, 8, 32, 4
    n = ncl * c
    src_tag = jnp.asarray(rng.integers(-1, k, (n, e)), jnp.int32)
    src_dest = jnp.asarray(rng.integers(0, ncl, (n, e)), jnp.int32)
    cam_tag = jnp.asarray(rng.integers(-1, k, (n, s)), jnp.int32)
    cam_syn = jnp.asarray(rng.integers(0, 4, (n, s)), jnp.int32)
    spikes = jnp.asarray(rng.random((b, n)) < 0.4, jnp.float32)
    ext = jnp.asarray(rng.random((b, ncl, k)), jnp.float32)
    queue = compact_events(spikes, 24)
    out_k = fused_deliver(
        queue, src_tag, src_dest, cam_tag, cam_syn, c, k,
        external_activity=ext, block_c=8, interpret=True,
    )
    out_r = fused_deliver_ref(
        queue, src_tag, src_dest, cam_tag, cam_syn, c, k, external_activity=ext
    )
    assert out_k.shape == (b, n, 4)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("active,capacity", [
    ((0, 0), 48),  # no events: the external-input row alone
    ((3, 0, 5), 48),  # 48 and 80 entries: one partial chunk
    ((8, 16), 48),  # 128 and 256 entries: exact chunk boundaries
    ((48, 11), 48),  # every source active: all 6 chunks
    ((48, 44), 40),  # over capacity: 40 kept, the rest dropped
], ids=["empty", "partial", "boundary", "all", "drops"])
def test_fused_deliver_scans_only_occupied_entries(monkeypatch, active, capacity):
    """Stage 1 stops after the chunk that holds the queue's last occupied
    entry: bit-identical to the full scan of the entry axis, equal to the
    jnp reference, and blind to whatever lies in the chunks past it."""
    import repro.kernels.cam_match.cam_match as cm
    from repro.core.two_stage import compact_events
    from repro.kernels.fused_deliver import fused_deliver, fused_deliver_pallas
    from repro.kernels.fused_deliver import fused_deliver_ref
    from repro.kernels.fused_deliver.ops import _event_entries_flat

    ncl, c, s, k, e, chunk = 3, 16, 8, 32, 16, 128
    monkeypatch.setattr(cm, "_PLANE_BUDGET_ELEMS", chunk * k)
    n, b = ncl * c, len(active)
    rng = np.random.default_rng(sum(active) + capacity)
    src_tag = jnp.asarray(rng.integers(-1, k, (n, e)), jnp.int32)
    src_dest = jnp.asarray(rng.integers(0, ncl, (n, e)), jnp.int32)
    cam_tag = jnp.asarray(rng.integers(-1, k, (n, s)), jnp.int32)
    cam_syn = jnp.asarray(rng.integers(0, 4, (n, s)), jnp.int32)
    spikes = np.zeros((b, n), np.float32)
    for i, a in enumerate(active):
        spikes[i, rng.choice(n, a, replace=False)] = rng.integers(1, 4, a)
    ext = jnp.asarray(rng.random((b, ncl, k)), jnp.float32)
    queue = compact_events(jnp.asarray(spikes), capacity)
    assert np.asarray(queue.dropped).tolist() == [max(0, a - capacity) for a in active]

    ev_flat, ev_w, n_entries = _event_entries_flat(queue, src_tag, src_dest, k)
    assert ev_flat.shape[-1] // chunk >= 4
    assert np.asarray(n_entries).tolist() == [min(a, capacity) * e for a in active]

    def kernel(f, w, ne):
        return fused_deliver_pallas(f, w, cam_tag, cam_syn, ext, c, k, n_entries=ne,
                                    block_c=8, interpret=True)

    bounded = kernel(ev_flat, ev_w, n_entries)
    np.testing.assert_array_equal(np.asarray(bounded), np.asarray(kernel(ev_flat, ev_w, None)))
    out_ops = fused_deliver(queue, src_tag, src_dest, cam_tag, cam_syn, c, k,
                            external_activity=ext, block_c=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_ops), np.asarray(bounded))
    out_r = fused_deliver_ref(queue, src_tag, src_dest, cam_tag, cam_syn, c, k,
                              external_activity=ext)
    np.testing.assert_allclose(np.asarray(bounded), np.asarray(out_r), rtol=1e-5, atol=1e-5)

    # live entries planted past each row's last scanned chunk are never read
    scanned = -(-np.asarray(n_entries) // chunk) * chunk
    tail = np.arange(ev_flat.shape[-1])[None, :] >= scanned[:, None]
    poisoned = kernel(jnp.where(tail, 0, ev_flat), jnp.where(tail, 1.0, ev_w), n_entries)
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(bounded))
    if tail.any():
        full = kernel(jnp.where(tail, 0, ev_flat), jnp.where(tail, 1.0, ev_w), None)
        assert not np.array_equal(np.asarray(full), np.asarray(bounded))


# ---------------------------------------------------------------------------
# engine: batched carry == independent single runs
# ---------------------------------------------------------------------------
def test_engine_batched_step_equals_independent_runs():
    tables = _tables(11)
    eng = EventEngine(tables)
    b = 3
    rng = np.random.default_rng(12)
    # distinct stimulus per stream so the batch is genuinely heterogeneous
    inp_b = jnp.asarray(rng.random((b, tables.n_clusters, tables.k_tags)) * 4.0,
                        jnp.float32)
    carry_b = eng.init_state(batch=b)
    singles = [eng.init_state() for _ in range(b)]
    for _ in range(20):
        carry_b, spikes_b = eng.step(carry_b, inp_b)
        for i in range(b):
            singles[i], s_i = eng.step(singles[i], inp_b[i])
            np.testing.assert_allclose(
                np.asarray(spikes_b[i]), np.asarray(s_i), atol=1e-6
            )
    for i in range(b):
        np.testing.assert_allclose(
            np.asarray(carry_b[0].v[i]), np.asarray(singles[i][0].v), atol=1e-6
        )


def test_engine_batched_run_scan_shapes_and_no_nan():
    tables = _tables(13)
    eng = EventEngine(tables)
    b, t = 4, 30
    inp = jnp.zeros((t, b, tables.n_clusters, tables.k_tags)).at[:, :, :, :4].set(2.0)
    carry, out = eng.run(eng.init_state(batch=b), inp)
    assert out.shape == (t, b, tables.n_neurons)
    assert carry[0].v.shape == (b, tables.n_neurons)
    assert not bool(jnp.isnan(out).any())


@pytest.mark.parametrize("backend", ["pallas", "sharded", "fused"])
def test_engine_backends_agree_with_reference_batched(backend):
    tables = _tables(17)
    b = 2
    inp = jnp.zeros((b, tables.n_clusters, tables.k_tags)).at[:, :, 0].set(4.0)
    eng_ref = EventEngine(tables, backend="reference")
    eng_alt = EventEngine(tables, backend=_bk(backend))
    carry_r, carry_a = eng_ref.init_state(batch=b), eng_alt.init_state(batch=b)
    for _ in range(10):
        carry_r, s_r = eng_ref.step(carry_r, inp)
        carry_a, s_a = eng_alt.step(carry_a, inp)
        np.testing.assert_allclose(np.asarray(s_a), np.asarray(s_r), atol=1e-5)


def test_dense_reference_step_batched():
    from repro.core.event_engine import dense_reference_step
    from repro.core.neuron import NeuronParams, init_state

    tables = _tables(19)
    dense = jnp.asarray(dense_weights_from_tables(tables))
    params = NeuronParams()
    b = 3
    rng = np.random.default_rng(20)
    spikes = jnp.asarray(rng.random((b, tables.n_neurons)) < 0.4, jnp.float32)
    state_b = init_state(tables.n_neurons, params, batch=b)
    new_b, out_b = dense_reference_step(dense, spikes, state_b, params)
    for i in range(b):
        state_i = init_state(tables.n_neurons, params)
        new_i, out_i = dense_reference_step(dense, spikes[i], state_i, params)
        np.testing.assert_allclose(np.asarray(out_b[i]), np.asarray(out_i), atol=1e-6)
        np.testing.assert_allclose(np.asarray(new_b.v[i]), np.asarray(new_i.v), atol=1e-6)
