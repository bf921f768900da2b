"""Ring fast path (DESIGN.md §14): time-wheel delivery vs the roll oracle.

The property suite locks the tentpole equivalence: the static-entry-table /
prefix-count / time-wheel pipeline of kernels/fabric_deliver must be
bit-identical to the per-step roll pipeline (``compact_events`` →
``stage1_route_events_fabric`` → ``advance_inflight``) in everything
integer-valued — arrival steps, drive patterns, queue drops, link drops,
delivered/hops counts — across random geometries, delays and capacities,
including cursor wraparound (T > max_delay). Float latency/energy sums may
associate differently (same addends) and are compared allclose.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dispatch import FabricBackend
from repro.core.event_engine import EventEngine
from repro.core.routing import ChipConstants, Fabric
from repro.core.two_stage import (
    _accumulate_into,
    compact_events,
    stage1_route,
    stage1_route_events,
)
from repro.kernels.fabric_deliver.ref import fabric_deliver_ring_ref

from tests._hypothesis_compat import given, settings, st

DT = 1e-3


def _random_tables(rng, n, n_clusters, k, e=3, s=4):
    src_tag = rng.integers(-1, k, (n, e)).astype(np.int32)
    src_dest = rng.integers(0, n_clusters, (n, e)).astype(np.int32)
    cam_tag = rng.integers(-1, k, (n, s)).astype(np.int32)
    cam_syn = rng.integers(0, 4, (n, s)).astype(np.int32)
    return src_tag, src_dest, cam_tag, cam_syn


def _assert_stats_equal(a, b, msg, float_rtol=1e-5):
    for f in ("dropped", "link_dropped", "delivered", "hops"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{msg}: {f}",
        )
    for f in ("latency_s", "energy_j"):
        np.testing.assert_allclose(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            rtol=float_rtol, err_msg=f"{msg}: {f}",
        )


# ---------------------------------------------------------------------------
# the tentpole property: ring == roll, bit-exact on integers, over whole runs
# ---------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    grid=st.sampled_from([(1, 2), (2, 2), (3, 2)]),
    cores_per_tile=st.integers(1, 2),
    cluster_size=st.integers(2, 5),
    k_tags=st.sampled_from([4, 8, 16]),
    link_capacity=st.sampled_from([None, 1, 2, 4]),
    queue_frac=st.sampled_from([0.25, 0.6, 1.0]),
    latency_mult=st.sampled_from([0.5, 1.0, 2.0]),
    batch=st.sampled_from([None, 2]),
)
def test_ring_matches_roll_property(
    seed, grid, cores_per_tile, cluster_size, k_tags, link_capacity,
    queue_frac, latency_mult, batch,
):
    """Random geometry/delay/capacity: the ring fast path, the ring ref and
    the roll oracle agree step-for-step over T > max_delay steps (cursor
    wraps), on drives and every integer stat; floats allclose."""
    gx, gy = grid
    fab = Fabric(
        grid_x=gx, grid_y=gy, cores_per_tile=cores_per_tile,
        constants=ChipConstants(latency_across_chip_s=latency_mult * DT),
    )
    nc = fab.n_cores
    n = nc * cluster_size
    rng = np.random.default_rng(seed)
    src_tag, src_dest, cam_tag, cam_syn = _random_tables(rng, n, nc, k_tags)
    qcap = max(1, int(queue_frac * n))

    be = FabricBackend(fabric=fab, dt=DT, link_capacity=link_capacity)
    model, arrs = be.model_for(nc)
    entries = be.build_entries(src_tag, src_dest, cluster_size, k_tags)
    t_steps = model.max_delay + 3  # > max_delay + 1: the cursor wraps

    inflight = be.init_inflight(nc, k_tags, batch=batch)
    ring_f, cur_f = be.init_ring(nc, k_tags, batch=batch)
    ring_r, cur_r = be.init_ring(nc, k_tags, batch=batch)
    lead = () if batch is None else (batch,)
    for t in range(t_steps):
        spikes = jnp.asarray(
            (rng.random((*lead, n)) < 0.4) * rng.random((*lead, n)), jnp.float32
        )
        d_roll, inflight, s_roll = be.deliver_fabric(
            spikes, src_tag, src_dest, cam_tag, cam_syn, cluster_size, k_tags,
            inflight=inflight, queue_capacity=qcap,
        )
        d_fast, ring_f, cur_f, s_fast = be.deliver_fabric_ring(
            spikes, entries, cam_tag, cam_syn, cluster_size, k_tags,
            ring_f, cur_f, queue_capacity=qcap,
        )
        d_ref, ring_r, cur_r, s_ref = fabric_deliver_ring_ref(
            spikes, jnp.asarray(src_tag), jnp.asarray(src_dest),
            jnp.asarray(cam_tag), jnp.asarray(cam_syn), cluster_size, k_tags,
            ring_r, cur_r, cluster_tile=arrs["cluster_tile"],
            delay_steps=arrs["delay_steps"], n_tiles=model.n_tiles,
            max_delay=model.max_delay, link_capacity=model.link_capacity,
            queue_capacity=qcap, mesh_hops=arrs["mesh_hops"],
            latency_s=arrs["latency_s"], energy_j=arrs["energy_j"],
        )
        np.testing.assert_allclose(
            np.asarray(d_roll), np.asarray(d_ref), rtol=1e-6, atol=1e-6,
            err_msg=f"step {t}: roll vs ref drive",
        )
        np.testing.assert_allclose(
            np.asarray(d_roll), np.asarray(d_fast), rtol=1e-5, atol=1e-5,
            err_msg=f"step {t}: roll vs fast-path drive",
        )
        _assert_stats_equal(s_roll, s_ref, f"step {t}: roll vs ref")
        _assert_stats_equal(s_roll, s_fast, f"step {t}: roll vs fast")
    # after T steps the wheel has wrapped; cursors agree and the carried
    # mass (events still in transit) matches the roll's in-flight tail
    assert int(cur_f) == t_steps % (model.max_delay + 1) == int(cur_r)
    np.testing.assert_allclose(
        np.asarray(ring_f).sum(), np.asarray(inflight).sum(), rtol=1e-5
    )


# ---------------------------------------------------------------------------
# engine-level parity + carry contract
# ---------------------------------------------------------------------------
def _engine_tables(rng, n=48, cluster=6, k=12):
    from repro.core.tags import RoutingTables

    nc = n // cluster
    src_tag, src_dest, cam_tag, cam_syn = _random_tables(rng, n, nc, k)
    return RoutingTables(
        src_tag=src_tag, src_dest=src_dest, cam_tag=cam_tag, cam_syn=cam_syn,
        cluster_size=cluster, k_tags=k,
    )


def _engines(tables, **extra):
    from repro.core.neuron import NeuronParams

    params = NeuronParams(dt=DT)
    fab = Fabric(grid_x=2, grid_y=2, cores_per_tile=2,
                 constants=ChipConstants(latency_across_chip_s=2 * DT))
    ring = EventEngine(tables, params, fabric=fab, queue_capacity=20,
                       fabric_options={"dt": DT, **extra})
    roll = EventEngine(tables, params, fabric=fab, queue_capacity=20,
                       fabric_options={"dt": DT, "ring": False, **extra})
    return ring, roll


def test_engine_ring_run_matches_roll():
    """Whole-scan engine parity: spikes and stats identical ring vs roll,
    over enough steps for several cursor revolutions."""
    rng = np.random.default_rng(2)
    tables = _engine_tables(rng)
    e_ring, e_roll = _engines(tables)
    assert e_ring.fabric_ring and not e_roll.fabric_ring
    assert e_ring.fabric_model.max_delay >= 2  # delays actually in play
    b, t = 3, 11
    inp = jnp.asarray(
        (rng.random((t, b, tables.n_clusters, tables.k_tags)) < 0.05) * 4.0,
        jnp.float32,
    )
    c_ring, (spk_ring, st_ring) = e_ring.run(e_ring.init_state(batch=b), inp)
    c_roll, (spk_roll, st_roll) = e_roll.run(e_roll.init_state(batch=b), inp)
    np.testing.assert_array_equal(np.asarray(spk_ring), np.asarray(spk_roll))
    _assert_stats_equal(st_ring, st_roll, "scan stats")
    assert len(c_ring) == 4 and len(c_roll) == 3
    assert c_ring[2].shape == (b, e_ring.fabric_model.max_delay + 1,
                               tables.n_clusters, tables.k_tags)
    assert int(c_ring[3]) == t % (e_ring.fabric_model.max_delay + 1)


def test_engine_ring_sharded_step_matches_local():
    """The ring-mode sharded fabric step (1x1 mesh; multi-device parity in
    test_distributed.py) matches the local ring step including the carried
    wheel and the replicated cursor."""
    rng = np.random.default_rng(3)
    tables = _engine_tables(rng)
    eng, _ = _engines(tables)
    mesh = jax.make_mesh((1,), ("model",))
    sharded = eng.make_sharded_step(mesh, axis="model")
    state, prev, ring, cur = eng.init_state()
    prev = prev.at[jnp.arange(0, tables.n_neurons, 3)].set(1.0)
    inp = jnp.zeros((tables.n_clusters, tables.k_tags)).at[:, 0].set(4.0)
    zeros = jnp.zeros((tables.n_neurons,))
    for t in range(5):
        (st_l, sp_l, ring_l, cur_l), (_, stats_l) = eng.step(
            (state, prev, ring, cur), inp
        )
        st_s, sp_s, ring_s, cur_s, stats_s = sharded(
            eng.tables, state, prev, ring, cur, inp, zeros
        )
        np.testing.assert_allclose(np.asarray(sp_l), np.asarray(sp_s), atol=1e-6)
        np.testing.assert_allclose(np.asarray(ring_l), np.asarray(ring_s), atol=1e-6)
        assert int(cur_l) == int(cur_s)
        _assert_stats_equal(stats_l, stats_s, f"step {t}")
        state, prev, ring, cur = st_l, sp_l, ring_l, cur_l


def test_reset_slots_ring_leak_free_at_any_phase():
    """Evicting a tenant mid-revolution (cursor != 0, events in transit at
    several depths) must zero that slot's entire wheel: with zero input the
    evicted slot stays silent for good, while the surviving tenant's
    in-transit events still arrive."""
    rng = np.random.default_rng(4)
    tables = _engine_tables(rng)
    eng, _ = _engines(tables)
    d1 = eng.fabric_model.max_delay + 1
    assert d1 >= 3
    b = 2
    carry = eng.init_state(batch=b)
    inp_hot = jnp.asarray(
        (rng.random((b, tables.n_clusters, tables.k_tags)) < 0.3) * 6.0,
        jnp.float32,
    )
    zero_inp = jnp.zeros_like(inp_hot)
    # drive both tenants until the cursor sits mid-phase with transit traffic
    for _ in range(d1 + 1):
        carry, _ = eng.step(carry, inp_hot)
    assert int(carry[3]) != 0  # genuinely mid-revolution
    assert float(jnp.abs(carry[2][0]).sum()) > 0  # slot 0 has events in transit
    carry = eng.reset_slots(carry, np.asarray([True, False]))
    assert float(jnp.abs(carry[2][0]).sum()) == 0.0
    survivor_delivered = 0
    for _ in range(2 * d1):
        carry, (spikes, stats) = eng.step(carry, zero_inp)
        assert float(jnp.abs(spikes[0]).sum()) == 0.0  # evicted slot silent
        assert int(stats.delivered[0]) == 0
        survivor_delivered += int(stats.delivered[1])
    assert survivor_delivered > 0  # the unmasked tenant kept its traffic


def test_ring_kernel_interpret_matches_jnp():
    """The fabric_deliver Pallas kernel (interpret mode) and the jnp fast
    path produce identical drives and rings over several wrapped steps."""
    rng = np.random.default_rng(5)
    fab = Fabric(grid_x=2, grid_y=1, cores_per_tile=2,
                 constants=ChipConstants(latency_across_chip_s=2 * DT))
    nc, cs, k = fab.n_cores, 4, 8
    n = nc * cs
    src_tag, src_dest, cam_tag, cam_syn = _random_tables(rng, n, nc, k)
    be_j = FabricBackend(fabric=fab, dt=DT, link_capacity=2)
    be_k = FabricBackend(fabric=fab, dt=DT, link_capacity=2, interpret=True)
    entries = be_j.build_entries(src_tag, src_dest, cs, k)
    model, _ = be_j.model_for(nc)
    b = 2
    ring_j, cur_j = be_j.init_ring(nc, k, batch=b)
    ring_k, cur_k = be_k.init_ring(nc, k, batch=b)
    for t in range(2 * (model.max_delay + 1) + 1):
        spikes = jnp.asarray((rng.random((b, n)) < 0.5), jnp.float32)
        ext = jnp.asarray(rng.random((b, nc, k)) < 0.1, jnp.float32)
        d_j, ring_j, cur_j, s_j = be_j.deliver_fabric_ring(
            spikes, entries, cam_tag, cam_syn, cs, k, ring_j, cur_j,
            external_activity=ext, queue_capacity=n // 2,
        )
        d_k, ring_k, cur_k, s_k = be_k.deliver_fabric_ring(
            spikes, entries, cam_tag, cam_syn, cs, k, ring_k, cur_k,
            external_activity=ext, queue_capacity=n // 2,
        )
        np.testing.assert_allclose(
            np.asarray(d_j), np.asarray(d_k), atol=1e-5, err_msg=f"step {t}"
        )
        np.testing.assert_allclose(
            np.asarray(ring_j), np.asarray(ring_k), atol=1e-5, err_msg=f"step {t}"
        )
        _assert_stats_equal(s_j, s_k, f"step {t}")


@pytest.mark.parametrize("b", [1, 130], ids=["one-slot", "several-blocks"])
def test_ring_kernel_slot_blocks_bit_identical(b):
    """Integer spikes and external input, as served: the slot-blocked
    fabric_deliver kernel (interpret mode) equals the jnp fast path exactly,
    drives and rings, with one slot and with more slots than one block holds
    (several blocks and padded slots). A link capacity of 1 drops entries to
    weight 0, and the steps outnumber the ring slots, so the cursor wraps."""
    from repro.kernels.fabric_deliver.fabric_deliver import fabric_plan

    rng = np.random.default_rng(b)
    fab = Fabric(grid_x=2, grid_y=1, cores_per_tile=2,
                 constants=ChipConstants(latency_across_chip_s=DT))
    nc, cs, k = fab.n_cores, 4, 8
    n = nc * cs
    src_tag, src_dest, cam_tag, cam_syn = _random_tables(rng, n, nc, k)
    be_j = FabricBackend(fabric=fab, dt=DT, link_capacity=1)
    be_k = FabricBackend(fabric=fab, dt=DT, link_capacity=1, interpret=True)
    entries = be_j.build_entries(src_tag, src_dest, cs, k)
    model, _ = be_j.model_for(nc)
    d1 = model.max_delay + 1
    assert d1 >= 2
    m = entries.src.shape[0]
    bb, b_pad = fabric_plan(b, k, cam_tag.shape[1], m, d1, block_c=cs)[:2]
    assert (bb, b_pad) == ((72, 144) if b == 130 else (b, b))
    ring_j, cur_j = be_j.init_ring(nc, k, batch=b)
    ring_k, cur_k = be_k.init_ring(nc, k, batch=b)
    link_dropped = 0
    for t in range(d1 + 1):
        spikes = jnp.asarray((rng.random((b, n)) < 0.5) * rng.integers(1, 4, (b, n)),
                             jnp.float32)
        ext = jnp.asarray((rng.random((b, nc, k)) < 0.1) * 8.0, jnp.float32)
        d_j, ring_j, cur_j, s_j = be_j.deliver_fabric_ring(
            spikes, entries, cam_tag, cam_syn, cs, k, ring_j, cur_j,
            external_activity=ext,
        )
        d_k, ring_k, cur_k, s_k = be_k.deliver_fabric_ring(
            spikes, entries, cam_tag, cam_syn, cs, k, ring_k, cur_k,
            external_activity=ext,
        )
        np.testing.assert_array_equal(np.asarray(d_j), np.asarray(d_k), err_msg=f"step {t}")
        np.testing.assert_array_equal(np.asarray(ring_j), np.asarray(ring_k),
                                      err_msg=f"step {t}")
        _assert_stats_equal(s_j, s_k, f"step {t}")
        link_dropped += int(np.asarray(s_k.link_dropped).sum())
    assert int(cur_k) == (d1 + 1) % d1 == int(cur_j)
    assert link_dropped > 0  # arbitration zeroed some entries' weights
    assert np.asarray(ring_k).any()  # events in transit across the wrap


# ---------------------------------------------------------------------------
# building blocks: scatter helper + dense stage-1 shortcut
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("path", ["flat32", "flat64", "2d"])
def test_accumulate_into_forced_paths_agree(path):
    """All overflow-guard paths of the in-place ring scatter add the same
    mass to the same cells — including out-of-range drops."""
    if path == "flat64" and not jax.config.jax_enable_x64:
        pytest.skip("flat64 path needs JAX_ENABLE_X64")
    rng = np.random.default_rng(6)
    b, size, m = 3, 40, 25
    buf = jnp.asarray(rng.random((b, size)), jnp.float32)
    flat = jnp.asarray(rng.integers(0, size, (b, m)), jnp.int32)
    w = jnp.asarray(rng.random((b, m)), jnp.float32)
    want = np.asarray(buf).copy()
    for i in range(b):
        for j in range(m):
            want[i, int(flat[i, j])] += float(w[i, j])
    got = _accumulate_into(buf, flat, w, _force_path=path)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)
    # batch-shared 1-D indices broadcast across the batch
    flat1 = flat[0]
    got1 = _accumulate_into(buf, flat1, w, _force_path=path)
    want1 = np.asarray(buf).copy()
    for i in range(b):
        for j in range(m):
            want1[i, int(flat1[j])] += float(w[i, j])
    np.testing.assert_allclose(np.asarray(got1), want1, rtol=1e-5)


def test_dense_stage1_shortcut_matches_lossless_queue():
    """queue_capacity >= N: the dense scatter shortcut is bit-identical to
    compacting through a lossless queue (the satellite-2 regression — the
    queued path at 100% activity paid compaction for nothing)."""
    rng = np.random.default_rng(7)
    n, nc, k = 48, 8, 16
    src_tag, src_dest, _, _ = _random_tables(rng, n, nc, k)
    spikes = jnp.asarray(
        (rng.random((4, n)) < 0.9) * rng.random((4, n)), jnp.float32
    )
    a_dense = stage1_route(spikes, src_tag, src_dest, nc, k)
    q = compact_events(spikes, n)
    a_queue = stage1_route_events(q, src_tag, src_dest, nc, k)
    np.testing.assert_array_equal(np.asarray(a_dense), np.asarray(a_queue))
    assert int(np.asarray(q.dropped).sum()) == 0
    # the backend hook takes the shortcut for cap >= N and stays bit-identical
    from repro.core.dispatch import _stage1_activity

    a_hook, dropped = _stage1_activity(spikes, src_tag, src_dest, nc, k, n)
    np.testing.assert_array_equal(np.asarray(a_hook), np.asarray(a_dense))
    assert int(np.asarray(dropped).sum()) == 0
