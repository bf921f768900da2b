"""Multi-device semantics (8 fake CPU devices via a subprocess, so the main
pytest process keeps its single-device view)."""

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, timeout=600, env_overrides: dict | None = None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    # fake CPU devices; the child never reaches for an accelerator, which
    # the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.update(env_overrides or {})
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, text=True, env=env, cwd=_ROOT, timeout=timeout,
    )
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_hierarchical_collectives_match_flat():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.shard_compat import SM_CHECK_KW, shard_map
        from repro.distributed.collectives import (
            hierarchical_all_reduce, hierarchical_all_to_all)
        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        sm = lambda f, i, o: shard_map(f, mesh=mesh, in_specs=i, out_specs=o, **SM_CHECK_KW)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 37)), jnp.float32)
        h = sm(lambda v: hierarchical_all_reduce(v, "data", "pod"), P(("pod","data")), P(("pod","data")))(x)
        f = sm(lambda v: jax.lax.psum(v, ("pod","data")), P(("pod","data")), P(("pod","data")))(x)
        assert float(jnp.abs(h - f).max()) < 1e-5
        y = jnp.asarray(np.random.default_rng(1).normal(size=(64, 5)), jnp.float32)
        ha = sm(lambda v: hierarchical_all_to_all(v, "data", "pod"), P(("pod","data")), P(("pod","data")))(y)
        fa = sm(lambda v: jax.lax.all_to_all(v.reshape(8,1,5), ("pod","data"), 0, 0).reshape(8,5),
                P(("pod","data")), P(("pod","data")))(y)
        assert float(jnp.abs(ha - fa).max()) == 0.0
        print("OK")
    """)


def test_ef_compression_unbiased_over_time():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.shard_compat import SM_CHECK_KW, shard_map
        from repro.distributed.collectives import ef_all_reduce
        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        g = jnp.asarray(np.random.default_rng(2).normal(size=(8, 16)), jnp.float32)
        step = shard_map(lambda gg, ee: ef_all_reduce(gg, ee, "pod"), mesh=mesh,
            in_specs=(P(("pod","data")), P(("pod","data"))),
            out_specs=(P(("pod","data")), P(("pod","data"))), **SM_CHECK_KW)
        true = shard_map(lambda gg: jax.lax.pmean(gg, "pod"), mesh=mesh,
            in_specs=P(("pod","data")), out_specs=P(("pod","data")), **SM_CHECK_KW)(g)
        err = jnp.zeros_like(g); acc = jnp.zeros_like(g)
        for _ in range(20):
            red, err = step(g, err); acc += red
        one_shot = float(jnp.abs(step(g, jnp.zeros_like(g))[0] - true).max())
        avged = float(jnp.abs(acc / 20 - true).max())
        assert avged < one_shot / 5, (avged, one_shot)  # error feedback integrates away
        print("OK")
    """)


def test_moe_sharded_matches_reference_both_modes():
    _run("""
        import jax, jax.numpy as jnp
        from repro.models.moe import init_moe, moe_reference, moe_block_sharded
        from repro.configs.base import ModelConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,2,2), ("pod","data","model"))
        cfg = ModelConfig(d_model=32, n_experts=8, top_k=2, moe_d_ff=16, capacity_factor=8.0)
        params = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        for shape in ((4, 8, 32), (8, 1, 32), (3, 1, 32)):
            x = jax.random.normal(jax.random.PRNGKey(shape[0]), shape, jnp.float32)
            y_ref, aux_r = moe_reference(params, x.reshape(-1, 32), cfg)
            y_sh, aux_s = jax.jit(lambda p, xx: moe_block_sharded(p, xx, cfg, mesh))(params, x)
            err = float(jnp.abs(y_ref.reshape(shape) - y_sh).max())
            assert err < 1e-5, (shape, err)
            assert bool((aux_r["load"] == aux_s["load"]).all()), shape
        print("OK")
    """)


def test_sharded_event_engine_matches_local():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.tags import NetworkSpec, compile_network
        from repro.core.event_engine import EventEngine
        from repro.core.neuron import NeuronState
        rng = np.random.default_rng(0)
        spec = NetworkSpec(n_neurons=64, cluster_size=8, k_tags=64, max_cam_words=32, max_sram_entries=16)
        seen = set()
        for _ in range(80):
            s, d = int(rng.integers(64)), int(rng.integers(64))
            if (s, d) in seen: continue
            seen.add((s, d)); spec.connect(s, d, int(rng.integers(4)))
        tables = compile_network(spec)
        eng = EventEngine(tables)
        mesh = jax.make_mesh((4,), ("data",))
        sharded = eng.make_sharded_step(mesh, "data")
        carry = eng.init_state()
        state, prev = carry
        inp = jnp.zeros((tables.n_clusters, tables.k_tags)).at[:, 0].set(4.0)
        for _ in range(10):
            (state_l, prev_l), spikes_l = eng.step((state, prev), inp)
            state_s, spikes_s = sharded(eng.tables, state, prev, inp, jnp.zeros((64,)))
            assert float(jnp.abs(spikes_l - spikes_s).max()) < 1e-6
            assert float(jnp.abs(state_l.v - state_s.v).max()) < 1e-6
            state, prev = state_l, spikes_l
        print("OK")
    """)


def test_sharded_event_engine_batched_2d_mesh():
    """Batched make_sharded_step on a 2-D (batch x cluster) mesh matches the
    local batched engine step: streams shard over `data`, clusters over
    `model`, stage-1 reduce-scatter runs per-stream."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.tags import NetworkSpec, compile_network
        from repro.core.event_engine import EventEngine
        rng = np.random.default_rng(0)
        spec = NetworkSpec(n_neurons=64, cluster_size=8, k_tags=64, max_cam_words=32, max_sram_entries=16)
        seen = set()
        for _ in range(80):
            s, d = int(rng.integers(64)), int(rng.integers(64))
            if (s, d) in seen: continue
            seen.add((s, d)); spec.connect(s, d, int(rng.integers(4)))
        tables = compile_network(spec)
        eng = EventEngine(tables)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        sharded = eng.make_sharded_step(mesh, "model", batch_axis="data")
        b = 4
        state, prev = eng.init_state(batch=b)
        inp = jnp.zeros((b, tables.n_clusters, tables.k_tags))
        for stream in range(b):  # heterogeneous stimuli per stream
            inp = inp.at[stream, stream % tables.n_clusters, :4].set(4.0)
        i_ext = jnp.zeros((b, 64))
        for _ in range(10):
            (state_l, prev_l), spikes_l = eng.step((state, prev), inp)
            state_s, spikes_s = sharded(eng.tables, state, prev, inp, i_ext)
            assert float(jnp.abs(spikes_l - spikes_s).max()) < 1e-6
            assert float(jnp.abs(state_l.v - state_s.v).max()) < 1e-6
            state, prev = state_l, spikes_l
        print("OK")
    """)


def test_fabric_sharded_step_matches_local_multidevice():
    """Tiles -> devices (DESIGN.md §11): the fabric-mode sharded step on a
    4-device cluster axis matches the local fabric engine bit-for-bit —
    time-wheel arrivals (ring sharded over clusters, cursor replicated),
    link-FIFO drops, and the psum-reduced stats."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.routing import ChipConstants, Fabric
        from repro.core.tags import NetworkSpec, compile_network
        from repro.core.event_engine import EventEngine
        dt = 1e-3
        const = ChipConstants(latency_across_chip_s=2 * dt)
        fab = Fabric(grid_x=2, grid_y=2, cores_per_tile=2, constants=const)
        rng = np.random.default_rng(0)
        spec = NetworkSpec(n_neurons=64, cluster_size=8, k_tags=64,
                           max_cam_words=32, max_sram_entries=16)
        seen = set()
        for _ in range(90):
            s, d = int(rng.integers(64)), int(rng.integers(64))
            if (s, d) in seen: continue
            seen.add((s, d)); spec.connect(s, d, int(rng.integers(4)))
        tables = compile_network(spec, fabric=fab)
        eng = EventEngine(tables, fabric=fab,
                          fabric_options={"dt": dt, "link_capacity": 2})
        mesh = jax.make_mesh((4,), ("model",))  # 1 tile per device
        sharded = eng.make_sharded_step(mesh, "model")
        state, prev, ring, cur = eng.init_state()
        prev = prev.at[jnp.arange(0, 64, 2)].set(1.0)
        inp = jnp.zeros((tables.n_clusters, tables.k_tags)).at[:, 0].set(4.0)
        saw_drop = saw_arrival = False
        for _ in range(8):
            (st_l, sp_l, ring_l, cur_l), (_, stats_l) = eng.step(
                (state, prev, ring, cur), inp)
            st_s, sp_s, ring_s, cur_s, stats_s = sharded(
                eng.tables, state, prev, ring, cur, inp, jnp.zeros((64,)))
            assert float(jnp.abs(sp_l - sp_s).max()) < 1e-6
            assert float(jnp.abs(ring_l - ring_s).max()) < 1e-6
            assert int(cur_l) == int(cur_s)
            assert float(jnp.abs(st_l.v - st_s.v).max()) < 1e-6
            for f in ("dropped", "link_dropped", "delivered", "hops"):
                assert int(getattr(stats_l, f)) == int(getattr(stats_s, f)), f
            assert abs(float(stats_l.energy_j) - float(stats_s.energy_j)) < 1e-12
            saw_drop |= int(stats_l.link_dropped) > 0
            saw_arrival |= float(ring_l.sum()) > 0
            state, prev, ring, cur = st_l, sp_l, ring_l, cur_l
        assert saw_drop and saw_arrival  # the interesting paths actually ran
        print("OK")
    """)


def test_dryrun_cell_on_test_mesh():
    """run_cell end-to-end on a (2,2,2) mesh with a smoke config — proves the
    lower+compile+analysis pipeline independent of the 512-device sweep.

    Pinned to x64-off: under JAX_ENABLE_X64=1 the LM cell's scan-over-periods
    trips an s64/s32 index-dtype mismatch inside XLA's SPMD partitioner
    (jaxlib-level; unrelated to what this test covers), so the CI x64 variant
    would fail here spuriously."""
    _run(env_overrides={"JAX_ENABLE_X64": "0"}, body="""
        from repro.configs import get_config, Shape
        from repro.launch import dryrun as dr
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,2,2), ("pod","data","model"))
        cfg = get_config("deepseek-v3-671b", smoke=True)
        r = dr.run_cell("deepseek-v3-671b", Shape("train_4k", 32, 8, "train"),
                        multi_pod=True, save=False, mesh=mesh, cfg=cfg)
        assert r["roofline"]["compute_s"] > 0
        assert r["collective_bytes_per_device"]["total"] > 0
        assert r["memory"]["temp_size_in_bytes"] > 0
        print("OK")
    """, timeout=900)


def test_elastic_remesh_restore():
    """Checkpoint written under one mesh restores onto a different mesh."""
    _run("""
        import jax, jax.numpy as jnp, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.launch.mesh import make_mesh
        mesh_a = make_mesh((2, 4), ("data", "model"))
        mesh_b = make_mesh((4, 2), ("data", "model"))
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        xa = jax.device_put(x, NamedSharding(mesh_a, P("data", "model")))
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(1, {"w": xa}, blocking=True)
            out = ck.restore(1, {"w": x},
                             shardings={"w": NamedSharding(mesh_b, P("data", "model"))})
            assert out["w"].sharding.mesh.shape["data"] == 4
            assert float(jnp.abs(out["w"] - x).max()) == 0.0
        print("OK")
    """)
