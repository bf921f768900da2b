"""distributed/elastic.py unit coverage: remesh resolution and resharding.

The elastic primitives are the substrate under both training restarts
(DESIGN.md §6) and the serving fleet's elastic restore (§17,
tests/test_sharded_serving.py) — here they are covered directly: pytrees
round-trip across two fake meshes of different shape without value changes,
and ``remesh_pspecs`` re-resolves a real model's logical axes on both.
Multi-device cases run in a subprocess so the main pytest process keeps its
single-device view (same pattern as tests/test_distributed.py).
"""

import os
import subprocess
import sys
import textwrap

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


def test_reshard_tree_round_trip_across_meshes():
    """A pytree sharded on mesh A lands on mesh B and back, bit-identical,
    and every leaf really carries the target mesh's sharding."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.elastic import reshard_tree
        from repro.launch.mesh import make_mesh
        mesh_a = make_mesh((2, 4), ("data", "model"))
        mesh_b = make_mesh((4, 2), ("data", "model"))
        tree = {
            "w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            "b": jnp.arange(8, dtype=jnp.float32),
            "nested": {"scale": jnp.float32(3.5)},
        }
        specs = {"w": P("data", "model"), "b": P("model"),
                 "nested": {"scale": P()}}
        on_a = reshard_tree(tree, specs, mesh_a)
        on_b = reshard_tree(on_a, specs, mesh_b)
        back = reshard_tree(on_b, specs, mesh_a)
        assert on_b["w"].sharding.mesh.shape["data"] == 4
        assert on_b["b"].sharding.spec == P("model")
        for k in ("w", "b"):
            assert bool((on_b[k] == tree[k]).all()), k
            assert bool((back[k] == tree[k]).all()), k
        assert float(on_b["nested"]["scale"]) == 3.5
        # round trip restores mesh A's layout exactly
        assert back["w"].sharding.mesh.shape["data"] == 2
        print("OK")
    """)


def test_remesh_pspecs_resolves_on_both_meshes():
    """The same model's logical axes resolve to valid specs on two mesh
    shapes; divisibility is respected on each (the elastic restart
    guarantee: any surviving mesh gets legal shardings, no special cases)."""
    _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import ModelConfig
        from repro.distributed.elastic import remesh_pspecs
        from repro.launch.mesh import make_mesh
        from repro.models.model import Model
        cfg = ModelConfig(d_model=32, n_heads=4, head_dim=8, d_ff=64,
                          vocab=96, n_periods=2)
        model = Model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        for mesh_shape in ((2, 4), (4, 2), (1, 8)):
            mesh = make_mesh(mesh_shape, ("data", "model"))
            specs = remesh_pspecs(model, shapes, mesh)
            leaves = jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P))
            assert leaves and all(isinstance(s, P) for s in leaves)
            # every resolved spec divides its tensor's dims on THIS mesh
            def check(spec, shaped):
                for dim, axes in zip(shaped.shape, tuple(spec)):
                    if axes is None:
                        continue
                    axes = (axes,) if isinstance(axes, str) else axes
                    size = 1
                    for a in axes:
                        size *= mesh.shape[a]
                    assert dim % size == 0, (spec, shaped.shape, mesh_shape)
            jax.tree.map(check, specs, shapes,
                         is_leaf=lambda x: isinstance(x, P))
        print("OK")
    """)


def test_reshard_state_moves_params_and_opt():
    """reshard_state: params land under their new-mesh specs, optimizer
    moments follow, values unchanged — the live-migration half of §6."""
    _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed.elastic import remesh_pspecs, reshard_state
        from repro.configs.base import ModelConfig
        from repro.launch.mesh import make_mesh
        from repro.models.model import Model
        cfg = ModelConfig(d_model=32, n_heads=4, head_dim=8, d_ff=64,
                          vocab=96, n_periods=2)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        state = {"params": params,
                 "opt": {"m": jax.tree.map(jnp.zeros_like, params),
                         "count": jnp.int32(7)}}
        mesh_b = make_mesh((4, 2), ("data", "model"))
        specs_b = remesh_pspecs(model, shapes, mesh_b)
        out = reshard_state(state, specs_b, mesh_b)
        flat_in = jax.tree.leaves(state["params"])
        flat_out = jax.tree.leaves(out["params"])
        assert all(bool((a == b).all()) for a, b in zip(flat_in, flat_out))
        assert int(out["opt"]["count"]) == 7
        # at least one big tensor actually sharded over the new mesh
        sharded = [x for x in flat_out
                   if not x.sharding.is_fully_replicated]
        assert sharded, "expected some parameter to shard on the new mesh"
        print("OK")
    """)
