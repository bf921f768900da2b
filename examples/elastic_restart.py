"""Elastic scaling demo (DESIGN.md §6): lose a pod, continue on the survivor.

Runs in a subprocess with 8 fake devices: trains on a (2,2,2) pod/data/model
mesh, checkpoints, then restores the SAME checkpoint onto a (1,2,2) mesh
(one pod lost) with re-resolved shardings and continues training — loss
curve continues smoothly because the deterministic pipeline keys batches by
step.

Run: PYTHONPATH=src python examples/elastic_restart.py
"""

import os
import subprocess
import sys
import textwrap

BODY = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models.model import build_model
from repro.train.loop import init_train_state, make_train_step
from repro.train.optimizer import OptConfig
from repro.data.pipeline import DataConfig, make_source
from repro.checkpoint.checkpointer import Checkpointer
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import model_param_pspecs
import tempfile

cfg = get_config("gemma3-1b", smoke=True)
model = build_model(cfg)
opt_cfg = OptConfig(lr=1e-3, total_steps=40, warmup_steps=2)
data = make_source(DataConfig(vocab=cfg.vocab, global_batch=4, seq_len=32, seed=0))
step_fn = jax.jit(make_train_step(model, opt_cfg))

def shard_state(state, mesh):
    pspecs = model_param_pspecs(model, jax.eval_shape(lambda: state["params"]), mesh)
    put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))
    params = jax.tree.map(put, state["params"], pspecs, is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, P))
    return {"params": params, "opt": jax.tree.map(jax.device_put, state["opt"])}

with tempfile.TemporaryDirectory() as d:
    ck = Checkpointer(d)
    mesh_a = make_mesh((2, 2, 2), ("pod", "data", "model"))
    state = init_train_state(model, jax.random.PRNGKey(0), opt_cfg)
    print(f"[pod A+B] training on mesh {dict(mesh_a.shape)}")
    for step in range(10):
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        state, m = step_fn(state, batch)
    print(f"[pod A+B] step 10 loss={float(m['loss']):.4f}")
    ck.save(10, state, blocking=True)

    # ---- pod B dies; restart on the 4-device survivor mesh --------------
    mesh_b = make_mesh((1, 2, 2), ("pod", "data", "model"))
    print(f"[pod A only] restoring ckpt onto mesh {dict(mesh_b.shape)}")
    restored = ck.restore(10, state)
    restored = shard_state(restored, mesh_b)
    for step in range(10, 20):
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        restored, m = step_fn(restored, batch)
    print(f"[pod A only] step 20 loss={float(m['loss']):.4f}")
    print("elastic restart OK: training continued on the degraded mesh")
"""


def main():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    # fake CPU devices; the child never reaches for an accelerator, which
    # the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(BODY)],
                         env=env, cwd=root, text=True)
    raise SystemExit(out.returncode)


if __name__ == "__main__":
    main()
