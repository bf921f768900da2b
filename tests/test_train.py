"""Optimizer, checkpointing, data pipeline, fault-tolerant driver."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import DataConfig, make_source
from repro.train.optimizer import OptConfig, adamw_update, init_opt_state, schedule


def _quadratic_converges(state_dtype):
    cfg = OptConfig(lr=0.1, warmup_steps=5, total_steps=200, weight_decay=0.0,
                    state_dtype=state_dtype)
    params = {"w": jnp.asarray([3.0, -2.0, 5.0])}
    opt = init_opt_state(params, cfg)
    target = jnp.asarray([1.0, 1.0, 1.0])

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    for _ in range(200):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw_update(g, opt, params, cfg)
    return float(loss(params))


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16", "q8"])
def test_adamw_converges(state_dtype):
    assert _quadratic_converges(state_dtype) < 1e-2


def test_schedule_warmup_and_decay():
    cfg = OptConfig(lr=1e-3, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
    lrs = [float(schedule(cfg, jnp.asarray(s))) for s in (0, 5, 10, 50, 100)]
    assert lrs[0] < lrs[1] < lrs[2]
    assert lrs[2] == pytest.approx(1e-3, rel=1e-3)
    assert lrs[3] < lrs[2]
    assert lrs[4] == pytest.approx(1e-4, rel=1e-2)


def test_grad_clipping_bounds_update():
    cfg = OptConfig(lr=1.0, clip_norm=1.0, warmup_steps=0, total_steps=10, weight_decay=0.0)
    params = {"w": jnp.zeros((3,))}
    opt = init_opt_state(params, cfg)
    g = {"w": jnp.asarray([1e6, 1e6, 1e6])}
    new_params, _, metrics = adamw_update(g, opt, params, cfg)
    assert float(metrics["grad_norm"]) > 1e5
    assert float(jnp.abs(new_params["w"]).max()) < 10.0  # clipped


# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip_and_retention(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    for step in (1, 2, 3):
        ck.save(step, jax.tree.map(lambda x: x * step, tree), blocking=True)
    assert ck.steps() == [2, 3]  # retention keeps newest 2
    restored = ck.restore(3, tree)
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.arange(6).reshape(2, 3) * 3)
    assert restored["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_atomicity(tmp_path):
    """A stray .tmp dir (simulated crash) is never listed as a valid step."""
    ck = Checkpointer(str(tmp_path), keep=5)
    ck.save(1, {"a": jnp.ones(3)}, blocking=True)
    os.makedirs(tmp_path / "step_2.tmp")
    assert ck.latest_step() == 1


def test_async_checkpoint_completes(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(7, {"a": jnp.ones(3)})
    ck.wait()
    assert ck.latest_step() == 7


# ---------------------------------------------------------------------------
def test_data_pipeline_deterministic_and_resumable():
    cfg = DataConfig(vocab=100, global_batch=4, seq_len=8, seed=3)
    src1 = make_source(cfg)
    src2 = make_source(cfg)
    b5a = src1.batch(5)
    # consume different steps first — batch(5) must not depend on history
    src2.batch(0), src2.batch(17)
    b5b = src2.batch(5)
    np.testing.assert_array_equal(b5a["tokens"], b5b["tokens"])
    assert b5a["tokens"].shape == (4, 8)
    assert (b5a["labels"][:, :-1] == b5a["tokens"][:, 1:]).all()


def test_data_pipeline_host_sharding():
    cfg = DataConfig(vocab=100, global_batch=8, seq_len=4, seed=0)
    h0 = make_source(cfg, host_id=0, n_hosts=2).batch(0)
    h1 = make_source(cfg, host_id=1, n_hosts=2).batch(0)
    assert h0["tokens"].shape == (4, 4)
    assert not (h0["tokens"] == h1["tokens"]).all()


def test_file_source_roundtrip(tmp_path):
    toks = np.arange(1000, dtype=np.uint16) % 50
    path = tmp_path / "toks.bin"
    toks.tofile(path)
    cfg = DataConfig(vocab=50, global_batch=2, seq_len=9, path=str(path))
    src = make_source(cfg)
    b = src.batch(0)
    np.testing.assert_array_equal(b["tokens"][0], toks[:9].astype(np.int32))


# ---------------------------------------------------------------------------
def test_supervisor_restart_after_injected_failure(tmp_path):
    """End-to-end fault tolerance: crash at step 15, resume from ckpt 10."""
    # the child trains on the CPU: an accelerator belongs to one process
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "gemma3-1b", "--smoke",
         "--steps", "20", "--batch", "2", "--seq", "16", "--ckpt-dir", str(tmp_path),
         "--ckpt-every", "10", "--log-every", "20", "--fail-at", "15"],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "failure #1" in out.stdout
    assert "resumed from step 10" in out.stdout
    assert "training complete" in out.stdout
