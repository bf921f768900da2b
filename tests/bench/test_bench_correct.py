"""``correct`` holds a sound run and catches a broken one.

Each run goes through the whole harness (bench/harness.py) on the CPU, past
its look for a chip, at a pool of a few slots: the program's timed path
against the plain reference. A sound run is correct; the control (the
reference itself in bfloat16, put in the program's place) and each fault
planted in the timed path (bench/faults.py) are not.
"""

import json
import os
import subprocess
import sys
import time

import _bench_path
import numpy as np
import pytest

from bench import control, faults, harness

SLOTS, SECONDS = 4, 1.5


@pytest.fixture(scope="module")
def models(monkeypatch_module):
    built = {}

    def cached(cfg):
        key = (cfg["network_seed"], tuple(cfg["models"]))
        if key not in built:
            from repro.serve.aer import table_v_models

            allm = table_v_models(np.random.default_rng(cfg["network_seed"]))
            built[key] = {m: allm[m] for m in cfg["models"]}
        return built[key]

    monkeypatch_module.setattr(harness, "resident_models", cached)
    return cached


@pytest.fixture(scope="module")
def monkeypatch_module():
    with pytest.MonkeyPatch.context() as mp:
        yield mp


def _run(name, seed=2**31 + 5, hook=None):
    cell = _bench_path.cell(*name.split("."))
    cell.cfg["pool_size"] = SLOTS
    return cell, harness.run_cell(cell, seed, SECONDS, False, time.perf_counter(), hook=hook)


@pytest.mark.parametrize("name", ["tablev-2res-fused.backlog", "tablev-2res-fused.poisson",
                                  "tablev-3x3-fabric.backlog"])
def test_sound_run_is_correct_and_control_is_not(models, name):
    cell, run = _run(name)
    assert run.checks["correct"], run.checks["compared"]
    assert run.checks["sessions"] > SLOTS
    low = control.control_numbers(cell, run.checks)
    limits = cell.cfg["limits"]
    assert any(low[k] > limits[k] for k in limits if k in low), low


@pytest.mark.parametrize("fault", ["stale_state", "half_batch", "altered_answer"])
def test_fault_in_the_timed_path_is_caught(models, fault):
    _, run = _run("tablev-2res-fused.backlog", hook=faults.FAULTS[fault])
    assert not run.checks["correct"], run.checks["compared"]


FLEET = """
import json, sys, time
sys.path[:0] = [{root!r}, {root!r} + "/src"]
from bench import harness, faults
from bench.spec import Cell
out = {{}}
for fault in (None, "drop_shard"):
    bench = {{"configs": [{{"name": "f", "file": "bench/configs/tablev-2res-fused-fleet4.json"}}],
             "workloads": [{{"name": "f.backlog", "config": "f", "traffic": "backlog", "chips": 4}}],
             "end_to_end": [], "per_layer": []}}
    cell = Cell("f.backlog", bench)
    cell.cfg["pool_size"] = {slots}
    cell.cfg["fleet"]["n_shards"] = 2
    run = harness.run_cell(cell, 7, {seconds}, False, time.perf_counter(),
                           hook=None if fault is None else faults.FAULTS[fault])
    out[str(fault)] = [run.checks["correct"], {{k: v for k, (v, _) in run.checks["compared"].items()}}]
print(json.dumps(out))
"""


def test_fleet_sound_and_with_a_shard_left_out():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    code = FLEET.format(root=str(_bench_path.ROOT), slots=SLOTS, seconds=3.0)
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    ok, nums = out["None"]
    assert ok, nums
    bad, nums = out["drop_shard"]
    assert not bad and nums["shard_share_gap"] > 0.4, nums
