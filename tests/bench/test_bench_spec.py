"""``BENCHMARK.json`` is well formed, every piece it names exists, and the
entry point refuses to run without a TPU."""

import json
import re
import shutil
import subprocess
import sys

import _bench_path
import pytest

ROOT = _bench_path.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRIC_KEYS = {"name", "unit", "better", "source"}


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)
    assert len(BENCH["command"]) <= 32


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]] + [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in _metrics()]
    names += [w["config"] for w in BENCH["workloads"]] + [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in _metrics():
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for group in (BENCH["configs"], BENCH["workloads"], _metrics()):
        assert len({x["name"] for x in group}) == len(group)


def test_every_cell_has_its_configuration_and_mix():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (ROOT / configs[w["config"]]["file"]).is_file()
        mix = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "generators" / f"{mix['generator']}.py").is_file()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / "bench" / "configs" / f"{cfg['reference']}.py").is_file()
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_at_most_half_the_cells_ask_for_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_metrics_have_readers_and_move_reported_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) <= METRIC_KEYS | {"bound", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")

    def reporting(metric):
        return set(metric.get("workloads", cells))

    for m in BENCH["per_layer"]:
        assert set(m) <= METRIC_KEYS | {"layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert reporting(m) <= reporting(e2e[m["moves"]]), m["name"]
    for m in _metrics():
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file(), m["name"]
        assert reporting(m) <= cells
    for c in cells:  # setup_s, another end-to-end metric and a per-layer one
        assert sum(c in reporting(m) for m in BENCH["end_to_end"]) >= 2
        assert any(c in reporting(m) for m in BENCH["per_layer"])


def test_rooflines_are_named_for_their_kernel():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def _run(args, cwd):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(cwd)}
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


ARGS = ["--workload", "tablev-2res-fused.backlog", "--seed", "3", "--seconds", "1", "--trace", "0"]


def _no_result(proc):
    return not any(line.lstrip().startswith("{") for line in proc.stdout.splitlines())


def test_no_tpu_no_result():
    proc = _run(ARGS, ROOT)
    assert proc.returncode != 0 and _no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_knee_sweep_needs_a_tpu():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(ROOT)}
    proc = subprocess.run([sys.executable, "bench/knee.py", "--workload", "tablev-2res-fused.poisson",
                           "--rates", "10", "--seconds", "1"], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and _no_result(proc)
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("trace", ["0", "1"])
def test_benchmark_files_alone_do_not_run(tmp_path, trace):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(ARGS[:-1] + [trace], tmp_path)
    assert proc.returncode != 0 and _no_result(proc)
