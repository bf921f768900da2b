"""What a cell is made of, found by name from ``BENCHMARK.json``.

Each configuration, traffic mix, generator and metric reader is a file of
its own under ``bench/``; this module finds and loads them, so a new cell,
mix, configuration or per-layer metric is added with files and entries only.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: pathlib.Path, name: str):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


class Cell:
    """One workload: configuration, traffic mix and the metrics it reports."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or benchmark()
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
        self.name = name
        self.workload = cells[name]
        conf = {c["name"]: c for c in bench["configs"]}[self.workload["config"]]
        self.cfg = json.loads((ROOT / conf["file"]).read_text())
        self.mix = json.loads((BENCH / "traffic" / f"{self.workload['traffic']}.json").read_text())
        self.chips = int(self.workload["chips"])
        self.end_to_end = [m for m in bench["end_to_end"] if self._reports(m)]
        self.per_layer = [m for m in bench["per_layer"] if self._reports(m)]

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def generator(self):
        return load_module(BENCH / "generators" / f"{self.mix['generator']}.py",
                           f"bench_gen_{self.mix['generator']}")

    def reference(self):
        return load_module(BENCH / "configs" / f"{self.cfg['reference']}.py",
                           f"bench_ref_{self.cfg['reference']}")


def reader(metric: str):
    """The reader module of a metric: ``read(run) -> float | None``."""
    return load_module(BENCH / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_"))


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise SystemExit(f"device {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]
