"""Puts the checkout root first on ``sys.path``, so ``import bench`` finds
the benchmark package and not this test directory."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p in sys.path:
        sys.path.remove(p)
    sys.path.insert(0, p)


def cell(config: str, traffic: str, chips: int = 1):
    """A cell of configuration ``config`` under mix ``traffic``, whether or
    not ``BENCHMARK.json`` lists it yet."""
    from bench.spec import Cell

    bench = {
        "configs": [{"name": config, "file": f"bench/configs/{config}.json"}],
        "workloads": [{"name": f"{config}.{traffic}", "config": config,
                       "traffic": traffic, "chips": chips}],
        "end_to_end": [],
        "per_layer": [],
    }
    return Cell(f"{config}.{traffic}", bench)
