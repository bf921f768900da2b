"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds the program under ``src/``. The cell
(``BENCHMARK.json``) names a configuration (``bench/configs/``) and a
traffic mix (``bench/traffic/``). The run builds the system, warms up every
shape the window uses (``setup_s``), serves the traffic for ``--seconds``,
checks what was served against the configuration's plain reference, and
prints one JSON line last on standard output: the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics from a profiled window. The
numbers compared for ``correct`` are printed beside their limits, last on
standard error and last in the result line.

It exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no libtpu logs outside the checkout
sys.dont_write_bytecode = True


def result_line(run) -> dict:
    from bench import spec
    from bench.metrics import _trace

    cell = run.cell
    metrics = {}
    for m in cell.per_layer if run.trace else cell.end_to_end:
        value = spec.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {k: v for k, v in run.device.items() if k != "ids"}
    out = {
        "correct": run.checks["correct"],
        "attempted": len(run.decided) + run.undecided,
        "failed": int(run.checks["program"].failed),
        "metrics": metrics,
        "device": dev,
    }
    if run.trace:
        tr = run.trace_data
        dev["busy_s"] = _trace.mean_busy_s(tr) or 0.0
        dev["window_s"] = tr.window_s
        out["breakdown"] = _trace.breakdown(tr)
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in run.checks["compared"].items()}
    return out


def report(run) -> None:
    """Lines for the reader of standard error; the compared numbers last."""
    import numpy as np

    from bench.metrics import _latency

    err = sys.stderr
    if run.cell.mix["arrival"] != "backlog":
        late = np.array([(d.released - d.due) * 1e3 for d in run.decided])
        print(f"generator lateness ms: median {_latency.percentile(late, 50)} "
              f"max {late.max(initial=0.0)} over {late.size} sessions; "
              f"{run.window_sessions} due in the window, {run.undecided} never decided",
              file=err)
    print(f"window {run.window_s} s, {run.steps} steps, {len(run.decided)} decided, "
          f"{run.compiles_in_window} compilations in the window, set-up {run.setup_s} s",
          file=err)
    print(f"compared {run.checks['sessions']} sessions against the reference; "
          f"leaf gaps {run.checks['leaf_gaps']}", file=err)
    for k, (v, lim) in run.checks["compared"].items():
        print(f"{k} {v} limit {lim}", file=err)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.spec import Cell

    cell = Cell(args.workload)
    import jax

    if jax.default_backend() != "tpu":
        print(f"bench/run.py needs a TPU; JAX's backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, JAX sees {len(jax.devices())}",
              file=sys.stderr)
        return 2

    from bench import harness

    run = harness.run_cell(cell, args.seed % 2**63, args.seconds, bool(args.trace), T_START)
    line = result_line(run)
    report(run)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
