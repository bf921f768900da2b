"""Record a traced chip window of a one-pool cell as a test fixture.

    python3 tests/bench/record_chip_trace.py --workload <cell> --seed <n> --seconds 4 --out <file>

On a TPU host, from the root of a checkout. One traced run of the cell, as
``bench/run.py --trace 1`` makes it, written as one JSON object: the result
line's per-layer metrics and breakdown, the harness's spans, the reduced
trace (``bench.metrics._trace.Trace``), and what that reduction leaves out:
the pool's counters over the window, the program's own host spans
(``repro.*``) and each device op's scope path (its instruction's
``op_name`` in ``EventEngine.compiled_step_text``). The tests under
``tests/bench/`` read such records on the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.dont_write_bytecode = True

INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', re.M)


def program_spans(log_dir: str, window: tuple) -> list:
    """The program's ``repro.*`` host spans inside the window."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    lo, hi = window
    return [(e.name, e.start_ns, e.duration_ns)
            for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.") and lo <= e.start_ns <= hi]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"needs a TPU; JAX's backend is {jax.default_backend()!r}", file=sys.stderr)
        return 2

    from bench import harness
    from bench.metrics import _trace
    from bench.run import result_line
    from bench.spec import Cell
    from repro.serve.aer import POOL_COUNTERS

    cell = Cell(args.workload)
    if cell.cfg.get("fleet"):
        print("records one-pool cells only", file=sys.stderr)
        return 2
    got: dict = {}

    def hook(system):
        pool = got["pool"] = system.pool
        spans, snapshot = system.spans, harness.snapshot

        def spans_at_open(wrap):  # the harness wraps its spans as the window opens
            got["open"] = pool.counters()
            spans(wrap)

        def snapshot_at_close(s):  # ... and snapshots the pool as it closes
            got["close"] = pool.counters()
            return snapshot(s)

        system.spans = spans_at_open
        harness.snapshot = snapshot_at_close

    load = _trace.load

    def load_with_program_spans(log_dir, device_ids):
        tr = load(log_dir, device_ids)
        got["program"] = program_spans(log_dir, tr.window)
        return tr

    _trace.load = load_with_program_spans
    run = harness.run_cell(cell, args.seed % 2**63, args.seconds, True, T_START, hook=hook)
    line = result_line(run)

    pool = got["pool"]
    inputs = jax.ShapeDtypeStruct(
        (pool.cfg.pool_size, pool.engine.n_clusters, pool.engine.k_tags), pool.carry[1].dtype)
    op_names = dict(INSTRUCTION.findall(pool.engine.compiled_step_text(pool.carry, inputs)))
    traced = {e[0] for evs in run.trace_data.ops.values() for e in evs}
    trace = json.loads(run.trace_data.to_json())
    trace["program"] = got["program"]
    trace["scopes"] = {op: op_names[op] for op in sorted(traced) if op in op_names}
    record = {
        "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
        "steps": run.steps, "window_s": run.window_s,
        "counters": {k: got["close"][k] - got["open"][k] for k in POOL_COUNTERS},
        "spans": dict(run.spans), "device": line["device"],
        "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        "breakdown": line["breakdown"], "compared": line["compared"], "trace": trace,
    }
    with open(args.out, "w") as f:
        json.dump(record, f)
    print(json.dumps({k: record[k] for k in ("workload", "steps", "counters", "metrics",
                                             "compared")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
