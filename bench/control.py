"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 4 [--fault <name>]

For each seed, in one process: a run of the cell at its own size and load
(``bench/harness.py``), whose compared numbers are the program's readings;
then the control, the reference put in the program's place and computed in
bfloat16, the precision below the configuration's float32, whose numbers
against the float32 reference are the control's readings. With ``--fault``
a fault of ``bench/faults.py`` is planted in the timed path instead, and
its readings are the program's. One JSON line per seed on standard output.
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
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.dont_write_bytecode = True


def control_numbers(cell, checks) -> dict:
    import jax.numpy as jnp

    from bench import correct

    ref_mod = cell.reference()
    low = ref_mod.replay(checks["reference"], checks["entries"], dtype=jnp.bfloat16)
    got = correct.Readings(
        {k: low.decisions.get(k) for k in checks["program"].decisions},
        {k: low.counts.get(k) for k in checks["program"].counts if k in low.counts},
        {k: low.states[k] for k in checks["program"].states if k in low.states},
    )
    return correct.numbers(got, checks["want"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    from bench import faults, harness
    from bench.spec import Cell

    import jax

    for seed in [int(s) for s in args.seeds.split(",")]:
        cell = Cell(args.workload)
        hook = None if args.fault is None else faults.FAULTS[args.fault]
        t0 = time.perf_counter()
        run = harness.run_cell(cell, seed, args.seconds, False, t0, hook=hook)
        line = {
            "workload": cell.name, "seed": seed, "fault": args.fault,
            "device": jax.devices()[0].device_kind,
            "sessions": run.checks["sessions"], "decided": len(run.decided),
            "program": {k: v for k, (v, _) in run.checks["compared"].items()},
            "program_leaf_gaps": run.checks["leaf_gaps"],
        }
        if args.fault is None:
            t1 = time.perf_counter()
            line["control"] = control_numbers(cell, run.checks)
            line["control_s"] = time.perf_counter() - t1
        line["run_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
