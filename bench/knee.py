"""Find the knee of an open-loop cell: the highest Poisson arrival rate whose
admission queue does not grow over a window.

    python3 bench/knee.py --workload <poisson cell> --rates 15,20,25,30 --seconds 20

One process builds the cell's system once, then serves each rate in turn
(from an empty pool, after a lead of the mix's ``lead_s``) and prints one
JSON line per rate: the rate offered and completed, the admission queue at
the window's open and close, and the decision latency.
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


def summary(rate: float, seconds: float, steps: int, completed: int, queue_open: int,
            queue_close: int, latency_ms) -> dict:
    """One rate's line: what was offered and completed, whether the
    admission queue grew over the window, and the decision latency over
    every session decided in it."""
    from bench.metrics import _latency

    return {
        "rate_per_s": rate, "completed_per_s": completed / seconds,
        "queue_open": queue_open, "queue_close": queue_close,
        "queue_grew": queue_close > queue_open, "steps": steps,
        "step_ms": seconds / max(steps, 1) * 1e3,
        "decision_ms_p50": _latency.percentile(latency_ms, 50),
        "decision_ms_p95": _latency.percentile(latency_ms, 95),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    if jax.default_backend() != "tpu":
        print(f"bench/knee.py needs a TPU; JAX's backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 2

    from bench import harness
    from bench.spec import Cell
    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()
    cell = Cell(args.workload)
    cfg = cell.cfg
    system = harness.build_system(cfg, harness.resident_models(cfg))
    system.step()
    base = 0
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.mix, rate_per_s=rate)
        traffic = cell.generator().build(mix, cfg["models"], args.seed, args.seconds)
        drv = harness.Driver(system, traffic, harness.program_session)
        drv.next_id = base
        traffic.due = np.concatenate([np.full(base, -1e9), traffic.due])
        drv.t0 = time.perf_counter() + float(mix["lead_s"])
        queue_open = None
        steps = 0
        while True:
            now = time.perf_counter()
            if now >= drv.t0 and queue_open is None:
                queue_open = len(drv.pending)
                drv.done.clear()
                steps = 0
            if now >= drv.t0 + args.seconds:
                break
            drv.release(now)
            drv.iterate()
            steps += 1
        lat = np.array([(t - drv.info[sid][0]) * 1e3 for sid, t, _, _ in drv.done
                        if drv.info[sid][0] >= 0])
        print(json.dumps(summary(rate, args.seconds, steps, len(drv.done), queue_open,
                                 len(drv.pending), lat)), flush=True)
        # drain before the next rate
        drv.pending.clear()
        while any(p.occupied for p in system.pools):
            drv.iterate()
        base = drv.next_id
    return 0


if __name__ == "__main__":
    sys.exit(main())
