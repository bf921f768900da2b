"""Paper §V as a *service*: continuous-batching multi-tenant DVS classification.

Where examples/poker_dvs_cnn.py presents a fixed batch of card flashes,
this example runs the same compiled Table-V network as a server
(serve/aer.py, DESIGN.md §12): a fixed pool of session slots over the
batched event engine, each slot one user's live DVS stream, with sessions
admitted and evicted independently — the slot a finished user vacates is
surgically reset (neuron state, FIFO stats, fabric in-flight events) and
backfilled from the waiting queue the same step, so the fabric never
drains between users.

Per session it reports the majority-rule prediction and latency-to-decision
(steps = ms at dt = 1 ms; paper: <30 ms); aggregate, sessions/s and p50/p99
decision latency.

Run: PYTHONPATH=src python examples/poker_dvs_serve.py
     PYTHONPATH=src python examples/poker_dvs_serve.py --backend fabric --pool 32 --sessions 64
"""

import argparse
import time

import numpy as np

from repro.data.pipeline import DvsStreamConfig, DvsStreamSource
from repro.launch.runtime import enable_compile_cache
from repro.serve.aer import AerServeConfig, AerSessionPool, DvsSession, table_v_models

SUITS = ["diamond(|)", "club(-)", "spade(^)", "heart(v)"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="reference",
                    choices=["reference", "pallas", "fused", "fabric"])
    ap.add_argument("--pool", type=int, default=32)
    ap.add_argument("--sessions", type=int, default=64)
    ap.add_argument("--events-per-step", type=int, default=16)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    enable_compile_cache()

    rng = np.random.default_rng(args.seed)
    # the Table-V network resident twice: 3x3-chip and 2x2-chip placements
    models = table_v_models(rng)
    cc = models["tableV-3x3"]
    pool = AerSessionPool.from_models(
        models, AerServeConfig(pool_size=args.pool), backend=args.backend
    )
    print(f"Table-V network ({cc.tables.n_neurons} neurons, "
          f"{cc.tables.n_clusters} cores) resident twice — 3x3-chip and "
          f"2x2-chip placements ({pool.engine.n_neurons} neurons combined) — "
          f"served via backend={args.backend!r}, pool of {args.pool} slots, "
          f"{args.sessions} sessions")

    names = list(models)
    suits = rng.integers(0, 4, args.sessions)
    sessions = [
        DvsSession(
            i,
            DvsStreamSource(
                DvsStreamConfig(symbol=int(suits[i]),
                                events_per_step=args.events_per_step,
                                seed=args.seed),
                session_id=i,
            ),
            label=int(suits[i]),
            model=names[i % 2],
        )
        for i in range(args.sessions)
    ]
    model_of = {s.session_id: s.model for s in sessions}

    t0 = time.time()
    results = pool.serve(sessions)
    wall = time.time() - t0

    for r in results[: min(8, len(results))]:
        tick = "ok " if r.correct else "MISS"
        print(f"  session {r.session_id:3d}  {SUITS[r.label]:12s} -> "
              f"{SUITS[r.prediction]:12s} {tick} latency {r.latency_steps:2d} ms")
    if len(results) > 8:
        print(f"  ... {len(results) - 8} more")

    dt_ms = pool.engine.params.dt * 1e3
    print(f"\nper-model results (paper: 100% on the 4-suit task, <30 ms):")
    for name in names:
        rs = [r for r in results if model_of[r.session_id] == name]
        acc_m = float(np.mean([r.correct for r in rs]))
        lat_m = np.array([r.latency_steps for r in rs], dtype=np.float64)
        print(f"  {name:12s}  accuracy {acc_m:.0%} over {len(rs)} sessions, "
              f"latency p50 {np.percentile(lat_m, 50) * dt_ms:.0f} ms / "
              f"p99 {np.percentile(lat_m, 99) * dt_ms:.0f} ms")
    acc = float(np.mean([r.correct for r in results]))
    lat = np.array([r.latency_steps for r in results], dtype=np.float64)
    print(f"combined accuracy: {acc:.0%} over {len(results)} sessions")
    print(f"decision latency: p50 {np.percentile(lat, 50) * dt_ms:.0f} ms, "
          f"p99 {np.percentile(lat, 99) * dt_ms:.0f} ms (paper: <30 ms)")
    print(f"throughput: {len(results) / wall:.1f} sessions/s "
          f"({pool.n_steps} engine steps, {wall:.1f}s wall)")
    dropped = sum(r.dropped for r in results)
    linkd = sum(r.link_dropped for r in results)
    print(f"event loss: {dropped} AER-queue drops, {linkd} link-FIFO drops")
    print("pool counters: " + ", ".join(f"{k} {v}" for k, v in pool.counters().items()))


if __name__ == "__main__":
    main()
