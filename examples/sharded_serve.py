"""Multi-host elastic serving on the compiled Table-V network.

The fleet layer over examples/poker_dvs_serve.py (serve/sharded.py,
DESIGN.md §17): serving capacity is partitioned into shards, each an
independent session pool over its own device mesh, with

  * admission control — sessions route to the least-loaded shard by the
    compiler's traffic model, behind bounded waiting queues;
  * live migration — mid-flight tenants move between shards (the demo
    drains a shard for "maintenance" while its users keep their state);
  * elastic restart — the fleet checkpoints atomically, one shard is
    killed mid-serve, and its tenants recover from the checkpoint onto
    the survivors, finishing bit-exactly as if nothing had died.

Run: PYTHONPATH=src python examples/sharded_serve.py
     PYTHONPATH=src python examples/sharded_serve.py --shards 4 --sessions 24
     PYTHONPATH=src python examples/sharded_serve.py --devices 4 --backend fabric

``--devices N`` fakes N host devices (must be set before jax initializes),
giving each shard a disjoint device set as on a real multi-host fleet. It
is refused on an accelerator, where the shards take the real devices.
"""

import argparse
import tempfile
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--pool", type=int, default=4, help="slots per shard")
    ap.add_argument("--sessions", type=int, default=16)
    ap.add_argument("--backend", default="fabric",
                    choices=["reference", "fused", "fabric"])
    ap.add_argument("--devices", type=int, default=None,
                    help="fake N host devices (shards get disjoint sets; "
                    "CPU only)")
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args()

    from repro.launch.runtime import enable_compile_cache, fake_host_devices

    if args.devices is not None:
        fake_host_devices(args.devices)
    enable_compile_cache()

    import numpy as np

    from repro.checkpoint.checkpointer import Checkpointer
    from repro.core.cnn import compile_poker_cnn
    from repro.data.pipeline import DvsStreamConfig, DvsStreamSource
    from repro.serve.aer import AerServeConfig, DvsSession
    from repro.serve.sharded import ShardConfig, ShardedSessionPool

    suits = ["diamond(|)", "club(-)", "spade(^)", "heart(v)"]
    cc = compile_poker_cnn()
    rng = np.random.default_rng(args.seed)

    def session(i):
        sym = int(rng.integers(0, 4))
        return DvsSession(
            i,
            DvsStreamSource(
                DvsStreamConfig(symbol=sym, events_per_step=16, seed=args.seed),
                session_id=i,
            ),
            label=sym,
        )

    def fleet_():
        return ShardedSessionPool(
            cc,
            AerServeConfig(pool_size=args.pool, max_steps=60),
            ShardConfig(n_shards=args.shards, queue_depth=2 * args.pool,
                        backend=args.backend),
        )

    # -- sustained load through the fleet -----------------------------------
    fleet = fleet_()
    t0 = time.perf_counter()
    results = fleet.serve([session(i) for i in range(args.sessions)])
    wall = time.perf_counter() - t0
    acc = float(np.mean([r.correct for r in results]))
    lat = np.array([r.latency_steps for r in results], dtype=np.float64)
    print(f"fleet: {args.shards} shards x {args.pool} slots, "
          f"backend={args.backend}")
    print(f"  {len(results)} sessions in {wall:.2f}s "
          f"({len(results) / wall:.1f} sess/s), accuracy {acc:.2f}, "
          f"p50 latency {np.percentile(lat, 50):.0f} steps")
    stats = fleet.fleet_stats()
    if stats is not None and stats.delivered is not None:
        print(f"  fleet last-step delivery: {int(stats.delivered)} events, "
              f"{int(stats.link_dropped or 0)} link drops")
    print("  fleet counters: " + ", ".join(f"{k} {v}" for k, v in fleet.counters().items()))

    # -- live migration: drain a shard under load ---------------------------
    # one tenant per shard, so the rest of the fleet always has room
    fleet = fleet_()
    for i in range(args.shards):
        fleet.submit(session(100 + i))
    for _ in range(5):
        fleet.step()
    moved = fleet.drain_shard(0)
    print(f"drained shard 0 under load: {moved} tenants migrated mid-flight "
          f"(occupancy now {fleet.occupancy()})")
    done = {r.session_id for r in fleet.serve([])}
    print(f"  all {len(done)} drained tenants finished on the other shards")

    # -- elastic restart: kill a shard, recover from the checkpoint ---------
    fleet = fleet_()
    for i in range(args.shards):
        fleet.submit(session(200 + i))
    for _ in range(3):
        fleet.step()
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        fleet.checkpoint(ck, blocking=True)
        fleet.step()
        victim = args.shards - 1
        held = [s.session_id for s in fleet.pools[victim].slots
                if s is not None]
        fleet.kill_shard(victim)
        n = fleet.recover_shard(ck, victim)
        print(f"killed shard {victim} (held sessions {held}); recovered "
              f"{n} tenants from the checkpoint onto the survivors")
    res = {r.session_id: r for r in fleet.serve([])}
    ok = all(res[sid].prediction is not None for sid in held)
    print(f"  recovered tenants finished: {ok} "
          f"(deterministic replay -> results match an undisturbed run)")
    for sid in held:
        r = res[sid]
        mark = "+" if r.correct else "-"
        print(f"    session {sid}: predicted {suits[r.prediction]} "
              f"[{mark}] in {r.latency_steps} steps")


if __name__ == "__main__":
    main()
