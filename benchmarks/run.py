"""Benchmark driver — one module per paper table/figure.

  Table II  -> routing_throughput   Table III + Fig 11 -> energy
  Table IV  -> comparison           Table V + Fig 12   -> cnn_poker
  Fig 13 + §II headline -> memory_scaling
  compiler v2 placement/tag-reuse (DESIGN.md §13) -> routing_throughput
  (``compiler_*`` rows: measured mean hops + link drops + sessions/s,
  optimized vs default placement, and the v2-vs-v1 tag spend)
  beyond-paper (MoE dispatch mapping) -> dispatch
  beyond-paper (multi-tenant AER serving, DESIGN.md §12) -> serving
  §Roofline artifacts -> roofline

Prints ``name,us_per_call,derived`` CSV and writes the routing/dispatch rows
to ``BENCH_routing.json`` (machine-readable perf trajectory across PRs).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

from repro.launch.runtime import enable_compile_cache, fake_host_devices

# modules whose rows land in BENCH_routing.json (the event-delivery hot path)
_ROUTING_MODULES = ("routing_throughput", "dispatch", "serving")


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--devices",
        type=int,
        default=None,
        metavar="N",
        help="fake N host-platform devices (sets "
        "--xla_force_host_platform_device_count before jax imports; the "
        "sharded serving rows then run shards on disjoint devices). "
        "CPU only: refused when the backend is an accelerator",
    )
    ap.add_argument(
        "--only",
        default=None,
        metavar="MOD[,MOD...]",
        help="run only these benchmark modules (e.g. 'serving'); "
        "BENCH_routing.json is not rewritten unless BENCH_ROUTING_JSON "
        "is set (a partial sweep must not clobber the full trajectory)",
    )
    args = ap.parse_args(argv)
    if args.devices is not None:
        fake_host_devices(args.devices)
    enable_compile_cache()
    _run_all(args.only.split(",") if args.only else None)


def _run_all(only: list[str] | None = None) -> None:
    from benchmarks import (
        cnn_poker,
        comparison,
        dispatch,
        energy,
        memory_scaling,
        roofline,
        routing_throughput,
        serving,
    )

    modules = [
        ("memory_scaling", memory_scaling),
        ("routing_throughput", routing_throughput),
        ("energy", energy),
        ("comparison", comparison),
        ("cnn_poker", cnn_poker),
        ("dispatch", dispatch),
        ("serving", serving),
        ("roofline", roofline),
    ]
    if only is not None:
        unknown = set(only) - {name for name, _ in modules}
        if unknown:
            raise SystemExit(f"unknown benchmark modules: {sorted(unknown)}")
        modules = [(n, m) for n, m in modules if n in only]
    print("name,us_per_call,derived")
    failed = 0
    failed_routing = False
    routing_rows: list[dict] = []
    for name, mod in modules:
        try:
            for row, us, derived in mod.run():
                print(f"{row},{us:.1f},{derived}")
                if name in _ROUTING_MODULES:
                    routing_rows.append(
                        {"module": name, "name": row, "us_per_call": round(us, 2),
                         "derived": derived}
                    )
        except Exception:  # noqa: BLE001 — report per-bench failures, keep going
            failed += 1
            failed_routing |= name in _ROUTING_MODULES
            print(f"{name},nan,FAILED", file=sys.stderr)
            traceback.print_exc()
    json_path = os.environ.get("BENCH_ROUTING_JSON", "BENCH_routing.json")
    if failed_routing:  # keep the last good trajectory instead of clobbering it
        print(f"routing benchmark failed; NOT rewriting {json_path}", file=sys.stderr)
    elif only is not None and "BENCH_ROUTING_JSON" not in os.environ:
        # a partial sweep must not clobber the committed full trajectory
        print(f"--only given; NOT rewriting {json_path}", file=sys.stderr)
    else:
        with open(json_path, "w") as f:
            json.dump({"rows": routing_rows}, f, indent=2)
            f.write("\n")
        print(f"wrote {len(routing_rows)} routing rows to {json_path}", file=sys.stderr)
    if failed:
        raise SystemExit(f"{failed} benchmark modules failed")


if __name__ == "__main__":
    main()
