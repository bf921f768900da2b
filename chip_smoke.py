"""On-chip smoke test of the DVS serving path (serve/aer.py, DESIGN.md §12).

Serves the paper's Table-V CNN at its real widths (256 neurons per core,
K = 1024 tags, 64 CAM words, 16 SRAM entries), resident twice, through
``AerSessionPool.from_models(...).serve(...)`` on one TPU, and checks:

  * every dispatch backend (reference, pallas, fused, fabric) classifies
    every session correctly, and the backends agree per session;
  * the pallas, fused and fabric pool steps lower to the compiled Pallas
    kernels (``tpu_custom_call``), not to their jnp references;
  * a few engine steps match the dense oracle (``dense_reference_step``);
  * the donated carry survives eviction, backfill, checkpoint and restore.

With ``--chips 4`` it runs only the sharded fleet (serve/sharded.py,
DESIGN.md §17) over four chips against one single-chip pool.

Run from the checkout root on a TPU host:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four-chip fleet

It exits non-zero without a result line when JAX finds no TPU. Its last
line of standard output is one JSON object naming the device. Times on
earlier lines are smoke timings, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

# the smoke writes nothing under the checkout but the compile cache
# (src/repro/launch/runtime.py): no libtpu logs, no bytecode files
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.dont_write_bytecode = True
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

BACKENDS = ("reference", "pallas", "fused", "fabric")
KERNEL_BACKENDS = ("pallas", "fused", "fabric")
POOL = 32
SESSIONS = 64
ORACLE_STEPS = 4
CHECKPOINT_AFTER = 5


def make_sessions(models, n: int, seed: int):
    """``n`` seeded DVS sessions alternating over the resident models."""
    from repro.data.pipeline import DvsStreamConfig, DvsStreamSource
    from repro.serve.aer import DvsSession

    names = list(models)
    suits = np.random.default_rng([seed, 1]).integers(0, 4, n)
    return [
        DvsSession(
            i,
            DvsStreamSource(
                DvsStreamConfig(symbol=int(suits[i]), seed=seed), session_id=i
            ),
            label=int(suits[i]),
            model=names[i % len(names)],
        )
        for i in range(n)
    ]


def decisions(results) -> dict[int, tuple[int, int]]:
    return {r.session_id: (r.prediction, r.latency_steps) for r in results}


def check_served(name: str, sessions, results) -> None:
    """Every session finished without error, and every model hit 100%."""
    got = {r.session_id: r for r in results}
    if set(got) != {s.session_id for s in sessions}:
        raise AssertionError(f"{name}: served {sorted(got)} of {len(sessions)}")
    for model in sorted({s.model for s in sessions}):
        rs = [got[s.session_id] for s in sessions if s.model == model]
        errors = [r.error for r in rs if r.error is not None]
        if errors:
            raise AssertionError(f"{name}/{model}: session faults {errors[:3]}")
        acc = float(np.mean([r.correct for r in rs]))
        if acc != 1.0:
            raise AssertionError(f"{name}/{model}: accuracy {acc:.3f} != 1.0")
        lat = [r.latency_steps for r in rs]
        print(f"  {name:10s} {model}: accuracy 100% over {len(rs)} sessions, "
              f"decision latency {min(lat)}-{max(lat)} steps")


def lowered_step_text(pool) -> str:
    zero = jnp.zeros(
        (pool.cfg.pool_size, pool.engine.n_clusters, pool.engine.k_tags),
        jnp.float32,
    )
    return jax.jit(pool.engine.step).lower(pool.carry, zero).as_text()


def serve_phase(models, cfg, seed: int) -> tuple[dict, dict]:
    """Serve the same sessions on every backend.

    Returns each run's pool (drained, so ready for new sessions) and its
    per-session decisions.
    """
    from repro.serve.aer import AerSessionPool

    runs = [(b, b, None) for b in BACKENDS]
    # the roll-carried fabric path is pure jnp: the reference the ring
    # kernel's arrival steps are held to (the fabric models mesh delays,
    # so its latencies legitimately differ from the delay-free backends)
    runs.append(("fabric-roll", "fabric", {"ring": False}))
    pools, got = {}, {}
    for name, backend, fabric_options in runs:
        pool = AerSessionPool.from_models(
            models, cfg, backend=backend, fabric_options=fabric_options
        )
        has_kernel = "tpu_custom_call" in lowered_step_text(pool)
        if has_kernel != (name in KERNEL_BACKENDS):
            raise AssertionError(
                f"{name}: compiled Pallas kernel in the pool step = {has_kernel}"
            )
        sessions = make_sessions(models, SESSIONS, seed)
        t0 = time.perf_counter()
        results = pool.serve(sessions)
        wall = time.perf_counter() - t0
        print(f"{name}: {pool.n_steps} pool steps, smoke timing {wall:.3f} s "
              f"wall incl. compile (not a benchmark number)")
        check_served(name, sessions, results)
        pools[name], got[name] = pool, decisions(results)
    for name in ("pallas", "fused"):
        if got[name] != got["reference"]:
            raise AssertionError(f"{name} decisions differ from reference")
    if got["fabric"] != got["fabric-roll"]:
        raise AssertionError("fabric ring kernel differs from the roll path")
    preds = {n: {s: d[0] for s, d in g.items()} for n, g in got.items()}
    if any(p != preds["reference"] for p in preds.values()):
        raise AssertionError("predictions differ across backends")
    print(f"decisions agree: reference == pallas == fused, fabric == "
          f"fabric-roll, predictions equal on all backends")
    return pools, got


def oracle_phase(pools, models, seed: int) -> None:
    """Teacher-forced engine steps against the dense float32 oracle.

    From the pool's live carry, each step's next neuron state is computed
    twice: by the backend's two-stage delivery (the pool step) and by
    ``dense_reference_step`` on the dense ``[N, N, 4]`` connectivity, with
    the external input's drive from the reference stage 2. The fabric
    backends are held to the roll path instead: they model mesh delays,
    which the dense oracle does not.
    """
    from repro.core.event_engine import dense_reference_step, dense_weights_from_tables
    from repro.core.two_stage import stage2_cam_match

    for backend in ("reference", "pallas", "fused"):
        pool = pools[backend]
        eng = pool.engine
        combined, _ = pool.registry.combined()
        dense_w = jnp.asarray(dense_weights_from_tables(combined))
        cam_tag, cam_syn = jnp.asarray(combined.cam_tag), jnp.asarray(combined.cam_syn)

        @jax.jit
        def oracle(dense_w, state, prev, inp):
            ext = stage2_cam_match(inp, cam_tag, cam_syn, eng.cluster_size)
            return dense_reference_step(
                dense_w, prev, state, eng.params, external_drive=ext
            )

        for sess in make_sessions(models, pool.cfg.pool_size, seed + 1):
            pool.admit(sess)
        worst = 0.0
        for _ in range(ORACLE_STEPS):
            inp = pool.gather_inputs()
            # read the oracle before the step: the step donates the carry
            want_state, want_spikes = jax.device_get(
                oracle(dense_w, pool.carry[0], pool.carry[1], inp)
            )
            pool.carry, out = eng.step(pool.carry, inp)
            spikes = pool.finish_step(out)
            state = jax.device_get(pool.carry[0])
            np.testing.assert_array_equal(spikes, want_spikes)
            for leaf in ("v", "i_syn"):
                got, want = getattr(state, leaf), getattr(want_state, leaf)
                np.testing.assert_allclose(
                    got, want, rtol=1e-5, atol=1e-6, err_msg=f"{backend}: {leaf}"
                )
                worst = max(worst, float(np.max(np.abs(got - want))))
        print(f"oracle {backend}: {ORACLE_STEPS} steps match "
              f"dense_reference_step (max |diff| {worst:.3g})")


def check_donated(carry) -> None:
    """The step consumed its input carry: membrane state and spikes, whose
    buffers the compiled step reuses for its outputs."""
    if not (carry[0].v.is_deleted() and carry[1].is_deleted()):
        raise AssertionError("the pool step did not donate its carry")


def donation_phase(pool, models, seed: int, served: dict) -> None:
    """Donated carry through step, checkpoint and restore.

    ``pool`` is the drained fabric pool of the serve phase, whose evictions
    and backfills already ran on donated carries; ``served`` its decisions.
    The serve phase's first wave is admitted again, at another ring phase.
    """
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.serve.aer import AerSessionPool

    cfg = pool.cfg
    for sess in make_sessions(models, SESSIONS, seed)[: cfg.pool_size]:
        pool.admit(sess)
    before = pool.carry
    pool.step()
    check_donated(before)
    for _ in range(CHECKPOINT_AFTER - 1):
        pool.step()
    with tempfile.TemporaryDirectory() as ckdir:
        ck = Checkpointer(ckdir)
        pool.checkpoint(ck, blocking=True)
        first = next(iter(models.values()))
        restored = AerSessionPool.restore(
            first, pool.engine, cfg, ck, models=models
        )
    a = decisions(pool.serve([]))
    b = decisions(restored.serve([]))
    if a != b:
        raise AssertionError("restored pool diverged from the original")
    want = {s: served[s] for s in a}
    if a != want:
        raise AssertionError("checkpointed run differs from the serve phase")
    print(f"donation: carry donated; {len(a)} sessions checkpointed at step "
          f"{CHECKPOINT_AFTER}, restored and finished identically")


def fleet_phase(models, seed: int) -> None:
    """Four one-chip shards against one single-chip pool, same sessions."""
    from repro.serve.aer import AerServeConfig, AerSessionPool
    from repro.serve.sharded import ShardConfig, ShardedSessionPool

    first = next(iter(models.values()))
    fleet = ShardedSessionPool(
        first, AerServeConfig(pool_size=POOL // 4), ShardConfig(n_shards=4),
        models=models,
    )
    shard_devices = [
        tuple(d.id for d in p.engine.mesh.devices.flat) for p in fleet.pools
    ]
    flat = [d for devs in shard_devices for d in devs]
    if len(set(flat)) != len(flat) or len(flat) != 4:
        raise AssertionError(f"shards share devices: {shard_devices}")
    print(f"fleet: shard devices {shard_devices}")
    sessions = make_sessions(models, SESSIONS, seed)
    t0 = time.perf_counter()
    res_fleet = fleet.serve(sessions)
    wall = time.perf_counter() - t0
    print(f"fleet: {fleet.n_steps} steps, smoke timing {wall:.3f} s wall "
          "incl. compile (not a benchmark number)")
    check_served("fleet", sessions, res_fleet)

    solo = AerSessionPool.from_models(models, AerServeConfig(pool_size=POOL))
    sessions = make_sessions(models, SESSIONS, seed)
    res_solo = solo.serve(sessions)
    check_served("one-chip", sessions, res_solo)
    if decisions(res_fleet) != decisions(res_solo):
        raise AssertionError("fleet decisions differ from the one-chip pool")
    print(f"fleet == one-chip pool on all {len(res_fleet)} sessions")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX's backend is {jax.default_backend()!r}"
        )
    devices = jax.devices()
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} but JAX sees {len(devices)} devices")

    from repro.launch.runtime import enable_compile_cache
    from repro.serve.aer import AerServeConfig, table_v_models

    print(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    models = table_v_models(np.random.default_rng(args.seed))
    print(f"setup: Table-V readout tuned, 2 residents, smoke timing "
          f"{time.perf_counter() - t0:.3f} s")
    if args.chips == 4:
        fleet_phase(models, args.seed)
    else:
        cfg = AerServeConfig(pool_size=POOL)
        pools, served = serve_phase(models, cfg, args.seed)
        oracle_phase(pools, models, args.seed)
        donation_phase(pools["fabric"], models, args.seed, served["fabric"])
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))


if __name__ == "__main__":
    main()
