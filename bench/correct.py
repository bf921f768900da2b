"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, the
configuration's plain reference (``bench/configs/<reference>.py``) serves
the same sessions from the traffic's own definition, and is compared with
what the timed path produced:

* ``decision_mismatches``: decided sessions (all of them, or a sample drawn
  from the seed that holds the longest) whose prediction, decision step or
  threshold crossing differ from the reference's; sessions in flight at the
  close whose per-class spike counts differ; and sessions due in the window
  that never decided or ended on a fault. Exact: limit 0.
* ``state_gap``: for every session in flight at the close, the neuron state
  that the last step wrote (membrane, adaptation, refractory time and the
  four synapse filters) against the reference's after as many steps: the
  largest gap of a leaf, as a share of that leaf's largest reference value.
* ``shard_share_gap`` (fleet): how far the busiest or idlest shard's share
  of the decided sessions lies from an even share.

Each number is printed beside its limit, from the configuration's
``limits``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

SAMPLE = 1024  # decided sessions replayed per run, at most
LEAVES = ("v", "w", "refrac", "i_syn")


@dataclasses.dataclass
class Readings:
    """What a system under test said of the sessions it was asked about."""

    decisions: dict  # key -> (prediction, steps, decided)
    counts: dict  # key -> per-class counts after its steps
    states: dict  # key -> (v, w, refrac, i_syn)
    failed: int = 0  # sessions due that never decided, or ended on a fault
    shards: list | None = None  # decided sessions per shard (fleet)


def sessions_of(cell, run, snap) -> tuple[list, Readings]:
    """The sessions to replay, and the program's readings of them."""
    cfg = cell.cfg
    traffic = cell.generator().build(cell.mix, cfg["models"], run.seed, run.seconds)
    index = {m: i for i, m in enumerate(cfg["models"])}
    entries, prog = [], Readings({}, {}, {}, failed=run.undecided)

    def entry(key, sid, steps, model, want_state=False):
        sess = traffic.session(sid)
        if sess["model"] != model:
            prog.failed += 1
        entries.append({"key": key, "id": sess["stream_id"], "model": index[sess["model"]],
                        "stream": sess["stream"], "steps": int(steps),
                        "want_state": want_state})

    for state, live in snap:
        for slot, sid, model, steps, counts in live:
            if steps == 0:
                continue
            key = ("state", sid)
            entry(key, sid, steps, model, want_state=True)
            prog.counts[key] = np.asarray(counts, np.float64)
            prog.states[key] = tuple(np.asarray(getattr(state, leaf)[slot]) for leaf in LEAVES)

    decided = list(run.decided)
    if len(decided) > SAMPLE:
        rng = np.random.default_rng([run.seed, 3])
        longest = max(range(len(decided)), key=lambda i: decided[i].result.latency_steps)
        pick = set(rng.choice(len(decided), SAMPLE - 1, replace=False).tolist()) | {longest}
        decided = [decided[i] for i in sorted(pick)]
    for d in decided:
        r = d.result
        if r.error is not None:
            prog.failed += 1
            continue
        key = ("decision", d.sid)
        entry(key, d.sid, r.latency_steps, traffic.session(d.sid)["model"])
        prog.decisions[key] = (int(r.prediction), int(r.latency_steps), bool(r.decided))
    if cfg.get("fleet"):
        per = np.zeros(cfg["fleet"]["n_shards"], int)
        for d in run.decided:
            per[d.shard] += 1
        prog.shards = per.tolist()
    return entries, prog


def numbers(got: Readings, want) -> dict:
    """The compared numbers, ``got`` (a system under test) against ``want``
    (the reference's replay)."""
    mism = got.failed
    for key, dec in got.decisions.items():
        mism += want.decisions.get(key) != dec
    for key, c in got.counts.items():
        mism += key not in want.counts or not np.array_equal(want.counts[key], c)
    out = {"decision_mismatches": float(mism)}
    gaps = {}
    for j, leaf in enumerate(LEAVES):
        keys = [k for k in got.states if k in want.states]
        if not keys:
            continue
        ref = np.stack([np.asarray(want.states[k][j], np.float64) for k in keys])
        prog = np.stack([np.asarray(got.states[k][j], np.float64) for k in keys])
        scale = max(float(np.abs(ref).max()), 1e-30)
        gaps[leaf] = float(np.abs(prog - ref).max()) / scale
    if gaps:
        out["state_gap"] = max(gaps.values())
        out["leaf_gaps"] = gaps
    if got.shards is not None:
        per = np.asarray(got.shards, np.float64)
        share = per / max(per.sum(), 1.0)
        out["shard_share_gap"] = float(np.abs(share - 1.0 / len(per)).max())
    return out


def check(cell, run, snap, pool_meta) -> dict:
    """Replay the run's sessions on the reference; each number beside its
    limit, and whether every one is within it."""
    ref_mod = cell.reference()
    ref = ref_mod.build(cell.cfg)
    lay_n = ref.lay.n
    index = {m: i for i, m in enumerate(cell.cfg["models"])}
    for slabs in pool_meta:  # each model's neurons where the reference has them
        for model, slab in slabs.items():
            if slab.neuron_lo != index[model] * lay_n:
                raise RuntimeError(f"model {model} sits at neuron {slab.neuron_lo}")
    entries, prog = sessions_of(cell, run, snap)
    want = ref_mod.replay(ref, entries)
    got = numbers(prog, want)
    leaf_gaps = got.pop("leaf_gaps", {})
    limits = cell.cfg["limits"]
    compared = {k: (v, limits[k]) for k, v in got.items()}
    ok = all(v <= lim for v, lim in compared.values())
    return {"correct": bool(ok), "compared": compared, "leaf_gaps": leaf_gaps,
            "sessions": len(entries),
            "reference": ref, "entries": entries, "program": prog, "want": want}
