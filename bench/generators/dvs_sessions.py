"""DVS session traffic: suit flashes held to a sensor, one tenant each.

A mix file gives the stream parameters (sensor size, jitter), the sensor
event rates (``events_per_step``: one rate per 1 ms step, or a list of them
that the sessions share equally), the share of each resident model, and the
arrivals:

* ``backlog``: a closed loop; an unbounded queue refills every freed slot.
  While warming up, the pool is filled over ``fill_steps`` steps, so that
  the slots' ages are spread as in a steady state when the window opens;
* ``poisson``: an open loop at ``rate_per_s``, starting ``lead_s`` before
  the window so that the window opens on a pool in steady state.

Every seed gets the same work in another order. Sessions come in blocks
that hold every (suit, model, event rate) in the mix's shares once; block
``b`` holds streams ``b * len(block)`` onwards, whatever the seed, and the
seed orders each block's sessions and permutes one fixed set of Poisson
gaps (from ``schedule_seed``). So any run serves whole blocks but for the
last, and a stream's events at step ``t`` are a pure function of
(``stream_seed``, stream id, t).
"""

from __future__ import annotations

import numpy as np


class Traffic:
    def __init__(self, mix: dict, models: list[str], seed: int, horizon_s: float):
        self.mix = mix
        self.seed = int(seed)
        weights = mix["model_weights"]
        present = [m for m in weights if m in models]
        if not present:
            raise ValueError(f"mix names none of the resident models {models}")
        unit = [m for m in present for _ in range(int(weights[m]))]
        rates = mix["events_per_step"]
        rates = rates if isinstance(rates, list) else [rates]
        self.block = [(s, m, int(r)) for m in unit for s in range(mix["suits"]) for r in rates]
        self._orders: dict[int, np.ndarray] = {}
        self.closed = mix["arrival"] == "backlog"
        self.due = None
        if not self.closed:
            if mix["arrival"] != "poisson":
                raise ValueError(f"unknown arrival kind {mix['arrival']!r}")
            rate = float(mix["rate_per_s"])
            lead = float(mix["lead_s"])
            n = int((horizon_s + lead) * rate * 1.25) + 64
            gaps = np.random.default_rng(mix["schedule_seed"]).exponential(1.0 / rate, n)
            gaps *= 1.0 / (rate * gaps.mean())  # offer the stated rate exactly
            gaps = np.random.default_rng([self.seed, 1]).permutation(gaps)
            self.due = np.cumsum(gaps) - gaps[0] - lead  # seconds from window open

    def session(self, i: int) -> dict:
        """Session ``i``: its stream, suit (the label) and model."""
        m = self.mix
        b, j = divmod(i, len(self.block))
        if b not in self._orders:
            self._orders[b] = np.random.default_rng([self.seed, 2, b]).permutation(len(self.block))
        k = int(self._orders[b][j])
        suit, model, rate = self.block[k]
        return {
            "id": i,
            "stream_id": b * len(self.block) + k,
            "label": suit,
            "model": model,
            "stream": {
                "symbol": suit,
                "events_per_step": rate,
                "input_hw": m["input_hw"],
                "jitter": m["jitter"],
                "seed": m["stream_seed"],
            },
        }


def build(mix: dict, models: list[str], seed: int, horizon_s: float) -> Traffic:
    return Traffic(mix, models, seed, horizon_s)
