"""Faults planted in the timed path, each of which ``correct`` must catch.

Each takes the built system (``bench.harness.PoolSystem`` or
``FleetSystem``) and breaks the program underneath it, on the instances:

* ``stale_state``: the step returns its neuron state unchanged;
* ``half_batch``: the second half of the slots gets no input;
* ``drop_shard``: one shard of a fleet never steps (its exchange with the
  rest of the fleet is left out);
* ``altered_answer``: every decision is handed out for the wrong class.

``stale_state`` reads the carry after the step, so it needs a backend that
does not donate it (the CPU); the others run anywhere.
"""

from __future__ import annotations

import dataclasses


def stale_state(system) -> None:
    for pool in system.pools:
        step = pool.engine.step

        def unchanged(carry, inp, i_ext=None, _step=step):
            new, out = _step(carry, inp, i_ext)
            return (carry[0], *new[1:]), out

        pool.engine.step = unchanged


def half_batch(system) -> None:
    for pool in system.pools:
        gather = pool.gather_inputs

        def half(_gather=gather):
            acts = _gather()
            acts[acts.shape[0] // 2:] = 0.0
            return acts

        pool.gather_inputs = half


def drop_shard(system) -> None:
    pool = system.pools[-1]
    pool.begin_step = lambda: None
    pool.finish_step = lambda out: None


def altered_answer(system) -> None:
    for pool in system.pools:
        evict_many = pool.evict_many

        def altered(slots, _evict=evict_many, _n=pool.n_classes):
            return [dataclasses.replace(r, prediction=int((r.prediction + 1) % _n))
                    for r in _evict(slots)]

        pool.evict_many = altered


FAULTS = {f.__name__: f for f in (stale_state, half_batch, drop_shard, altered_answer)}
