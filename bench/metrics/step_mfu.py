"""The whole step's share of the chip's peak over the traced window: the
least time one pool step's required work could take (delivery and neuron
update), times the steps, over the window. A fleet runs one pool per chip,
so the share is per chip."""

from bench import spec
from bench.metrics import _work


def read(run):
    tr = run.trace_data
    if tr is None or not tr.ops or not run.steps:
        return None
    need = _work.bound_s(_work.step(run.cell.cfg), spec.peaks(run.device["kind"]))
    return 100.0 * need * run.steps / tr.window_s
