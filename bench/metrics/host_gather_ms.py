"""Host input gather (``AerSessionPool.gather_inputs``): ms per step."""

from bench.metrics import _spans


def read(run):
    return _spans.ms_per_step(run, "gather_inputs")
