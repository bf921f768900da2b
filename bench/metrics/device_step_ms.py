"""Device time per step: the trace's busy time per chip over the steps."""

from bench.metrics import _trace


def read(run):
    busy = None if run.trace_data is None else _trace.mean_busy_s(run.trace_data)
    return busy / run.steps * 1e3 if busy and run.steps else None
