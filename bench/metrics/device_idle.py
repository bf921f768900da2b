"""Share of the traced window with no operation on the chip; mean over chips."""

from bench.metrics import _trace


def read(run):
    tr = run.trace_data
    busy = None if tr is None else _trace.mean_busy_s(tr)
    return None if not busy else 100.0 * (1.0 - busy / tr.window_s)
