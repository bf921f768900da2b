"""A kernel's share of its roofline: the least time the chip could take for
the work its calls need, over the time they took in the trace."""

from bench import spec
from bench.metrics import _trace, _work


def share(run, prefix: str):
    tr = run.trace_data
    if tr is None:
        return None
    calls = _trace.kernel_events(tr, prefix)
    took = sum(e[2] for e in calls) * 1e-9
    if not calls or took <= 0:
        return None
    need = _work.bound_s(_work.deliver(run.cell.cfg), spec.peaks(run.device["kind"]))
    return 100.0 * need * len(calls) / took
