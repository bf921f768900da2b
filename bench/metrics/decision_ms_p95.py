"""95th percentile of wall ms from a session's due arrival to its decision,
over every session due in the window."""

from bench.metrics import _latency


def read(run):
    return _latency.percentile(_latency.decision_ms(run), 95)
