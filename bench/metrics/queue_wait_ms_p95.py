"""95th percentile of wall ms from a session's due arrival to its admission
(``AerSessionPool.admit_next``), over the sessions due in the window."""

from bench.metrics import _latency


def read(run):
    return _latency.percentile(_latency.queue_wait_ms(run), 95)
