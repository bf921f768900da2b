"""Wall-clock decision latency of the sessions due in the window."""

import numpy as np


def decision_ms(run) -> np.ndarray:
    """Milliseconds from each session's due time to its decision."""
    return np.array([(d.done - d.due) * 1e3 for d in run.decided])


def queue_wait_ms(run) -> np.ndarray:
    """Milliseconds from each session's due time to its admission."""
    return np.array([(d.admitted - d.due) * 1e3 for d in run.decided if d.admitted is not None])


def percentile(x: np.ndarray, q: float):
    return float(np.percentile(x, q)) if x.size else None
