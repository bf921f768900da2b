"""Wall ms per pool step in the open loop: the window over its ``step()`` calls."""


def read(run):
    return run.window_s / run.steps * 1e3 if run.steps else None
