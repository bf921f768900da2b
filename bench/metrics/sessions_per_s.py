"""Decisions completed in the window over the window's seconds."""


def read(run):
    return len(run.decided) / run.window_s if run.window_s > 0 else None
