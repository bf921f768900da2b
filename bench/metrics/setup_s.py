"""Set-up: from process start to the window's open (loading, compiling, warming up)."""


def read(run):
    return run.setup_s
