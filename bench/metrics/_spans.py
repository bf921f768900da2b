"""Host spans the harness put around the program's calls."""


def ms_per_step(run, name: str):
    """Mean wall ms per fleet or pool step spent in span ``name``, summed
    over a fleet's shards."""
    durs = run.spans.get(name)
    if not durs or not run.steps:
        return None
    return sum(durs) * 1e-6 / run.steps
