"""Relaxation rounds per query: the mean of ``SolveOutput.telemetry.iterations``
over the queries the window completed (an exact integer count each)."""


def read(run):
    if not run.iterations:
        return None
    return sum(run.iterations) / len(run.iterations)
