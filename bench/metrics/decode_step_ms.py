"""Mean device time of one decode step (model step, decode): the traced
executions that the benchmark's ``bench.decode`` spans launched."""
from bench import xplane as tr


def read(run):
    if run.trace is None:
        return None
    calls = tr.calls(run.trace, run.cfg["modules"], "decode")
    if not calls:
        return None
    return sum(b - a for _, a, b in calls) * 1e-6 / len(calls)
