"""Device time of the prefill step per 1,000 prompt tokens (model step, prefill).

Reads the traced executions that the benchmark's
``bench.prefill#<call>:<tokens>`` spans launched."""
from bench import xplane as tr


def read(run):
    if run.trace is None:
        return None
    calls = tr.calls(run.trace, run.cfg["modules"], "prefill")
    tokens = sum(run.prefills[i] or 0 for i, _, _ in calls if i < len(run.prefills))
    if not tokens:
        return None
    return sum(b - a for _, a, b in calls) * 1e-6 / (tokens / 1e3)
