"""Device time of writing pages into the pool per 1,000 prompt tokens
(page pool: ``PagedKVCache.append`` -> the slab scatter, which also writes
back sequences refetched after a spill), over the prompts whose prefill
ran in the traced window."""
from bench import xplane as tr


def read(run):
    if run.trace is None:
        return None
    calls = tr.calls(run.trace, run.cfg["modules"], "prefill")
    tokens = sum(run.prefills[i] or 0 for i, _, _ in calls if i < len(run.prefills))
    writes = tr.executions(run.trace, run.cfg["modules"]["kv_write"])
    if not tokens or not writes:
        return None
    return sum(b - a for _, a, b in writes) * 1e-6 / (tokens / 1e3)
