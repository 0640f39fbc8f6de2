"""Pages the page pool spilled to the host and fetched back per 1,000
prompt tokens prefilled (page pool), in the traced window: the engine's
``spilled_pages`` plus ``refetched_pages`` over its ``prefill_tokens``.
The two page counters are written only when a page moves, so a window
that prefilled and moved none reads 0."""
from bench import program


def read(run):
    c = program.counts(run)
    if not c or not c.get("prefill_tokens"):
        return None
    return (c.get("spilled_pages", 0) + c.get("refetched_pages", 0)) / (c["prefill_tokens"] / 1e3)
