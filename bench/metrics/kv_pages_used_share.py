"""Peak share of the fullest page pool in use over the window (page pool),
from each pool's own counter sampled every 0.1 s by the load loop."""


def read(run):
    if not run.samples or not run.pool_pages:
        return None
    return 100.0 * max(max(s[2]) for s in run.samples) / run.pool_pages
