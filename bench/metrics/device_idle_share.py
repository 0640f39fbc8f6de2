"""Share of the traced window in which no operation ran on the device
(device): one minus the union of the device's op intervals over the window,
averaged over the chips."""
from bench import xplane as tr


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    busy = tr.busy_s(run.trace)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / run.trace.window_s)
