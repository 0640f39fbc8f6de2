"""Device idle time between consecutive decode steps while the decode lane
held work (decode lane): table build, logits to host, sampling and the
resident-state round trip all fall in it.

Two consecutive decode calls count when the lane cannot have waited for
work between them: a row of the first continues in the second (same first
page, one token longer), or the second is at the lane's row cap.  The gap
is the device's idle time between the two executions."""
from bench import xplane as tr


def read(run):
    if run.trace is None:
        return None
    calls = sorted(tr.calls(run.trace, run.cfg["modules"], "decode"), key=lambda c: c[1])
    cap = run.cfg["engine"]["decode_max_batch"]
    busy = [iv for d in run.trace.devices for iv in tr.busy_intervals(run.trace, d)]
    gaps = []
    for (i, _, end0), (j, start1, _) in zip(calls, calls[1:]):
        if j != i + 1 or j >= len(run.decodes):
            continue
        _, pages0, lens0 = run.decodes[i]
        _, pages1, lens1 = run.decodes[j]
        held = len(pages1) >= cap or bool(
            set(zip(pages0.tolist(), (lens0 + 1).tolist())) & set(zip(pages1.tolist(), lens1.tolist())))
        if not held or start1 <= end0:
            continue
        other = sum(max(0.0, min(b, start1) - max(a, end0)) for a, b in busy)
        gaps.append((start1 - end0) - other)
    if not gaps:
        return None
    return sum(gaps) * 1e-6 / len(gaps)
