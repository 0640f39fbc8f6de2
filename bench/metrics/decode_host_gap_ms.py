"""Device idle time between consecutive decode steps while the decode lane
held work (decode lane): table build, logits to host, sampling and the
resident-state round trip all fall in it.

Each device has its own lane, so two calls are consecutive when no call of
their device comes between them.  They count when the lane cannot have
waited for work between them: a row of the first continues in the second
(same first page, one token longer), or the second is at the lane's row
cap.  The gap is that device's idle time between the two executions."""
from bench import xplane as tr


def read(run):
    if run.trace is None:
        return None
    at = {i: (a, b) for i, a, b in tr.calls(run.trace, run.cfg["modules"], "decode")}
    cap = run.cfg["engine"]["decode_max_batch"]
    after, last = {}, {}
    for j, d in enumerate(run.decodes):
        if d[3] in last:
            after[last[d[3]]] = j
        last[d[3]] = j
    busy = {d: tr.busy_intervals(run.trace, d) for d in run.trace.devices}
    gaps = []
    for i, j in after.items():
        if i not in at or j not in at:
            continue
        end0, start1 = at[i][1], at[j][0]
        _, pages0, lens0, dev = run.decodes[i]
        _, pages1, lens1, _ = run.decodes[j]
        held = len(pages1) >= cap or bool(
            set(zip(pages0.tolist(), (lens0 + 1).tolist())) & set(zip(pages1.tolist(), lens1.tolist())))
        if not held or start1 <= end0:
            continue
        other = sum(max(0.0, min(b, start1) - max(a, end0)) for a, b in busy.get(dev, []))
        gaps.append((start1 - end0) - other)
    if not gaps:
        return None
    return sum(gaps) * 1e-6 / len(gaps)
