"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

* device executions: the ``XLA Modules`` line of each TPU device plane, one
  event per run of a compiled program, named ``jit_<function>(<id>)`` with
  one id per compiled shape;
* when each execution was handed to its device, on the host's clock: the
  host's ``DoEnqueueProgram`` event with the execution's device
  (``device_ordinal``) and ``run_id``, which each device counts on its own;
* device ops: the ``XLA Ops`` line, whose union is the busy time;
* host spans: every event on the host plane's threads, the benchmark's own
  ``TraceAnnotation`` spans among them, with the thread each ran on;
* the window: the benchmark's ``bench.window`` span, else the events' extent.

jit names a program after its function, and the engine's two zoo steps are
``functools.partial`` objects, which jit names alike (``jit__unknown``).  So
an execution is told apart by the span that launched it,
``bench.<kind>#<call>:<shape>@<device>``: the engine waits for every step's
logits before its next step on that thread, so the execution launched by
such a span is handed to that device after the span starts and before the
host's next sync on that thread (``np.asarray``) ends.  Both ends are on
the host's clock, so the device's clock, which the trace puts on the
host's only to a few milliseconds, plays no part.  Every compiled shape
belongs to one kind, which the executions that only one kind's spans could
have launched decide.

All times are nanoseconds on the trace's one clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
SYNC = "np.asarray"
ENQUEUE = "DoEnqueueProgram"
SKEW_NS = 5e6
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Trace:
    window: "tuple[float, float]"
    modules: "dict[int, list[tuple[str, float, float]]]" = field(default_factory=dict)
    ops: "dict[int, list[tuple[str, float, float]]]" = field(default_factory=dict)
    spans: "list[tuple[str, float, float, int]]" = field(default_factory=list)  # + host line
    # device -> {start of an execution on it: when the host handed it over}
    enqueued: "dict[int, dict[float, float]]" = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> "list[int]":
        return sorted(set(self.modules) | set(self.ops))


def find(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path))


def reduce(data) -> Trace:
    """A ``jax.profiler.ProfileData`` reduced to a ``Trace``."""
    modules: dict = {}
    ops: dict = {}
    spans = []
    run_ids: dict = {}      # device -> [(execution start, run_id)]
    handed: dict = {}       # (device, run_id) -> enqueue time
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    evs = list(line.events)
                    modules[dev] = [(e.name, e.start_ns, e.end_ns) for e in evs]
                    run_ids[dev] = [(e.start_ns, dict(e.stats).get("run_id")) for e in evs]
                elif line.name == "XLA Ops":
                    ops[dev] = [(e.name, e.start_ns, e.end_ns) for e in line.events]
        elif plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name == ENQUEUE:
                        st = dict(e.stats)
                        handed[(st.get("device_ordinal"), st.get("run_id"))] = e.start_ns
                    if e.end_ns > e.start_ns:
                        spans.append((e.name, e.start_ns, e.end_ns, i))
    enqueued = {dev: {a: handed[(dev, r)] for a, r in runs if (dev, r) in handed}
                for dev, runs in run_ids.items()}
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if win:
        window = (win[0][1], win[0][2])
    else:
        every = [e for evs in list(modules.values()) + list(ops.values()) for e in evs] + spans
        window = (min(e[1] for e in every), max(e[2] for e in every)) if every else (0.0, 0.0)
    spans = [s for s in spans if s[0] != WINDOW_SPAN]
    return Trace(window, modules, ops, spans, enqueued)


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def union(intervals) -> "list[tuple[float, float]]":
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_intervals(trace: Trace, dev: int) -> "list[tuple[float, float]]":
    evs = trace.ops.get(dev) or trace.modules.get(dev) or []
    return union(_clip([(a, b) for _, a, b in evs], *trace.window))


def busy_s(trace: Trace) -> "float | None":
    """Seconds in which an operation ran, averaged over the traced devices."""
    devs = trace.devices
    if not devs:
        return None
    return sum(sum(b - a for a, b in busy_intervals(trace, d)) for d in devs) * 1e-9 / len(devs)


def idle_gaps(trace: Trace, dev: int) -> "list[tuple[float, float]]":
    lo, hi = trace.window
    gaps, t = [], lo
    for a, b in busy_intervals(trace, dev):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def span_kind(name: str) -> str:
    """``bench.decode#12:8@0`` -> ``bench.decode``: a span's name without its call tag."""
    return name.split("#", 1)[0]


def span_call(name: str) -> "tuple[int, int | None]":
    """``bench.decode#12:8@0`` -> ``(12, 0)``: the call's index and the
    device it ran on, ``None`` where the span does not say."""
    tag = name.split("#", 1)[1]
    _, at, dev = tag.partition("@")
    return int(tag.split(":", 1)[0]), (int(dev) if at else None)


def host_cause(trace: Trace, a: float, b: float) -> str:
    """The host event that overlaps [a, b] most (the shorter on a tie)."""
    best, key = "no host span", None
    for name, s, e, _ in trace.spans:
        ov = min(b, e) - max(a, s)
        if ov <= 0:
            continue
        k = (ov, -(e - s))
        if key is None or k > key:
            best, key = span_kind(name), k
    return best


def executions(trace: Trace, prefix: str) -> "list[tuple[str, float, float]]":
    """(module name, start, end) of each run of a program whose module name
    starts with ``prefix`` and that starts inside the window (give or take
    the clocks' offset), in time order."""
    lo, hi = trace.window[0] - SKEW_NS, trace.window[1]
    out = []
    for d in trace.devices:
        out.extend((n, a, b) for n, a, b in trace.modules.get(d, []) if n.startswith(prefix) and lo <= a < hi)
    return sorted(out, key=lambda e: e[1])


def _launch_windows(trace: Trace, kind: str) -> "list[tuple[int, int | None, float, float]]":
    """(call index, device, span start, end of the host's next sync on the
    span's thread, else the start of the thread's next span of the kind)."""
    spans = sorted((s, e, line, name) for name, s, e, line in trace.spans if span_kind(name) == kind)
    syncs: dict = {}
    for name, s, e, line in trace.spans:
        if name.startswith(SYNC):
            syncs.setdefault(line, []).append((s, e))
    for v in syncs.values():
        v.sort()
    out = []
    for j, (s, e, line, name) in enumerate(spans):
        later = [t for t, _, ln, _ in spans[j + 1:] if ln == line]
        limit = later[0] if later else float("inf")
        sync = next((se for ss, se in syncs.get(line, []) if ss >= e), None)
        out.append((*span_call(name), s, min(limit, sync) if sync is not None else limit))
    return out


def calls(trace: Trace, modules: dict, kind: str) -> "list[tuple[int, float, float]]":
    """(call index, start, end) of each execution launched by a
    ``bench.<kind>#<call>`` span and handed to its device inside the window,
    in time order.  ``modules`` maps each kind to the module-name prefix of
    its program.

    An execution's candidates are the launch windows, of spans on its own
    device (or that name none), that hold the moment it was handed over.
    One thread's windows do not overlap, so only a second thread launching
    onto the same device makes a second candidate.  An execution that only
    one free window holds is that window's.  When that settles nothing more,
    each compiled shape takes the kind its settled executions have most, an
    open execution keeps only the windows of its shape's kind, and settling
    goes on.  An execution still open, or handed over at no recorded time,
    is left out."""
    prefix = modules[kind]
    kinds = [k for k, p in modules.items() if p == prefix]
    wins = [(k, i, dev, s, e) for k in kinds for i, dev, s, e in _launch_windows(trace, "bench." + k)]
    lo, hi = trace.window
    execs = []
    for d in trace.devices:
        handed = trace.enqueued.get(d, {})
        for n, a, b in trace.modules.get(d, []):
            t = handed.get(a)
            if n.startswith(prefix) and t is not None and lo <= t < hi:
                execs.append((n, a, b, [(k, i) for k, i, dev, s, e in wins
                                        if dev in (None, d) and s <= t <= e]))
    owner: dict = {}
    taken: set = set()
    shape_kind: dict = {}
    while True:
        settled = False
        for j, (n, _, _, cands) in enumerate(execs):
            free = [c for c in cands if c not in taken and shape_kind.get(n, c[0]) == c[0]]
            if j not in owner and len(free) == 1:
                owner[j] = free[0]
                taken.add(free[0])
                settled = True
        if settled:
            continue
        tally: dict = {}
        for j, (k, _) in owner.items():
            t = tally.setdefault(execs[j][0], {})
            t[k] = t.get(k, 0) + 1
        learned = {n: max(t, key=t.get) for n, t in tally.items()}
        if learned == shape_kind:
            break
        shape_kind = learned
    out = [(owner[j][1], a, b) for j, (_, a, b, _) in enumerate(execs)
           if j in owner and owner[j][0] == kind]
    return sorted(out, key=lambda c: c[1])


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time, and the longest idle gaps by what
    the host was doing, each as [name, seconds]."""
    lo, hi = trace.window
    tot: dict = {}
    for d in trace.devices:
        evs = trace.ops.get(d) or trace.modules.get(d) or []
        for n, a, b in evs:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                n = n.split(" = ", 1)[0]  # an op's HLO name, without its text
                tot[n] = tot.get(n, 0.0) + (b - a) * 1e-9
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for d in trace.devices:
        gaps.extend(idle_gaps(trace, d))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[host_cause(trace, a, b), (b - a) * 1e-9] for a, b in gaps]}
