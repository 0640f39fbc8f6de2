"""What the program records about itself in a traced run (``--trace 1``).

The engine opens spans at its stage boundaries (``paged.*``) and writes
each addition to its counters into the same profiler trace as a
``counter`` event whose arguments are ``counter`` (the total's name),
``key`` (for a total kept per stage) and ``n`` (the amount added); see
``src/repro/core/trace.py``.

``xplane.reduce`` keeps each host event's name and interval but not its
arguments, and a counter event is nothing without its amount.  So this
module reads the run's trace file again, where ``run.py`` writes it
(``.bench_out/trace/<cell>``), and keeps those events with their
arguments.  A program that records none (the commits before these spans
and counters) leaves nothing to read: ``events``, ``counts`` and ``span_ms``
then return ``None``.
"""
from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import NamedTuple

from bench import xplane

TRACE_ROOT = Path(__file__).resolve().parent.parent / ".bench_out" / "trace"
COUNTER = "counter"
PREFIX = "paged."


class Event(NamedTuple):
    name: str
    start: float            # ns, on the trace's clock
    end: float
    line: int               # host thread: the line's index on the host plane
    args: dict


def events(run) -> "list[Event] | None":
    """The program's spans and counter events in this run's trace, in time
    order, or ``None`` where there are none."""
    name = (run.workload or {}).get("name")
    if run.trace is None or not name:
        return None
    try:
        path = xplane.find(str(TRACE_ROOT / name))
    except FileNotFoundError:
        return None
    st = os.stat(path)
    return _read(path, st.st_mtime_ns, st.st_size) or None


@functools.lru_cache(maxsize=2)
def _read(path: str, _mtime_ns: int, _size: int) -> "list[Event]":
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name == COUNTER or e.name.startswith(PREFIX):
                    out.append(Event(e.name, e.start_ns, e.end_ns, i, dict(e.stats)))
    return sorted(out, key=lambda e: e.start)


def in_window(run, e: Event) -> bool:
    lo, hi = run.trace.window
    return lo <= e.start < hi


def counts(run) -> "dict | None":
    """Totals of the counter events inside the traced window, shaped as
    ``Counters.snapshot()``: ``{name: n}``, or ``{name: {key: n}}`` for a
    total kept per key."""
    evs = events(run)
    if evs is None:
        return None
    out: dict = {}
    for e in evs:
        if e.name != COUNTER or not in_window(run, e):
            continue
        name, n, key = e.args.get("counter"), e.args.get("n", 0), e.args.get("key")
        if key is None:
            out[name] = out.get(name, 0) + n
        else:
            per_key = out.setdefault(name, {})
            per_key[key] = per_key.get(key, 0) + n
    return out or None


def span_ms(run, kinds) -> "float | None":
    """Milliseconds spent in the program's spans of ``kinds`` that start
    inside the traced window, or ``None`` where there is none."""
    evs = [e for e in events(run) or [] if e.name in kinds and in_window(run, e)]
    if not evs:
        return None
    return sum(e.end - e.start for e in evs) * 1e-6
