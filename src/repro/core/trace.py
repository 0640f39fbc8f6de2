"""The program's own spans and counters, on the profiler's clock.

``span(name, **meta)`` is ``jax.profiler.TraceAnnotation``: while a
profiler trace is being taken (``jax.profiler.start_trace`` or a profiler
server), its event lands on the host plane of that same trace, on the
clock the device's events are put on; otherwise it costs about a
microsecond.  ``meta`` becomes the event's arguments: the request id
(``rid``) on the prefill lane's spans, the lane's step number (``step``) on
the decode lane's, so that the spans of one request or one step can be
found together.

``Counters`` keeps monotonic totals for operators and tests
(``snapshot()``).  Each addition made while a trace is being taken is also
written into it as a ``COUNTER`` event whose arguments are ``counter`` (the
total's name), ``key`` (for a keyed total) and ``n`` (the amount added), so
that a reader of the trace can total any counter over any interval of it.

Nothing here exports, buffers or is configured: a trace is taken by
whoever starts the profiler.
"""
from __future__ import annotations

import threading

from jax.profiler import TraceAnnotation

__all__ = ["COUNTER", "Counters", "span"]

COUNTER = "counter"


def span(name: str, **meta) -> TraceAnnotation:
    """A context manager that records ``name`` over its body in the
    profiler's trace, with ``meta`` as the event's arguments."""
    return TraceAnnotation(name, **meta)


class Counters:
    """Thread-safe monotonic totals, plain (``add(name, n)``) or keyed
    (``add(name, n, key=...)``, one total per key)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._totals: dict = {}

    def add(self, name: str, n=1, key: "str | None" = None) -> None:
        with self._lock:
            if key is None:
                self._totals[name] = self._totals.get(name, 0) + n
            else:
                per_key = self._totals.setdefault(name, {})
                per_key[key] = per_key.get(key, 0) + n
        if TraceAnnotation.is_enabled():
            meta = {"counter": name, "n": n}
            if key is not None:
                meta["key"] = key
            with TraceAnnotation(COUNTER, **meta):
                pass

    def snapshot(self) -> dict:
        """The totals now: ``{name: total}``, a keyed total as
        ``{name: {key: total}}``."""
        with self._lock:
            return {name: dict(v) if isinstance(v, dict) else v
                    for name, v in self._totals.items()}
