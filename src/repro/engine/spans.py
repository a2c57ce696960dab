"""The program's spans and counters: one table per process, on the
profiler's clock.

``span(name)`` opens a ``jax.profiler.TraceAnnotation`` — so a profile
of the process shows the span on the host line, on the same clock as
the device's ``XLA Ops`` — and adds ``(count, seconds)`` for ``name``
(``time.perf_counter``) to the table.  A span never waits for the
device: it times what the host does inside it, and a span around a
dispatch ends when the dispatch returns.

JAX's compile-phase events (jaxpr tracing, lowering to MLIR — Mosaic
kernels included — backend compilation, and persistent-cache retrieval,
which runs inside backend compilation) are added to the innermost span
open on the thread that compiles, as ``"<span>/<event>"``.  Tracing
nests (an inner ``jax.jit`` traces inside the outer trace), so their
seconds are the union of the event intervals, not a sum; ``count`` is
the number of events.  Events outside any span are not recorded.

``note_trace(key)`` counts a trace of a jitted body: called as a Python
side effect inside the body, it runs only when JAX traces, so its count
says how often that step was (re)compiled.

``set_counter(name, value)`` records the latest value of a quantity
the program knows at a point, such as the session's index layout and
its device bytes at placement (a later placement replaces it).

``snapshot()`` returns the tables; ``reset()`` empties them.  The
names each part of the program opens are listed in docs/ENGINE.md
("Tracing").
"""
from __future__ import annotations

import bisect
import contextlib
import threading
import time

from jax import monitoring
from jax.profiler import TraceAnnotation

#: JAX events recorded with a (start, end) time span.
_SPAN_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        "jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration": "backend_compile_duration",
}
#: JAX events recorded as a duration only (summed: they do not nest).
_DURATION_EVENTS = {
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "cache_retrieval_time_sec",
}


class _Table:
    """Span totals, compile-event intervals and trace counts."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._spans: dict[str, list] = {}       # name -> [count, seconds]
        self._events: dict[str, list] = {}      # name -> [count, starts, ends]
        self._traces: dict[str, int] = {}
        self._counters: dict[str, object] = {}

    def stack(self) -> list:
        if not hasattr(self._local, "stack"):
            self._local.stack = []
        return self._local.stack

    def add_span(self, name: str, seconds: float) -> None:
        with self._lock:
            entry = self._spans.setdefault(name, [0, 0.0])
            entry[0] += 1
            entry[1] += seconds

    def add_event(self, name: str, start: float, end: float) -> None:
        """One compile event, merged into the name's sorted disjoint
        intervals so nested events are not counted twice.  Events end in
        order (JAX records them on exit), so the intervals an event
        covers are the last few: a bisect and a tail merge, never a scan
        (a Pallas step's trace records tens of thousands of events)."""
        with self._lock:
            entry = self._events.setdefault(name, [0, [], []])
            entry[0] += 1
            starts, ends = entry[1], entry[2]
            i = j = bisect.bisect_left(ends, start)
            while j < len(starts) and starts[j] <= end:
                start, end = min(start, starts[j]), max(end, ends[j])
                j += 1
            starts[i:j] = [start]
            ends[i:j] = [end]

    def note_trace(self, key: str) -> None:
        with self._lock:
            self._traces[key] = self._traces.get(key, 0) + 1

    def set_counter(self, name: str, value) -> None:
        with self._lock:
            self._counters[name] = value

    def snapshot(self) -> dict:
        with self._lock:
            spans = {k: {"count": c, "seconds": s}
                     for k, (c, s) in self._spans.items()}
            spans.update({k: {"count": c, "seconds": sum(
                e - s for s, e in zip(starts, ends))}
                for k, (c, starts, ends) in self._events.items()})
            return {"spans": spans, "traces": dict(self._traces),
                    "counters": dict(self._counters)}

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._events.clear()
            self._traces.clear()
            self._counters.clear()


_TABLE = _Table()


@contextlib.contextmanager
def span(name: str):
    """Time the block as ``name`` and show it in a running profile."""
    stack = _TABLE.stack()
    stack.append(name)
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(name):
            yield
    finally:
        _TABLE.add_span(name, time.perf_counter() - t0)
        stack.pop()


def note_trace(key: str) -> None:
    """Count one trace of the jitted body ``key`` (call while tracing)."""
    _TABLE.note_trace(key)


def set_counter(name: str, value) -> None:
    """Record ``value`` (a number or a name) as the latest of ``name``."""
    _TABLE.set_counter(name, value)


def snapshot() -> dict:
    """``{"spans": {name: {"count", "seconds"}}, "traces": {key: n},
    "counters": {name: value}}``; compile events appear under
    ``"<span>/<event>"``."""
    return _TABLE.snapshot()


def reset() -> None:
    """Empty the span table, the trace counts and the counters."""
    _TABLE.reset()


def _innermost() -> str | None:
    stack = _TABLE.stack()
    return stack[-1] if stack else None


def _on_time_span(event: str, start: float, end: float, **_) -> None:
    short = _SPAN_EVENTS.get(event)
    owner = _innermost()
    if short is not None and owner is not None:
        _TABLE.add_event(f"{owner}/{short}", start, end)


def _on_duration(event: str, seconds: float, **_) -> None:
    short = _DURATION_EVENTS.get(event)
    owner = _innermost()
    if short is not None and owner is not None:
        _TABLE.add_span(f"{owner}/{short}", seconds)


monitoring.register_event_time_span_listener(_on_time_span)
monitoring.register_event_duration_secs_listener(_on_duration)
