"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The window is the benchmark's own ``bench.window`` host span.  Device
time is the union of the operations on each device plane's ``XLA Ops``
line, clipped to the window and averaged over the devices.  A kernel
family's time is the summed, clipped duration of the operations whose
name matches its pattern.  Idle gaps are the stretches of the window in
which no operation ran, each named after the innermost benchmark span
(``bench.*``) the host was in across most of the gap, else after the
longest host event on the benchmark's thread that overlaps it.  Device
ops are named by their HLO instruction name (`op_name`).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event: TPU traces name an
    op by its whole instruction text, ``%name = shape op(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Span:
    name: str
    start: float   # ns
    end: float     # ns


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi) that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


@dataclasses.dataclass
class TraceSummary:
    window: Span
    ops: dict          # device name -> list[Span]
    host: list         # Span, the benchmark thread's host events

    @property
    def window_s(self) -> float:
        return (self.window.end - self.window.start) / 1e9

    def _clipped(self, spans):
        w0, w1 = self.window.start, self.window.end
        return [(max(s.start, w0), min(s.end, w1)) for s in spans
                if s.end > w0 and s.start < w1]

    def busy_s(self) -> float:
        """Seconds some operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(union_length(self._clipped(spans))
                   for spans in self.ops.values()) / len(self.ops) / 1e9

    def op_seconds(self, pattern: str) -> float:
        """Summed in-window seconds of operations matching ``pattern``
        (a regex, full match), averaged over the devices."""
        rx = re.compile(pattern)
        total = sum(e - s for spans in self.ops.values()
                    for s, e in self._clipped(
                        [x for x in spans if rx.fullmatch(x.name)]))
        return total / max(len(self.ops), 1) / 1e9

    def top_ops(self, n: int = 10) -> list:
        """[name, seconds] of the operations that took most device time."""
        acc: dict = {}
        for spans in self.ops.values():
            for x in spans:
                for s, e in self._clipped([x]):
                    acc[x.name] = acc.get(x.name, 0.0) + (e - s) / 1e9
        k = max(len(self.ops), 1)
        return [[name, t / k] for name, t in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """[host activity, seconds] of the longest idle gaps (first device)."""
        if not self.ops:
            return []
        spans = next(iter(self.ops.values()))
        found = gaps(self._clipped(spans), self.window.start, self.window.end)
        found.sort(key=lambda g: g[0] - g[1])
        return [[self._host_activity(s, e), (e - s) / 1e9]
                for s, e in found[:n]]

    def _host_activity(self, s: float, e: float) -> str:
        bench = [h for h in self.host if h.name.startswith("bench.")
                 and h.name != WINDOW_SPAN
                 and _overlap(h.start, h.end, s, e) > (e - s) / 2]
        if bench:
            return min(bench, key=lambda h: h.end - h.start).name
        other = [(_overlap(h.start, h.end, s, e), h.name) for h in self.host
                 if not h.name.startswith("bench.")]
        other = [o for o in other if o[0] > 0]
        return max(other)[1] if other else "no host event"


def read_trace(trace_dir: str) -> TraceSummary:
    """Load the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    ops, window, host = {}, None, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        Span(op_name(ev.name), ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                        for ev in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = [Span(ev.name, ev.start_ns,
                               ev.start_ns + ev.duration_ns)
                          for ev in line.events]
                mine = [x for x in events if x.name == WINDOW_SPAN]
                if mine:
                    window = mine[0]
                    host = events
    if window is None:
        raise RuntimeError(f"the trace has no {WINDOW_SPAN!r} span")
    return TraceSummary(window=window, ops=ops, host=host)
