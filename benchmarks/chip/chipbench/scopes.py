"""The program's own names in a run: device ops by stage scope, and the
in-process span table.

Each op on a device plane's ``XLA Ops`` line has event metadata whose
``tf_op`` stat is the HLO ``op_name`` the op was compiled with, e.g.
``jit(fused)/light_align/jit(candidate_pair_align)/ref_layout/reshape:``
— the `jax.named_scope`s the program opened around it.  The profiler's
Python API does not expose event-metadata stats, so `op_paths` reads
them from the ``.xplane.pb`` itself (protobuf wire format, the XSpace
field numbers of ``tsl/profiler/protobuf/xplane.proto``); the op times
come from ``jax.profiler.ProfileData`` as in `tracing.read_trace`.

A program without scopes (no op under any `STAGES` name) or without the
`repro.engine.spans` module reads as ``None``: the metrics that need
them are absent, never zero.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os

from chipbench.tracing import OPS_LINE, Span

#: The program's stage scopes (docs/ENGINE.md, "Tracing").
STAGES = frozenset({
    "frontend", "light_align", "residual_dp", "assemble", "ref_layout",
    "lr.frontend", "lr.vote", "lr.anchor_dp", "stage_stats", "reduce"})

# XSpace field numbers
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MD_NAME, _MD_STATS = 2, 5                 # XEventMetadata / XStatMetadata
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_MAP_VALUE = 2


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        out |= (b & 0x7F) << shift
        i += 1
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of one message; length-delimited values are
    memoryview slices, varints ints, fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _device_plane_paths(plane) -> dict[str, str]:
    stat_names, metadata = {}, []
    for field, value in _fields(plane):
        if field == _PLANE_NAME:
            name = _text(value)
            if not name.startswith("/device:") or "CPU" in name:
                return {}
        elif field == _PLANE_STAT_MD:
            entry = dict(_fields(value))
            md = dict(_fields(entry.get(_MAP_VALUE, b"")))
            stat_names[md.get(1, 0)] = _text(md.get(_MD_NAME, b""))
        elif field == _PLANE_EVENT_MD:
            metadata.append(dict(_fields(value)).get(_MAP_VALUE, b""))
    paths = {}
    for md in metadata:
        ev_name, tf_op = "", None
        for field, value in _fields(md):
            if field == _MD_NAME:
                ev_name = _text(value)
            elif field == _MD_STATS:
                stat = dict(_fields(value))
                if stat_names.get(stat.get(_STAT_MD_ID)) == "tf_op":
                    tf_op = (_text(stat[_STAT_STR]) if _STAT_STR in stat
                             else stat_names.get(stat.get(_STAT_REF), ""))
        if ev_name and tf_op is not None:
            paths[ev_name] = tf_op.split(":", 1)[0]
    return paths


@functools.lru_cache(maxsize=4)
def op_paths(xplane_file: str, _mtime: float = 0.0) -> dict[str, str]:
    """Device op event name -> scope path (``tf_op`` without its type)."""
    with open(xplane_file, "rb") as f:
        space = f.read()
    paths = {}
    for field, plane in _fields(space):
        if field == _SPACE_PLANES:
            paths.update(_device_plane_paths(plane))
    return paths


def stages_of(path: str) -> set:
    """The stage scopes on an op's scope path."""
    return STAGES.intersection(path.split("/")[:-1])


@dataclasses.dataclass
class ScopedOps:
    window: Span
    ops: dict          # device -> list of (scope path, start ns, end ns)

    def seconds(self, keep) -> float:
        """Summed in-window seconds of the ops whose path ``keep``
        accepts, averaged over the devices."""
        w0, w1 = self.window.start, self.window.end
        total = sum(min(e, w1) - max(s, w0) for ops in self.ops.values()
                    for path, s, e in ops
                    if e > w0 and s < w1 and keep(path))
        return total / max(len(self.ops), 1) / 1e9


def _newest_trace(trace_dir) -> str | None:
    files = glob.glob(os.path.join(os.fspath(trace_dir), "**",
                                   "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


@functools.lru_cache(maxsize=4)
def _device_ops(xplane_file: str, _mtime: float = 0.0) -> dict:
    from jax.profiler import ProfileData

    paths = op_paths(xplane_file, _mtime)
    ops = {}
    for plane in ProfileData.from_file(xplane_file).planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops.setdefault(plane.name, []).extend(
                    (paths.get(ev.name, ""), ev.start_ns,
                     ev.start_ns + ev.duration_ns) for ev in line.events)
    return ops


def scoped_ops(run) -> ScopedOps | None:
    """The traced window's device ops with their scope paths; None when
    there is no trace or no op in the window carries a stage scope."""
    if run.trace is None:
        return None
    path = _newest_trace(run.cell.bench_dir / "out" / "trace")
    if path is None:
        return None
    scoped = ScopedOps(window=run.trace.window,
                      ops=_device_ops(path, os.path.getmtime(path)))
    if scoped.seconds(stages_of) <= 0:
        return None
    return scoped


def span_table() -> dict | None:
    """The program's span table (`repro.engine.spans.snapshot()`), read
    in the benchmark's own process; None where the program has none."""
    try:
        from repro.engine import spans
    except ImportError:
        return None
    return spans.snapshot()["spans"]
