"""The readers of the program's own names on a synthetic trace and span
table: op scope paths from the trace's event metadata, the reference
layout's and the unscoped ops' device shares, the stream loop's host
share, and the set-up split read from `repro.engine.spans`.  A program
without scopes or spans reads as absent, never as zero."""
from __future__ import annotations

import importlib.util
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import scopes
from chipbench.tracing import Span, TraceSummary

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"

MS = 1e6  # ns


# -- a minimal XSpace encoder (tsl/profiler/protobuf/xplane.proto) --------
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _msg(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _str(field: int, s: str) -> bytes:
    return _msg(field, s.encode())


def _xspace(ops, tf_op_by_ref: bool = False) -> bytes:
    """One TPU plane whose ``XLA Ops`` line holds ``ops``: (instruction
    text, tf_op or None, start ms, end ms)."""
    TF_OP, PATH0 = 1, 100
    stat_md = [(TF_OP, "tf_op")]
    event_md, events = b"", b""
    for i, (name, tf_op, s, e) in enumerate(ops, start=1):
        stats = b""
        if tf_op is not None:
            if tf_op_by_ref:
                stat_md.append((PATH0 + i, tf_op))
                stats = _msg(5, _int(1, TF_OP) + _int(7, PATH0 + i))
            else:
                stats = _msg(5, _int(1, TF_OP) + _str(5, tf_op))
        md = _int(1, i) + _str(2, name) + stats
        event_md += _msg(4, _int(1, i) + _msg(2, md))
        events += _msg(4, _int(1, i) + _int(2, int(s * MS * 1000))
                       + _int(3, int((e - s) * MS * 1000)))
    line = _int(1, 1) + _str(2, "XLA Ops") + _int(3, 0) + events
    smd = b"".join(_msg(5, _int(1, k) + _msg(2, _int(1, k) + _str(2, v)))
                   for k, v in stat_md)
    plane = (_int(1, 1) + _str(2, "/device:TPU:0") + _msg(3, line)
             + event_md + smd)
    host = _int(1, 2) + _str(2, "/host:CPU")
    return _msg(1, host) + _msg(1, plane)


OPS = [
    ("%candidate_pair_align.4 = (s32[]) custom-call()",
     "jit(fused)/light_align/jit(candidate_pair_align)/"
     "candidate_pair_align/pallas_call:", 10, 40),
    ("%pad_bitcast_fusion = s32[8,128] fusion()",
     "jit(fused)/light_align/jit(candidate_pair_align)/ref_layout/"
     "reshape:", 40, 45),
    ("%concatenate.56 = s32[1024] concatenate()",
     "jit(fused)/residual_dp/jit(residual_pair_dp)/ref_layout/"
     "concatenate:", 45, 50),
    ("%copy.13 = s32[8] copy()", None, 50, 51),
    ("%fusion.2 = s32[] fusion()", "reduce_window_sum:", 60, 62),
    ("%fusion.9 = s32[] fusion()", "jit(fused)/assemble/add:", 95, 110),
]


def _run(tmp_path, ops, host=(), by_ref=False):
    trace_dir = tmp_path / "out" / "trace" / "plugins" / "profile" / "t"
    trace_dir.mkdir(parents=True)
    (trace_dir / "host.xplane.pb").write_bytes(_xspace(ops, by_ref))
    window = Span("bench.window", 0, 100 * MS)
    summary = TraceSummary(
        window=window,
        ops={"/device:TPU:0": [Span(n.split(" = ")[0].lstrip("%"),
                                    s * MS, e * MS) for n, _, s, e in ops]},
        host=[window, *host])
    return types.SimpleNamespace(cell=types.SimpleNamespace(
        bench_dir=tmp_path), trace=summary)


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"test_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("by_ref", [False, True], ids=["str", "ref"])
def test_op_paths_read_tf_op_from_event_metadata(tmp_path, by_ref):
    run = _run(tmp_path, OPS, by_ref=by_ref)
    f = next((tmp_path / "out" / "trace").rglob("*.xplane.pb"))
    paths = scopes.op_paths(str(f), f.stat().st_mtime)
    assert paths[OPS[1][0]] == OPS[1][1][:-1]
    assert OPS[3][0] not in paths                  # no tf_op stat
    assert scopes.stages_of(paths[OPS[1][0]]) == {"light_align",
                                                  "ref_layout"}
    assert scopes.stages_of(paths[OPS[4][0]]) == set()
    ops = scopes.scoped_ops(run)
    assert ops.seconds(lambda p: True) == pytest.approx(0.030 + 0.005 * 2
                                                        + 0.001 + 0.002
                                                        + 0.005)


def test_ref_layout_busy_share(tmp_path):
    # 10 ms of ref_layout ops in a 100 ms window
    assert _reader("ref_layout.busy_share")(_run(tmp_path, OPS)) == \
        pytest.approx(10.0)


def test_unscoped_device_share(tmp_path):
    run = _run(tmp_path, OPS)
    # busy: 10-51, 60-62, 95-100 (clipped) = 48 ms; unscoped: copy.13
    # (1 ms, no tf_op) and fusion.2 (2 ms, no stage on its path)
    assert _reader("unscoped_device_share")(run) == \
        pytest.approx(100.0 * 3 / 48)


def test_device_readers_absent_without_scopes(tmp_path):
    unscoped = [(n, "jit(fused)/jit(pair_frontend)/pallas_call:", s, e)
                for n, _, s, e in OPS]
    run = _run(tmp_path, unscoped)
    assert _reader("ref_layout.busy_share")(run) is None
    assert _reader("unscoped_device_share")(run) is None
    assert _reader("ref_layout.busy_share")(
        types.SimpleNamespace(trace=None, cell=None)) is None


def test_device_readers_absent_without_a_trace_file(tmp_path):
    run = _run(tmp_path, OPS)
    for f in (tmp_path / "out" / "trace").rglob("*.xplane.pb"):
        f.unlink()
    assert _reader("ref_layout.busy_share")(run) is None


def test_stream_host_share_is_the_union_of_host_spans(tmp_path):
    host = [Span("stream.pad", 10 * MS, 12 * MS),
            Span("stream.dispatch", 12 * MS, 15 * MS),
            Span("stream.retire", 14 * MS, 16 * MS),     # overlaps
            Span("stream.pull", 20 * MS, 30 * MS),       # not host work
            Span("stream.dispatch", 98 * MS, 104 * MS)]  # clipped
    run = _run(tmp_path, OPS, host=host)
    assert _reader("stream.host_share")(run) == pytest.approx(8.0)
    assert _reader("stream.host_share")(_run(tmp_path / "p", OPS)) is None


def test_setup_split_reads_the_span_table():
    from repro.engine import spans

    spans.reset()
    try:
        with spans.span("session.load"):
            pass
        with spans.span("stream.warmup"):
            jax.jit(lambda x: x * 19.0 - 1.0)(jnp.arange(3.0)) \
                .block_until_ready()
        run = types.SimpleNamespace()
        prepare = _reader("setup.step_prepare_s")(run)
        load = _reader("setup.executable_load_s")(run)
        store = _reader("setup.store_load_s")(run)
        table = spans.snapshot()["spans"]
        assert prepare > 0 and load > 0 and store >= 0
        assert prepare + load <= table["stream.warmup"]["seconds"]
        assert store == table["session.load"]["seconds"]
    finally:
        spans.reset()
    assert _reader("setup.step_prepare_s")(types.SimpleNamespace()) is None
    assert _reader("setup.store_load_s")(types.SimpleNamespace()) is None


def test_setup_readers_absent_without_the_spans_module(monkeypatch):
    import repro.engine

    monkeypatch.setitem(sys.modules, "repro.engine.spans", None)
    monkeypatch.delattr(repro.engine, "spans", raising=False)
    for name in ("setup.step_prepare_s", "setup.executable_load_s",
                 "setup.store_load_s"):
        assert _reader(name)(types.SimpleNamespace()) is None
