"""Each roofline reader's bytes function against hand-counted shapes,
and the readers end to end on a stand-in run."""
from __future__ import annotations

import importlib.util
import json
import types
from pathlib import Path

import pytest

from chipbench.tracing import Span, TraceSummary

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name.replace('.', '_')}", BENCH / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_pair_frontend_row_bytes():
    m = _reader("pair_frontend.hbm_roofline")
    # R=150, S=3, K=32, C=8: reads 2*150*4=1200, bucket ids out 24 and
    # in 24, location rows 2*3*32*4=768, candidates 2*8*4=64, counts 12
    assert m.row_bytes(150, 3, 32, 8) == 1200 + 24 + 24 + 768 + 64 + 12


def test_pair_frontend_rows_per_batch():
    m = _reader("pair_frontend.hbm_roofline")
    pairs = types.SimpleNamespace(lane="pairs", batch=4096)
    long = types.SimpleNamespace(
        lane="long", batch=128, traffic={"read_len": 15000},
        config={"long_read": {"segment_len": 150, "segment_stride": 300}})
    assert m.rows_per_batch(pairs) == 4096
    assert m.rows_per_batch(long) == 128 * 49   # 50 segments, 49 pairs


def test_candidate_align_pair_bytes():
    m = _reader("candidate_align.hbm_roofline")
    # reads 1200; windows 2*8*(150+16) bases at 4 B (int32) = 10624 or
    # at a quarter byte (2-bit words) = 664; tables 6*8*4=192; out 48
    assert m.pair_bytes(150, 8, 8, False) == 1200 + 10624 + 192 + 48
    assert m.pair_bytes(150, 8, 8, True) == 1200 + 664 + 192 + 48


def test_residual_dp_mate_bytes():
    m = _reader("residual_dp.hbm_roofline")
    # read 600, window (150+32)*4 = 728, score and end 8
    assert m.mate_bytes(150, 16, False) == 600 + 728 + 8
    assert m.mate_bytes(150, 16, True) == 600 + 45.5 + 8


def test_location_vote_read_bytes():
    m = _reader("location_vote.hbm_roofline")
    # 49*8 = 392 diagonals padded to 512 lanes, 3 int32 out
    assert m.read_bytes(50, 8) == 512 * 4 + 12


def _run(lane: str, ops: list, totals: dict):
    cfg = json.loads((BENCH / "configs" / (
        "chr1_pe150.json" if lane == "pairs" else "chr1_hifi.json"))
        .read_text())
    traffic = {"read_len": 150 if lane == "pairs" else 15000}
    cell = types.SimpleNamespace(lane=lane, batch=cfg["batch"], config=cfg,
                                 traffic=traffic)
    trace = TraceSummary(window=Span("bench.window", 0, 1e9),
                         ops={"/device:TPU:0": ops}, host=[])
    return types.SimpleNamespace(cell=cell, trace=trace, totals=totals,
                                 n_batches=10, memory_peak_bytes=2**31,
                                 peaks={"hbm_bytes_per_s": 819e9})


def test_readers_on_a_stand_in_run():
    ops = [Span("pair_frontend.5", 0, 1e8),
           Span("candidate_pair_align.2", 1e8, 3e8),
           Span("residual_pair_dp.1", 3e8, 3.5e8)]
    run = _run("pairs", ops, {"n_pairs": 40960, "light_mapped": 39000,
                              "dp_mate_alignments": 2000})
    assert _reader("pair_frontend.busy_share").read(run) == \
        pytest.approx(10.0)
    assert _reader("device_idle_share").read(run) == pytest.approx(65.0)
    assert _reader("peak_hbm_gib").read(run) == pytest.approx(2.0)
    assert _reader("light_mapped_share").read(run) == \
        pytest.approx(100 * 39000 / 40960)
    fe = _reader("pair_frontend.hbm_roofline")
    assert fe.read(run) == pytest.approx(
        100 * 10 * 4096 * fe.row_bytes(150, 3, 32, 8) / 0.1 / 819e9)
    dp = _reader("residual_dp.hbm_roofline")
    assert dp.read(run) == pytest.approx(
        100 * 2000 * dp.mate_bytes(150, 16, False) / 0.05 / 819e9)
    # nothing to read: no location_vote op in a pairs cell
    assert _reader("location_vote.busy_share").read(run) is None
    assert _reader("location_vote.hbm_roofline").read(run) is None


def test_long_lane_readers():
    run = _run("long", [Span("location_vote.1", 0, 2e7)],
               {"n_reads": 1280})
    lv = _reader("location_vote.hbm_roofline")
    assert lv.read(run) == pytest.approx(
        100 * 10 * 128 * lv.read_bytes(50, 8) / 0.02 / 819e9)
    assert _reader("light_mapped_share").read(run) is None
    assert _reader("candidate_align.hbm_roofline").read(run) is None
