"""The trace reduction on a small synthetic trace: union busy time,
per-family sums, idle gaps and what the host was doing in them."""
from __future__ import annotations

import pytest

from chipbench.tracing import Span, TraceSummary, gaps, op_name, union_length

MS = 1e6  # ns


def _summary() -> TraceSummary:
    # Window 0-100 ms; two overlapping front-end ops, one aligner op, a
    # gap 40-60 ms in which the host was feeding, and one op that starts
    # before the window (clipped).
    ops = [Span("pair_frontend.5", 10 * MS, 20 * MS),
           Span("pair_frontend.6", 15 * MS, 30 * MS),
           Span("candidate_pair_align.3", 30 * MS, 40 * MS),
           Span("fusion.12", 60 * MS, 90 * MS),
           Span("copy.1", -10 * MS, 5 * MS)]
    host = [Span("bench.window", 0, 100 * MS),
            Span("bench.feed", 41 * MS, 59 * MS),
            Span("PjitFunction(fused)", 90 * MS, 99 * MS)]
    return TraceSummary(window=Span("bench.window", 0, 100 * MS),
                        ops={"/device:TPU:0": ops}, host=host)


def test_union_length_merges_overlaps():
    assert union_length([(0, 10), (5, 15), (20, 30)]) == 25
    assert union_length([]) == 0


def test_gaps_are_the_uncovered_parts():
    assert gaps([(10, 20), (15, 30)], 0, 50) == [(0, 10), (30, 50)]
    assert gaps([(0, 50)], 0, 50) == []


def test_busy_time_is_the_clipped_union():
    s = _summary()
    # 0-5 (clipped copy), 10-40, 60-90
    assert s.busy_s() == pytest.approx(0.065)
    assert s.window_s == pytest.approx(0.1)


def test_family_time_sums_matching_ops():
    s = _summary()
    assert s.op_seconds(r"pair_frontend(\.\d+)?") == pytest.approx(0.025)
    assert s.op_seconds(r"candidate_pair_align(\.\d+)?") == \
        pytest.approx(0.010)
    assert s.op_seconds(r"location_vote(\.\d+)?") == 0.0


def test_top_ops_are_ranked_by_device_time():
    top = _summary().top_ops(2)
    assert [name for name, _ in top] == ["fusion.12", "pair_frontend.6"]
    assert top[0][1] == pytest.approx(0.030)


def test_idle_gaps_are_named_by_the_host_span():
    named = dict(_summary().idle_gaps())
    assert named["bench.feed"] == pytest.approx(0.020)
    # 90-100 ms: no benchmark span, the longest overlapping host event
    assert named["PjitFunction(fused)"] == pytest.approx(0.010)
    # 5-10 ms: nothing on the host
    assert named["no host event"] == pytest.approx(0.005)


def test_averages_over_devices():
    s = _summary()
    s.ops["/device:TPU:1"] = [Span("pair_frontend.5", 0, 100 * MS)]
    assert s.busy_s() == pytest.approx((0.065 + 0.1) / 2)
    assert s.op_seconds(r"pair_frontend(\.\d+)?") == \
        pytest.approx((0.025 + 0.1) / 2)


def test_op_name_is_the_hlo_instruction_name():
    # as a TPU trace names a Pallas custom call
    text = ("%pair_frontend.5 = (s32[2048,8]{1,0:T(8,128)S(1)}, s32[2048,1]"
            "{1,0}) custom-call(s32[6144]{0} %reshape.2), "
            'custom_call_target="tpu_custom_call"')
    assert op_name(text) == "pair_frontend.5"
    assert op_name("fusion.12") == "fusion.12"
