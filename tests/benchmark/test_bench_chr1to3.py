"""The whole-genome share's cell: its configuration, cell and metrics
found by name, the CSR front end's readers on a synthetic trace and
span table, and the harness's refusal of a cell whose index the
program cannot place."""
from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

from chipbench import refuse_unplaceable_index, workload_arg
from chipbench.cell import load_cell
from test_bench_scopes import _reader, _run

REPO = Path(__file__).resolve().parents[2]
BENCH_DIR = REPO / "benchmarks" / "chip"


def test_chr1to3_cell_is_found_by_name():
    """The cell, configuration and metrics as `BENCHMARK.json` names
    them; the configuration changes only the genome's length and the
    table's bits from the chr1 deployment."""
    cell = load_cell(REPO, "pe150_chr1to3")
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "chr1to3_pe150", "illumina_wgs", 1)
    assert {m["name"] for m in cell.per_layer} == {
        "csr_frontend.busy_share", "csr_frontend.hbm_roofline",
        "index.device_gib"}
    assert {m["name"] for m in cell.end_to_end} == {"mbp_per_s", "setup_s"}
    chr1 = load_cell(REPO, "pe150_illumina").config
    assert cell.config["genome"] == {**chr1["genome"], "length": 689445510}
    assert cell.config["seedmap"] == {**chr1["seedmap"], "table_bits": 28}
    for key in ("lane", "batch", "pipeline", "long_read", "control"):
        assert cell.config[key] == chr1[key], key
    assert set(cell.config["reduced"]) == {"sequence", "contigs",
                                           "cross_chip_pick"}
    for m in cell.per_layer:
        assert (BENCH_DIR / "metrics" / f"{m['name']}.py").exists()


def test_csr_frontend_row_bytes():
    row_bytes = _reader("csr_frontend.hbm_roofline").__globals__[
        "row_bytes"]
    # R=150, S=3, K=32, C=8: the padded front end's reads 2*150*4=1200,
    # bucket ids out 24 and in 24, location rows 2*3*32*4=768,
    # candidates 2*8*4=64, counts 12; plus both ends of 2 mates x 3
    # seeds' buckets: 2*3*2*4 = 48
    assert row_bytes(150, 3, 32, 8) == 1200 + 24 + 24 + 768 + 64 + 12 + 48


CSR_OPS = [
    ("%pair_frontend.4 = (s32[]) custom-call()",
     "jit(fused)/frontend/jit(pair_frontend)/pair_frontend/pallas_call:",
     10, 40),
    ("%gather_fusion = s32[2,8192,3] fusion()",
     "jit(fused)/frontend/jit(pair_frontend)/index_offsets/gather:", 5, 7),
    ("%concatenate.3 = s32[12288] concatenate()",
     "jit(fused)/frontend/jit(pair_frontend)/index_offsets/"
     "concatenate:", 7, 8),
    ("%fusion.9 = s32[] fusion()", "jit(fused)/assemble/add:", 50, 60),
]


def _csr_run(tmp_path, ops):
    run = _run(tmp_path, ops)
    cfg = {"pipeline": {"read_len": 150, "seeds_per_read": 3,
                        "max_locs_per_seed": 32, "max_candidates": 8}}
    run.cell = types.SimpleNamespace(bench_dir=tmp_path, lane="pairs",
                                     batch=4096, config=cfg)
    run.n_batches = 10
    run.peaks = {"hbm_bytes_per_s": 819e9}
    return run


def test_csr_frontend_readers(tmp_path):
    run = _csr_run(tmp_path, CSR_OPS)
    # 30 ms of pair_frontend and 3 ms under index_offsets in 100 ms
    assert _reader("csr_frontend.busy_share")(run) == pytest.approx(33.0)
    moved = 10 * 4096 * (1200 + 24 + 24 + 768 + 64 + 12 + 48)
    assert _reader("csr_frontend.hbm_roofline")(run) == pytest.approx(
        100.0 * moved / 0.033 / 819e9)


def test_csr_frontend_readers_absent_without_index_offsets(tmp_path):
    """A front end that gathered from padded rows has no op under
    ``index_offsets``: the CSR readers find nothing to read."""
    run = _csr_run(tmp_path, [op for op in CSR_OPS
                              if "index_offsets" not in op[1]])
    assert _reader("csr_frontend.busy_share")(run) is None
    assert _reader("csr_frontend.hbm_roofline")(run) is None
    assert _reader("csr_frontend.busy_share")(
        types.SimpleNamespace(trace=None, cell=None)) is None


def test_index_device_gib_reads_the_placement_counter(monkeypatch):
    from repro.engine import spans

    spans.reset()
    try:
        assert _reader("index.device_gib")(types.SimpleNamespace()) is None
        spans.set_counter("session.index_bytes", 3 * 2**29)
        assert _reader("index.device_gib")(types.SimpleNamespace()) == 1.5
    finally:
        spans.reset()
    import repro.engine

    monkeypatch.setitem(sys.modules, "repro.engine.spans", None)
    monkeypatch.delattr(repro.engine, "spans", raising=False)
    assert _reader("index.device_gib")(types.SimpleNamespace()) is None


def test_workload_arg_reads_both_spellings():
    assert workload_arg(["--workload", "pe150_chr1to3", "--seed", "1"]) \
        == "pe150_chr1to3"
    assert workload_arg(["--seed", "1", "--workload=long_hifi15k"]) \
        == "long_hifi15k"
    assert workload_arg(["--seed", "1"]) is None


def test_unplaceable_index_runs_on_a_program_with_csr_lines():
    """This program places the 2^28-bucket index as CSR lines: the new
    cell and the chr1 cells pass the check; an unknown cell stops with
    the harness's own error."""
    for workload in ("pe150_chr1to3", "pe150_illumina"):
        refuse_unplaceable_index(REPO, workload)
    with pytest.raises(SystemExit, match="unknown workload"):
        refuse_unplaceable_index(REPO, "no_such_cell")


def test_unplaceable_index_refused_without_csr_lines(monkeypatch):
    """A program without CSR lines is stopped at once on the cell whose
    padded table (2^28 x 32 x 4 B) fits no chip, with status 1 and the
    reason; the chr1 cells, whose padded tables fit, are left alone."""
    import repro.core.seedmap as seedmap

    monkeypatch.delattr(seedmap, "LinedCSRSeedMap")
    with pytest.raises(SystemExit) as stop:
        refuse_unplaceable_index(REPO, "pe150_chr1to3")
    assert "3.436e+10 bytes" in str(stop.value.code)
    for workload in ("pe150_illumina", "pe150_higherr", "long_hifi15k"):
        refuse_unplaceable_index(REPO, workload)
