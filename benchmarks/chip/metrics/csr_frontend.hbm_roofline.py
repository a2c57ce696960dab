"""The CSR front end's time against its HBM roofline: the bytes it must
move for the window's rows, over its device time (the `pair_frontend`
launches and the ops under ``index_offsets``, as
``csr_frontend.busy_share`` counts them) and the chip's HBM bandwidth.
There is no compute term: the kernels do no matmuls and the chip
publishes no integer VPU peak.

Bytes per pair: what the padded-row front end moves
(``pair_frontend.hbm_roofline``'s count), plus both ends of every
seed's bucket read from the Seed Table: 2 mates x S seeds x 2 offsets
x 4 B."""

import importlib.util
from pathlib import Path


def _sibling(metric: str):
    """The reader module ``metrics/<metric>.py`` beside this one."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}",
        Path(__file__).with_name(f"{metric}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


frontend_seconds = _sibling("csr_frontend.busy_share").frontend_seconds
_padded_row_bytes = _sibling("pair_frontend.hbm_roofline").row_bytes


def row_bytes(R: int, S: int, K: int, C: int) -> int:
    return _padded_row_bytes(R, S, K, C) + 2 * S * 2 * 4


def read(run):
    if run.cell.lane != "pairs":
        return None
    t = frontend_seconds(run)
    if t is None:
        return None
    p = run.cell.config["pipeline"]
    moved = run.n_batches * run.cell.batch * row_bytes(
        p["read_len"], p["seeds_per_read"], p["max_locs_per_seed"],
        p["max_candidates"])
    return 100.0 * moved / t / run.peaks["hbm_bytes_per_s"]
