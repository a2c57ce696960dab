"""`pair_frontend` kernel time against its HBM roofline: the bytes the
front end must move for the window's rows, over its device time and the
chip's HBM bandwidth.  There is no compute term: the kernels do no
matmuls and the chip publishes no integer VPU peak.

Bytes per front-end row (a pair, or a long read's pseudo-pair), from
the shapes: both mates' reads as int32 into the bucket kernel, their
S bucket ids out and back in, S*K int32 location rows gathered per
mate, and C candidate pairs plus three counts out."""

PATTERN = r"pair_frontend(\.\d+)?"


def row_bytes(R: int, S: int, K: int, C: int) -> int:
    return 2 * R * 4 + 2 * S * 4 + 2 * S * 4 + 2 * S * K * 4 + 2 * C * 4 + 3 * 4


def rows_per_batch(cell) -> int:
    if cell.lane == "pairs":
        return cell.batch
    lr = cell.config["long_read"]
    n_seg = (cell.traffic["read_len"] - lr["segment_len"]) \
        // lr["segment_stride"] + 1
    return cell.batch * (n_seg - 1)


def read(run):
    if run.trace is None:
        return None
    t = run.trace.op_seconds(PATTERN)
    if t <= 0:
        return None
    p = run.cell.config["pipeline"]
    R = p["read_len"] if run.cell.lane == "pairs" \
        else run.cell.config["long_read"]["segment_len"]
    moved = run.n_batches * rows_per_batch(run.cell) * row_bytes(
        R, p["seeds_per_read"], p["max_locs_per_seed"], p["max_candidates"])
    return 100.0 * moved / t / run.peaks["hbm_bytes_per_s"]
