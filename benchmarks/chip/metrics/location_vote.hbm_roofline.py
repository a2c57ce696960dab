"""`location_vote` kernel time against its HBM roofline.

Bytes per long read: its (S-1)*C candidate diagonals as int32, padded
to whole 128-lane lines, in; winning bin, votes and a flag out.  No
compute term: the chip publishes no integer VPU peak."""

PATTERN = r"location_vote(\.\d+)?"


def read_bytes(n_seg: int, C: int) -> int:
    m = (n_seg - 1) * C
    return (m + (-m) % 128) * 4 + 3 * 4


def read(run):
    if run.trace is None or run.cell.lane != "long":
        return None
    t = run.trace.op_seconds(PATTERN)
    if t <= 0:
        return None
    lr = run.cell.config["long_read"]
    n_seg = (run.cell.traffic["read_len"] - lr["segment_len"]) \
        // lr["segment_stride"] + 1
    moved = run.n_batches * run.cell.batch * read_bytes(
        n_seg, run.cell.config["pipeline"]["max_candidates"])
    return 100.0 * moved / t / run.peaks["hbm_bytes_per_s"]
