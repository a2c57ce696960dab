"""`residual_dp` kernel time against its HBM roofline.

Bytes per DP'd mate (StageStats ``dp_mate_alignments`` over the
window): its read as int32, its reference window of R + 2*dp_pad bases
in the session's reference flavour, and its score and end out.  No
compute term: the chip publishes no integer VPU peak."""

PATTERN = r"residual_pair_dp(\.\d+)?"


def mate_bytes(R: int, dp_pad: int, packed: bool) -> float:
    base = 0.25 if packed else 4
    return R * 4 + (R + 2 * dp_pad) * base + 2 * 4


def read(run):
    if run.trace is None or run.cell.lane != "pairs":
        return None
    t = run.trace.op_seconds(PATTERN)
    mates = run.totals.get("dp_mate_alignments", 0)
    if t <= 0 or mates <= 0:
        return None
    p = run.cell.config["pipeline"]
    moved = mates * mate_bytes(p["read_len"], p["dp_pad"], p["packed_ref"])
    return 100.0 * moved / t / run.peaks["hbm_bytes_per_s"]
