"""`candidate_align` kernel time against its HBM roofline.

Bytes per pair, from the shapes: both mates' reads as int32, the
2*C reference windows of R + 2E bases in the session's reference flavour
(int32 bases unpacked, 2-bit words packed), the kernel's per-candidate
tables (DMA line, lane offset, validity per mate) and 12 int32 results.
No compute term: the chip publishes no integer VPU peak."""

PATTERN = r"candidate_pair_align(\.\d+)?"


def pair_bytes(R: int, C: int, E: int, packed: bool) -> float:
    base = 0.25 if packed else 4
    return 2 * R * 4 + 2 * C * (R + 2 * E) * base + 6 * C * 4 + 12 * 4


def read(run):
    if run.trace is None or run.cell.lane != "pairs":
        return None
    t = run.trace.op_seconds(PATTERN)
    if t <= 0:
        return None
    p = run.cell.config["pipeline"]
    moved = run.n_batches * run.cell.batch * pair_bytes(
        p["read_len"], p["max_candidates"], p["max_gap"], p["packed_ref"])
    return 100.0 * moved / t / run.peaks["hbm_bytes_per_s"]
