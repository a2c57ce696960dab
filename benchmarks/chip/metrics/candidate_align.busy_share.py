"""Share of the traced window in which the `candidate_align` kernels ran: the
summed device time of the operations the Pallas calls of `candidate_pair_align`
compile to, over the window."""

PATTERN = r"candidate_pair_align(\.\d+)?"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.op_seconds(PATTERN)
    if t <= 0:
        return None
    return 100.0 * t / run.trace.window_s
