"""Share of the traced window in which the `residual_dp` kernels ran: the
summed device time of the operations the Pallas calls of `residual_pair_dp`
compile to, over the window."""

PATTERN = r"residual_pair_dp(\.\d+)?"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.op_seconds(PATTERN)
    if t <= 0:
        return None
    return 100.0 * t / run.trace.window_s
