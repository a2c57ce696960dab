"""Share of the traced window in which the `pair_frontend` kernels ran: the
summed device time of the operations the Pallas calls of `pair_frontend`
compile to, over the window."""

PATTERN = r"pair_frontend(\.\d+)?"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.op_seconds(PATTERN)
    if t <= 0:
        return None
    return 100.0 * t / run.trace.window_s
