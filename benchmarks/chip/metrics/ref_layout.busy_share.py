"""Share of the traced window spent laying out the reference for the
aligners' window DMAs: the device time of the ops under the program's
``ref_layout`` scope (the int32 cast, the edge padding and the cut into
128-lane lines, remade in every step by `candidate_align` and
`residual_dp`), over the window."""

from chipbench.scopes import scoped_ops


def read(run):
    ops = scoped_ops(run)
    if ops is None:
        return None
    t = ops.seconds(lambda path: "ref_layout" in path.split("/")[:-1])
    if t <= 0:
        return None
    return 100.0 * t / run.trace.window_s
