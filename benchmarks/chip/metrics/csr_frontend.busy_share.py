"""Share of the traced window in which the front end gathered from a CSR
index: the device time of the `pair_frontend` launches plus that of the
ops under the program's ``index_offsets`` scope (each seed's two Seed
Table offsets looked up, and the kernels' start and count tables made
from them), over the window.  Absent where no op carries that scope: the
front end did not gather from CSR lines."""

from chipbench.scopes import scoped_ops

PATTERN = r"pair_frontend(\.\d+)?"


def frontend_seconds(run):
    """Device seconds of the CSR front end in the window, or None."""
    ops = scoped_ops(run)
    if ops is None:
        return None
    offsets = ops.seconds(lambda path: "index_offsets" in path.split("/")[:-1])
    if offsets <= 0:
        return None
    return run.trace.op_seconds(PATTERN) + offsets


def read(run):
    t = frontend_seconds(run)
    if t is None:
        return None
    return 100.0 * t / run.trace.window_s
