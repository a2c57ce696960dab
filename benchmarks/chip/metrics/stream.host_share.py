"""Share of the traced window the stream loop's host work took: the
union of the program's ``stream.pad``, ``stream.dispatch`` and
``stream.retire`` spans on the benchmark thread, over the window.  What
is left is the host's headroom before it would hold the device back."""

from chipbench.tracing import union_length

SPANS = ("stream.pad", "stream.dispatch", "stream.retire")


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    w0, w1 = run.trace.window.start, run.trace.window.end
    mine = [(max(h.start, w0), min(h.end, w1)) for h in run.trace.host
            if h.name in SPANS and h.end > w0 and h.start < w1]
    if not mine:
        return None
    return 100.0 * union_length(mine) / 1e9 / run.trace.window_s
