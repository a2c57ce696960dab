"""Share of the device's busy time in ops under none of the program's
stage scopes (`chipbench.scopes.STAGES`): the completeness check on the
scopes, so that every other device metric of a stage sees its ops."""

from chipbench.scopes import scoped_ops, stages_of


def read(run):
    ops = scoped_ops(run)
    busy = run.trace.busy_s() if run.trace is not None else 0.0
    if ops is None or busy <= 0:
        return None
    return 100.0 * ops.seconds(lambda path: not stages_of(path)) / busy
