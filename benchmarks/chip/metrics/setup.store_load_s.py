"""Seconds the session took to come up from its index store: the
program's ``session.load`` span (`Mapper.load`: reading the store into
host arrays, resolving the session and dispatching its placement)."""

from chipbench.scopes import span_table


def read(run):
    table = span_table()
    if not table or "session.load" not in table:
        return None
    return table["session.load"]["seconds"]
