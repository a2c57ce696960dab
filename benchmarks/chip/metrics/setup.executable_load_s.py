"""Seconds of the stream warm-up spent getting the step's executable:
JAX's backend compilation under ``stream.warmup`` in the program's span
table, which on a persistent-cache hit is the cache retrieval (recorded
inside it, so not added again)."""

from chipbench.scopes import span_table

EVENT = "stream.warmup/backend_compile_duration"


def read(run):
    table = span_table()
    if not table or EVENT not in table:
        return None
    return table[EVENT]["seconds"]
