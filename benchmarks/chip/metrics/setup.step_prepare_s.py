"""Seconds of the stream warm-up spent preparing the step program:
JAX's jaxpr tracing and lowering to MLIR (Mosaic kernels included), as
the program's span table records them under ``stream.warmup``."""

from chipbench.scopes import span_table

EVENTS = ("stream.warmup/jaxpr_trace_duration",
          "stream.warmup/jaxpr_to_mlir_module_duration")


def read(run):
    table = span_table()
    if not table or not any(e in table for e in EVENTS):
        return None
    return sum(table[e]["seconds"] for e in EVENTS if e in table)
