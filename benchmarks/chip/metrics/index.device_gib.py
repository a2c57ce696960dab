"""The session's index on the device, in GiB: the program's
``session.index_bytes`` counter, set when the session places its index
(the offsets plus the location lines of a CSR index, the row lines of a
padded one).  Absent where the program keeps no such counter."""


def read(run):
    try:
        from repro.engine import spans
    except ImportError:
        return None
    counters = spans.snapshot().get("counters") or {}
    if "session.index_bytes" not in counters:
        return None
    return counters["session.index_bytes"] / 2**30
