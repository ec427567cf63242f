"""Mean host milliseconds per GET call, from the program's own span:
``histore.get`` minus the device busy time inside it (padding, syncs,
the lease tick, lane merges)."""
from bench.program_trace import per_call, span_total


def read(rec):
    v = per_call(rec, "get", lambda r: span_total(r, "histore.get")
                 - span_total(r, "histore.get", "busy_s"))
    return None if v is None else v * 1e3
