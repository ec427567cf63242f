"""Milliseconds per PUT call spent in drains the call forced (a full
backup log: apply rounds and gc rounds), ``histore.put.drain`` per
``histore.put`` span."""
from bench.program_trace import per_call, span_total


def read(rec):
    v = per_call(rec, "put", lambda r: span_total(r, "histore.put.drain"))
    return None if v is None else v * 1e3
