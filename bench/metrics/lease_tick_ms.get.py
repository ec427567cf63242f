"""Milliseconds of lease aging per GET call: the ``histore.get.lease_tick``
span (tick launch, heartbeat fetch, aging) per ``histore.get`` span."""
from bench.program_trace import per_call, span_total


def read(rec):
    v = per_call(rec, "get",
                 lambda r: span_total(r, "histore.get.lease_tick"))
    return None if v is None else v * 1e3
