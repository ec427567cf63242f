"""Blocking device-to-host reads per GET call: ``histore.get.sync``
spans per ``histore.get`` span (each sync also counts ``get.syncs``)."""
from bench.program_trace import per_call, span_total


def read(rec):
    return per_call(rec, "get",
                    lambda r: span_total(r, "histore.get.sync", "count"))
