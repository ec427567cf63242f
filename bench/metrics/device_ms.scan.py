"""Device milliseconds of the scan program per SCAN call."""
from bench.trace import program_ms_per_call


def read(rec):
    return program_ms_per_call(rec, "scan", "scan")
