"""Device milliseconds of the get program per GET call."""
from bench.trace import program_ms_per_call


def read(rec):
    return program_ms_per_call(rec, "get", "get")
