"""Device milliseconds of the put program per PUT call."""
from bench.trace import program_ms_per_call


def read(rec):
    return program_ms_per_call(rec, "put", "put")
