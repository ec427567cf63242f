"""Device milliseconds per background apply round."""
from bench.trace import program_s


def read(rec):
    s = program_s(rec, "apply")
    return None if s is None else s / rec["programs"]["apply"]["count"] * 1e3
