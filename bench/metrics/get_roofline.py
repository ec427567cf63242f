"""The GET program's share of its roofline, in %: the bytes a GET must
move (bench/roofline.py ``get_bytes``) over its device time at the
chip's peak HBM bandwidth.  GETs are bound by bytes."""
from bench.trace import program_s


def read(rec):
    s = program_s(rec, "get")
    bw = rec.get("peak_hbm_bytes_per_s")
    if not s or not bw or not rec.get("get_bytes"):
        return None
    return rec["get_bytes"] / (s * bw) * 100.0
