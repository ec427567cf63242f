"""The apply program's share of its roofline, in %: the bytes an apply
round must move (bench/roofline.py ``apply_bytes``) over its device
time at the chip's peak HBM bandwidth."""
from bench.trace import program_s


def read(rec):
    s = program_s(rec, "apply")
    bw = rec.get("peak_hbm_bytes_per_s")
    if not s or not bw or not rec.get("apply_bytes"):
        return None
    return rec["apply_bytes"] / (s * bw) * 100.0
