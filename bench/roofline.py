"""Peaks of the chips the benchmark runs on, and the bytes an operation
must move whatever implements it.

Peaks: Google Cloud documentation, "TPU v5e" (system architecture):
16 GB of HBM at 819 GB/s per chip.  A ``device_kind`` that is not in the
table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

KEY_BYTES = 4          # int32 keys
ADDR_BYTES = 4         # int32 value addresses
OP_BYTES = 1           # int8 log opcode
SLOT_BYTES = 12        # hash slot: signature, fingerprint, address (int32)
BUCKET_SLOTS = 8       # one 64 B bucket of the paper: 8 slots


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add "
                       f"them to bench/roofline.py with their source"
                       ) from None


def get_bytes(keys: int, value_words: int) -> int:
    """A GET of ``keys`` distinct keys: each key read in, one bucket of
    the hash read, the value read from the store and written out."""
    per = KEY_BYTES + BUCKET_SLOTS * SLOT_BYTES + 2 * 4 * value_words
    return keys * per


def apply_bytes(entries: int) -> int:
    """An apply round that takes ``entries`` log entries (summed over the
    replicas): each entry read from its log and written into its
    replica."""
    return entries * ((KEY_BYTES + ADDR_BYTES + OP_BYTES)
                      + (KEY_BYTES + ADDR_BYTES))
