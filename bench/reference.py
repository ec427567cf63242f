"""The plain reference: what the store must answer, kept with numpy
alone.  It imports nothing of the program.

A record's value is a fixed function of (key, version): version 0 for
the load, and an operation's id + 1 for each update or insert.  The
reference keeps each record's acknowledged version and the sorted set of
live keys, replays the calls in the order they were made, and says for
every answer what it should have been.
"""
from __future__ import annotations

import numpy as np


def value_of(keys, versions, words: int) -> np.ndarray:
    """[n, words] int32, the value of each key at its version: the same
    arithmetic, mod 2**32, as the benchmark's device-side generator."""
    k = np.asarray(keys, np.int64).astype(np.uint32)
    ver = (np.asarray(versions, np.int64) % 2 ** 32).astype(np.uint32)
    w = np.arange(words, dtype=np.uint32)
    with np.errstate(over="ignore"):
        v = (k[:, None] * np.uint32(2654435761) + w * np.uint32(0x9E3779B9)
             + (ver * np.uint32(0x85EBCA77))[:, None])
    return (v >> np.uint32(1)).astype(np.int32)


class Reference:
    """Record versions by record index, and the sorted live keys.

    ``keys[i]`` is record i's key; records ``>= loaded`` are inserted by
    the window, and count as live once their insert is acknowledged."""

    def __init__(self, keys, loaded: int):
        self.keys = np.asarray(keys, np.int32)
        self.loaded = loaded
        self.version = np.zeros(len(self.keys), np.int64)
        self.version[loaded:] = -1          # not inserted yet
        self.base = np.sort(self.keys[:loaded])
        self.inserted = np.zeros((0,), np.int32)   # sorted

    def write(self, recs, versions) -> None:
        recs = np.asarray(recs, np.int64)
        self.version[recs] = versions
        new = self.keys[recs[recs >= self.loaded]]
        if len(new):
            self.inserted = np.union1d(self.inserted, new).astype(np.int32)

    def expect_get(self, recs):
        """The version each record's GET must return; -1: not found."""
        return self.version[np.asarray(recs, np.int64)].copy()

    def expect_scan(self, start_key: int, length: int) -> np.ndarray:
        """The first ``length`` live keys >= start_key, in order."""
        a = self.base[np.searchsorted(self.base, start_key):][:length]
        b = self.inserted[np.searchsorted(self.inserted, start_key):][:length]
        return np.sort(np.concatenate([a, b]))[:length]

    def live_keys(self) -> np.ndarray:
        return np.sort(np.concatenate([self.base, self.inserted]))

    def live_count(self) -> int:
        return len(self.base) + len(self.inserted)
