"""YCSB traffic generators (Cooper et al., SoCC 2010; the YCSB core
package's ``ScrambledZipfianGenerator``, ``ZipfianGenerator`` and
``Utils.fnvhash64``), vectorised with numpy, and the open-loop schedule
of one window.

Every schedule of a cell has the same number of operations, the same
count of each kind, the same multiset of scan lengths and the same
multiset of inter-arrival gaps (exponential, as a Poisson process has),
whatever the seed: the seed chooses their order and the keys, so that
seeds change which work is done and when, not how much.  A kind that a
mix lists under ``evenly_spaced`` (YCSB-E's rare inserts, each of which
makes the next scan drain the logs) falls at evenly spaced positions, so
its spacing does not change with the seed either.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

KEY_SPACE = 2 ** 31 - 2      # int32 keys below the sorted index's sentinel
ZIPF_ITEMS = 10_000_000_000  # ScrambledZipfianGenerator.ITEM_COUNT
ZIPF_ZETAN = 26.46902820178302  # its precomputed zeta(ITEM_COUNT, 0.99)
FNV_OFFSET_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)

READ, UPDATE, INSERT, SCAN = 0, 1, 2, 3
KINDS = {"read": READ, "update": UPDATE, "insert": INSERT, "scan": SCAN}


def fnvhash64(v) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the 8 bytes of a long,
    low byte first, then ``Math.abs``."""
    v = np.asarray(v, np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * FNV_PRIME_64
            v = v >> np.uint64(8)
    s = h.view(np.int64)
    # Math.abs(Long.MIN_VALUE) stays negative in Java; it cannot index
    # a record, so it folds to 0 here
    return np.where(s == np.iinfo(np.int64).min, 0, np.abs(s))


def zipf_ranks(rng, n: int, theta: float = 0.99,
               items: int = ZIPF_ITEMS, zetan: float = ZIPF_ZETAN):
    """``ZipfianGenerator.nextLong`` (Gray et al.'s method): n ranks in
    [0, items), rank 0 the most popular."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(n)
    uz = u * zetan
    r = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    r = np.where(uz < zeta2, 1, r)
    r = np.where(uz < 1.0, 0, r)
    return np.minimum(r, items - 1)


def scrambled_zipfian(rng, n: int, itemcount: int,
                      theta: float = 0.99) -> np.ndarray:
    """``ScrambledZipfianGenerator``: zipfian ranks over ITEM_COUNT items,
    scattered over [0, itemcount) by fnvhash64."""
    return fnvhash64(zipf_ranks(rng, n, theta)) % itemcount


def draw_keys(rng, n: int) -> np.ndarray:
    """n distinct int32 keys in [0, KEY_SPACE), in random order."""
    k = np.unique(rng.integers(0, KEY_SPACE, int(n * 1.1) + 1024,
                               dtype=np.int64))
    while len(k) < n:
        k = np.union1d(k, rng.integers(0, KEY_SPACE, n, dtype=np.int64))
    return rng.permutation(k)[:n].astype(np.int32)


@dataclass
class Schedule:
    """One window's operations, in arrival order.

    ``due`` seconds after the window opens; ``kind`` READ/UPDATE/INSERT/
    SCAN; ``rec`` the record index (a key is ``keys[rec]``, an insert's
    record is new); ``length`` the scan length (0 for other kinds)."""
    due: np.ndarray
    kind: np.ndarray
    rec: np.ndarray
    length: np.ndarray

    def __len__(self) -> int:
        return len(self.due)


def arrivals(rng, n: int, seconds: float) -> np.ndarray:
    """n due times in [0, seconds): exponential inter-arrival gaps, as a
    Poisson process has, taken as the n quantiles of the exponential
    distribution in seeded order, so every seed has the same gaps."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps)
    t = np.cumsum(gaps)
    return t / (t[-1] + gaps.mean()) * seconds


def exact_counts(n: int, proportions: dict) -> dict:
    """Split n operations by proportion, largest remainders first, so
    every seed gets the same count of each kind."""
    names = [k for k, p in proportions.items() if p > 0]
    raw = np.array([proportions[k] * n for k in names], float)
    cnt = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - cnt))[: n - cnt.sum()]:
        cnt[i] += 1
    return dict(zip(names, cnt.tolist()))


def place_kinds(rng, n: int, counts: dict, even=()) -> np.ndarray:
    """The kind of each of n operations.  Kinds named in ``even`` fall at
    evenly spaced positions, from one seeded phase, so every seed spaces
    them alike; the rest take the other positions in seeded order."""
    kind = np.full(n, -1, np.int8)
    for k in even:
        c = counts.get(k, 0)
        if c:
            free = np.nonzero(kind < 0)[0]
            at = ((np.arange(c) + rng.random()) * len(free) / c).astype(int)
            kind[free[at]] = KINDS[k]
    rest = np.concatenate([np.full(c, KINDS[k], np.int8)
                           for k, c in counts.items() if k not in even])
    kind[kind < 0] = rng.permutation(rest)
    return kind


def schedule(rng, traffic: dict, rate: float, seconds: float,
             records: int) -> Schedule:
    """The open-loop schedule of one window at ``rate`` ops/s."""
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate} over {seconds} s offers no operation")
    due = arrivals(rng, n, seconds)
    counts = exact_counts(n, traffic["proportions"])
    kind = place_kinds(rng, n, counts, traffic.get("evenly_spaced", ()))
    chooser = traffic["request_distribution"]
    if chooser != "scrambled_zipfian":
        raise ValueError(f"unknown request_distribution {chooser!r}")
    theta = float(traffic.get("zipfian_constant", 0.99))
    ins = kind == INSERT
    # an insert creates the next record: YCSB's transaction insert key
    # sequence, whose keys are hashed (orderedinserts=false)
    inserted_before = np.cumsum(ins) - ins
    rec = np.zeros(n, np.int64)
    rec[ins] = records + np.arange(int(ins.sum()))
    other = ~ins
    if traffic.get("choose_from_inserted", False):
        # CoreWorkload.nextKeynum: the chooser spans the records expected
        # by the end of the run and redraws any record not inserted yet
        expected = int(ins.sum())
        span = records + 2 * expected
        live = records + inserted_before
        idx = np.nonzero(other)[0]
        r = scrambled_zipfian(rng, len(idx), span, theta)
        bad = r >= live[idx]
        while bad.any():
            r[bad] = scrambled_zipfian(rng, int(bad.sum()), span, theta)
            bad = r >= live[idx]
        rec[idx] = r
    else:
        rec[other] = scrambled_zipfian(rng, int(other.sum()), records, theta)
    length = np.zeros(n, np.int32)
    sc = kind == SCAN
    if sc.any():
        lo, hi = traffic["scan_length"]["min"], traffic["scan_length"]["max"]
        span = hi - lo + 1
        # uniform lengths as an even multiset, in seeded order
        length[sc] = rng.permutation(lo + np.arange(int(sc.sum())) % span)
    return Schedule(due, kind, rec, length)
