"""The YCSB generators: YCSB's own hash and zipfian arithmetic, the
key sets, the scan lengths, and the same work from every seed."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import ycsb
from bench.spec import ROOT

TRAFFIC = {n: json.loads((ROOT / "bench" / "traffic" / f"{n}.json")
                         .read_text()) for n in ("ycsb-a", "ycsb-c",
                                                 "ycsb-e")}


def _fnv_java(v: int) -> int:
    """Utils.fnvhash64 as YCSB writes it, in Python integers."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        v >>= 8
        h = (h * 1099511628211) % 2 ** 64
    s = h - 2 ** 64 if h >= 2 ** 63 else h
    return abs(s)


def test_fnvhash64_matches_the_java_loop():
    vals = [0, 1, 2, 255, 256, 12345, 2 ** 31 - 1, 10 ** 10 - 1]
    assert ycsb.fnvhash64(np.array(vals)).tolist() == [_fnv_java(v)
                                                       for v in vals]


def test_zipfian_rank_frequencies():
    """P(rank r) = 1 / (r+1)^theta / zeta(ITEM_COUNT): rank 0 takes
    1/26.469 of the draws, rank 1 2^-0.99 of that."""
    rng = np.random.default_rng(0)
    r = ycsb.zipf_ranks(rng, 400_000)
    p0 = (r == 0).mean()
    p1 = (r == 1).mean()
    assert p0 == pytest.approx(1 / ycsb.ZIPF_ZETAN, rel=0.03)
    assert p1 == pytest.approx(0.5 ** 0.99 / ycsb.ZIPF_ZETAN, rel=0.05)
    assert r.max() < ycsb.ZIPF_ITEMS


def test_scrambled_zipfian_spreads_the_hot_records():
    rng = np.random.default_rng(1)
    n = 1 << 20
    idx = ycsb.scrambled_zipfian(rng, 200_000, n)
    assert idx.min() >= 0 and idx.max() < n
    counts = np.bincount(idx, minlength=n)
    hot = np.argsort(-counts)[:2]
    # the two hottest records are fnvhash64(0) and fnvhash64(1) mod n,
    # not records 0 and 1
    assert set(hot.tolist()) == {_fnv_java(0) % n, _fnv_java(1) % n}


def test_draw_keys_distinct_below_the_sentinel():
    k = ycsb.draw_keys(np.random.default_rng(2), 50_000)
    assert k.dtype == np.int32 and len(np.unique(k)) == 50_000
    assert k.min() >= 0 and k.max() < ycsb.KEY_SPACE


@pytest.mark.parametrize("mix", sorted(TRAFFIC))
def test_every_seed_gets_the_same_work(mix):
    """Same count, same count of each kind, same scan lengths; the seed
    changes only order, keys and arrival times."""
    a = ycsb.schedule(np.random.default_rng(2 ** 31 + 7), TRAFFIC[mix],
                      500.0, 4.0, 10_000)
    b = ycsb.schedule(np.random.default_rng(3), TRAFFIC[mix],
                      500.0, 4.0, 10_000)
    assert len(a) == len(b) == 2000
    assert np.bincount(a.kind, minlength=4).tolist() == \
        np.bincount(b.kind, minlength=4).tolist()
    assert sorted(a.length.tolist()) == sorted(b.length.tolist())
    assert (np.diff(a.due) >= 0).all() and 0 < a.due[0] and a.due[-1] < 4.0
    assert np.allclose(np.sort(np.diff(a.due, prepend=0.0)),
                       np.sort(np.diff(b.due, prepend=0.0)))
    assert not np.array_equal(a.rec, b.rec)
    assert not np.array_equal(a.due, b.due)


def test_arrival_gaps_are_exponential():
    due = ycsb.arrivals(np.random.default_rng(4), 20_000, 10.0)
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(10.0 / 20_000, rel=1e-3)
    # an exponential's standard deviation equals its mean
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.05)
    # the gaps are in seeded order, not sorted
    assert not (np.diff(gaps) >= 0).all()


@pytest.mark.parametrize("mix", sorted(TRAFFIC))
def test_schedule_reproduces_from_the_seed(mix):
    a = ycsb.schedule(np.random.default_rng(11), TRAFFIC[mix], 300.0, 2.0,
                      5000)
    b = ycsb.schedule(np.random.default_rng(11), TRAFFIC[mix], 300.0, 2.0,
                      5000)
    for f in ("due", "kind", "rec", "length"):
        assert np.array_equal(getattr(a, f), getattr(b, f))


def test_ycsb_e_inserts_and_scans():
    """Inserts make new records in order; a scan starts only at a record
    inserted before it; lengths are uniform over 1..100."""
    s = ycsb.schedule(np.random.default_rng(5), TRAFFIC["ycsb-e"], 2000.0,
                      2.0, 3000)
    ins = s.kind == ycsb.INSERT
    sc = s.kind == ycsb.SCAN
    assert ins.sum() == 200 and sc.sum() == 3800
    assert s.rec[ins].tolist() == list(range(3000, 3200))
    live = 3000 + np.cumsum(ins) - ins
    assert (s.rec[sc] < live[sc]).all()
    assert s.length[sc].min() == 1 and s.length[sc].max() == 100
    assert np.bincount(s.length[sc])[1:].std() < 1.0
    assert (s.length[~sc] == 0).all()


def test_ycsb_a_and_c_read_loaded_records():
    for mix, want in (("ycsb-a", {0: 1000, 1: 1000}), ("ycsb-c", {0: 2000})):
        s = ycsb.schedule(np.random.default_rng(6), TRAFFIC[mix], 1000.0,
                          2.0, 4096)
        assert dict(zip(*np.unique(s.kind, return_counts=True))) == want
        assert s.rec.max() < 4096


def test_evenly_spaced_kinds_keep_their_spacing_across_seeds():
    """YCSB-E's inserts fall every 20th operation, give or take one, on
    every seed; the scans fill the rest."""
    gaps = []
    for seed in (7, 2 ** 31 + 9):
        s = ycsb.schedule(np.random.default_rng(seed), TRAFFIC["ycsb-e"],
                          100.0, 20.0, 5000)
        at = np.nonzero(s.kind == ycsb.INSERT)[0]
        assert len(at) == 100 and (s.kind != ycsb.INSERT).sum() == 1900
        gaps.append(np.diff(at))
    for g in gaps:
        assert g.min() >= 19 and g.max() <= 21
