"""The front end against a fake server: no key twice in one call, writes
of one key combined in arrival order, call sizes from the warmed set,
and latency from the due time."""
from __future__ import annotations

import time

import numpy as np

from bench import frontend as fe
from bench import ycsb


class FakeArray:
    def block_until_ready(self):
        return self


class FakeServer:
    """Answers at once, or after ``delay`` seconds; records every call."""

    def __init__(self, delay: float = 0.0):
        self.delay = delay
        self.calls = []

    def _call(self, kind, keys, versions=None):
        self.calls.append((kind, np.array(keys), None if versions is None
                           else np.array(versions)))
        if self.delay:
            time.sleep(self.delay)
        return (), FakeArray()

    def get(self, keys, keep):
        return self._call("get", keys)

    def put(self, keys, versions):
        return self._call("put", keys, versions)

    def scan(self, start):
        return self._call("scan", [start])


def _sched(kinds, recs, due):
    n = len(kinds)
    return ycsb.Schedule(np.asarray(due, float), np.asarray(kinds, np.int8),
                         np.asarray(recs, np.int64), np.ones(n, np.int32))


def test_call_sizes():
    s = fe.call_sizes(16384)
    assert s[:16] == list(range(1, 17)) and s[-1] == 16384
    assert 24 in s and 12288 in s and 20 not in s
    assert fe.call_sizes(64)[-2:] == [48, 64]
    # a call waits behind at most a third of the pending keys
    for u in range(1, 16385):
        k = max(x for x in s if x <= u)
        assert k >= u * 2 / 3


def test_one_key_once_per_call_and_last_write_wins():
    keys = np.arange(100, 200, dtype=np.int32)
    # ten writes of record 3, interleaved with others, all due at once
    recs = [3, 5, 3, 7, 3, 3, 9, 3, 3, 3, 3, 3]
    sched = _sched([ycsb.UPDATE] * len(recs), recs, [0.0] * len(recs))
    srv = FakeServer()
    w = fe.FrontEnd(srv, keys, 64, 0.0, np.random.default_rng(0)).serve(
        sched, 0.2, time.perf_counter())
    assert len(srv.calls) == 1
    kind, k, v = srv.calls[0]
    assert kind == "put" and len(k) == len(set(k.tolist())) == 4
    byk = dict(zip(k.tolist(), v.tolist()))
    # versions are op id + 1: record 3's last write is op 11
    assert byk[103] == 12 and byk[105] == 2 and byk[107] == 4
    assert byk[109] == 7
    assert np.isfinite(w.done).all()


def test_calls_take_allowed_sizes_in_arrival_order():
    keys = np.arange(1000, dtype=np.int32)
    recs = list(range(21))                      # 21 distinct reads
    sched = _sched([ycsb.READ] * 21, recs, [0.0] * 21)
    srv = FakeServer()
    fe.FrontEnd(srv, keys, 64, 0.0, np.random.default_rng(0)).serve(
        sched, 0.2, time.perf_counter())
    sizes = [len(k) for _, k, _ in srv.calls]
    assert sizes == [16, 5]
    assert srv.calls[0][1].tolist() == list(range(16))


def test_oldest_pending_group_goes_first_and_scans_alone():
    keys = np.arange(100, dtype=np.int32)
    sched = _sched([ycsb.SCAN, ycsb.INSERT, ycsb.SCAN, ycsb.SCAN],
                   [1, 50, 2, 3], [0.0, 0.001, 0.002, 0.003])
    srv = FakeServer()
    fe.FrontEnd(srv, keys, 64, 0.0, np.random.default_rng(0)).serve(
        sched, 0.2, time.perf_counter())
    assert [c[0] for c in srv.calls] == ["scan", "put", "scan", "scan"]


def test_latency_runs_from_the_due_time():
    """A slow call delays every operation queued behind it: the second
    read, due at 0, waits for the first call and its own."""
    keys = np.arange(10, dtype=np.int32)
    sched = _sched([ycsb.UPDATE, ycsb.READ], [1, 2], [0.0, 0.001])
    srv = FakeServer(delay=0.05)
    w = fe.FrontEnd(srv, keys, 64, 0.0, np.random.default_rng(0)).serve(
        sched, 0.5, time.perf_counter())
    lat = w.done - sched.due
    assert 0.05 <= lat[0] < 0.09
    assert 0.099 <= lat[1] < 0.16
    assert w.completed_in_window() == 2 and w.backlog_at_close() == 0
    assert w.calls_by_group() == {"get": 1, "put": 1, "scan": 0}


def test_backlog_counts_what_missed_the_close():
    keys = np.arange(10, dtype=np.int32)
    sched = _sched([ycsb.READ] * 3, [1, 2, 3], [0.0, 0.01, 0.02])
    srv = FakeServer(delay=0.06)
    w = fe.FrontEnd(srv, keys, 1, 0.0, np.random.default_rng(0)).serve(
        sched, 0.1, time.perf_counter())
    assert w.completed_in_window() == 1
    assert w.backlog_at_close() == 2 and w.unanswered() == 0
