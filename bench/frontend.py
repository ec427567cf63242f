"""The open-loop front end: a batching server in front of a client that
has one call in flight.

Operations arrive on the schedule (due times fixed in set-up).  The front
end picks the group (get, put or scan) whose oldest pending operation is
oldest, and sends up to ``keys_per_call`` of that group's pending
operations, in arrival order, in one client call; a scan is a call of its
own.  A call never carries one key twice: reads of one key share a lane,
and writes of one key that wait together are combined in arrival order,
so the lane carries the last version and every write of it is
acknowledged when the call completes.  The number of distinct keys in a
call is one of ``call_sizes``, the sizes warmed up in set-up, so nothing
compiles inside the window.

An operation's latency runs from its due time to the moment its call's
results are ready, so a stall counts against every operation queued
behind it.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np

from bench.ycsb import INSERT, READ, SCAN, UPDATE, Schedule

GROUPS = ("get", "put", "scan")
GROUP_OF_KIND = {READ: "get", UPDATE: "put", INSERT: "put", SCAN: "scan"}
GRACE_S = 60.0   # operations due in the window are awaited this long past it


def no_span(name):
    return contextlib.nullcontext()


def call_sizes(keys_per_call: int) -> list:
    """Distinct-key counts a call may carry: 1..16, then 2**k and
    3 * 2**(k-1), then ``keys_per_call``.  A call takes the largest size
    that its pending operations fill, so at most a third of them wait for
    the next call."""
    s = set(range(1, 17))
    p = 16
    while p <= keys_per_call:
        s.update((p, 3 * p // 2))
        p *= 2
    s.add(keys_per_call)
    return sorted(x for x in s if x <= keys_per_call)


@dataclass
class Call:
    group: str
    ops: np.ndarray          # operation ids served, in arrival order
    recs: np.ndarray         # record of each lane (distinct)
    versions: np.ndarray     # put: the version each lane writes
    t_dispatch: float
    t_ready: float = float("nan")
    result: tuple = ()       # host copies of the answers, for the check


@dataclass
class Window:
    """What one window did: every call in order, and per operation the
    time its answer was ready (nan if never)."""
    sched: Schedule
    seconds: float
    grace: float = GRACE_S
    calls: list = field(default_factory=list)
    done: np.ndarray = None

    def latencies(self, kinds) -> np.ndarray:
        """Seconds from due to ready for the operations of ``kinds``; an
        operation never answered counts the whole wait, from due to the
        close of the grace period."""
        sel = np.isin(self.sched.kind, kinds)
        lat = self.done[sel] - self.sched.due[sel]
        never = np.isnan(lat)
        lat[never] = self.seconds + self.grace - self.sched.due[sel][never]
        return lat

    def completed_in_window(self) -> int:
        return int((self.done <= self.seconds).sum())

    def backlog_at_close(self) -> int:
        """Operations due in the window that were not answered by its
        close."""
        return int((~(self.done <= self.seconds)).sum())

    def unanswered(self) -> int:
        return int(np.isnan(self.done).sum())

    def calls_by_group(self) -> dict:
        out = {g: 0 for g in GROUPS}
        for c in self.calls:
            out[c.group] += 1
        return out


class FrontEnd:
    """Serves one schedule against ``server`` (``get(keys)``,
    ``put(keys, versions)``, ``scan(start_key)``, each returning the
    device arrays to keep and the array to wait on)."""

    def __init__(self, server, keys: np.ndarray, keys_per_call: int,
                 keep_values: float, rng, span=None):
        self.server = server
        self.keys = keys
        self.B = keys_per_call
        self.sizes = np.asarray(call_sizes(keys_per_call))
        self.keep_values = keep_values
        self.rng = rng
        self.span = span or no_span

    def _take(self, ops: np.ndarray, rec: np.ndarray, last: bool):
        """The prefix of pending ``ops`` whose distinct records fill the
        largest allowed call size; returns (ops taken, lane records, and
        with ``last`` the op id each lane's record was last written by)."""
        seg = ops[: 2 * self.B]
        r = rec[seg]
        uniq, first = np.unique(r, return_index=True)
        order = np.sort(first)
        u = len(order)
        s = int(self.sizes[np.searchsorted(self.sizes, min(u, self.B),
                                           "right") - 1])
        cut = int(order[s]) if s < u else len(seg)
        taken = seg[:cut]
        if not last:
            return taken, uniq[first < cut], None
        lanes, rev_first = np.unique(r[:cut][::-1], return_index=True)
        return taken, lanes, taken[cut - 1 - rev_first]

    def serve(self, sched: Schedule, seconds: float, t0: float,
              clock=time.perf_counter, grace: float = GRACE_S) -> Window:
        """Run the window that opens at ``t0`` (on ``clock``): every
        operation due in it is served, those late past its close for up to
        ``grace`` seconds more."""
        w = Window(sched, seconds, grace)
        n = len(sched)
        w.done = np.full(n, np.nan)
        grp = np.array([GROUPS.index(GROUP_OF_KIND[int(k)])
                        for k in range(4)])[sched.kind]
        pend = [np.nonzero(grp == i)[0] for i in range(len(GROUPS))]
        dues = [sched.due[p] for p in pend]
        cur = [0] * len(GROUPS)
        stop = seconds + grace
        while True:
            now = clock() - t0
            if now > stop:
                break
            best, best_due, nxt = -1, np.inf, np.inf
            for i in range(len(GROUPS)):
                if cur[i] < len(pend[i]):
                    d = dues[i][cur[i]]
                    if d <= now and d < best_due:
                        best, best_due = i, d
                    nxt = min(nxt, d)
            if best < 0:
                if nxt == np.inf:
                    break
                with self.span("wait.arrival"):
                    time.sleep(max(0.0, nxt - now))
                continue
            g = GROUPS[best]
            with self.span("frontend"):
                hi = int(np.searchsorted(dues[best], now, "right"))
                ops = pend[best][cur[best]:hi]
                if g == "scan":
                    ops = ops[:1]
                    lanes = sched.rec[ops]
                    vers = np.zeros(0, np.int64)
                else:
                    ops, lanes, lastop = self._take(ops, sched.rec,
                                                    g == "put")
                    vers = lastop + 1 if g == "put" else \
                        np.zeros(0, np.int64)
                cur[best] += len(ops)
                lane_keys = self.keys[lanes]
            call = Call(g, ops, lanes, vers, clock() - t0)
            with self.span(f"call.{g}"):
                if g == "get":
                    keep, wait = self.server.get(
                        lane_keys, self.rng.random() < self.keep_values)
                elif g == "put":
                    keep, wait = self.server.put(lane_keys,
                                                 vers.astype(np.int32))
                else:
                    keep, wait = self.server.scan(int(lane_keys[0]))
                wait.block_until_ready()
            call.t_ready = clock() - t0
            # the answers kept for the check move to the host at once:
            # hundreds of small buffers held on the device through the
            # window would lie scattered among the store's multi-GiB ones
            call.result = tuple(None if x is None else np.asarray(x)
                                for x in keep)
            w.done[ops] = call.t_ready
            w.calls.append(call)
        return w
