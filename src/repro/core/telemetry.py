"""Ops telemetry plane: latency histograms, counters, gauges, spans.

The paper's core claim is a latency decomposition (indexing is up to 74%
of op latency; §6 reports percentiles, not means), yet the repro could
only report mean wall-clock per figure script and had no visibility into
how often the degraded paths actually fire (retries, second-hop GETs,
lease demotions).  This module is the one low-overhead plane the whole
stack reports through:

  * ``LatencyHistogram`` — log2-bucketed (1 µs granularity floor) with a
    fixed numpy bucket array: ``record()`` is allocation-free on the hot
    path (one integer bit-length + three scalar updates), percentiles
    (p50/p95/p99/max) are extracted at snapshot time;
  * ``Telemetry`` — counters, per-op histograms and spans, keyed on
    ``cfg.telemetry``:
        "off"       count/observe/span are no-ops; a snapshot taken
                    before equals one taken after any workload;
        "counters"  counters + latency histograms + spans (the default);
  * spans — ``jax.profiler.TraceAnnotation``s named ``histore.<op>`` for
    a client call and ``histore.<op>.<phase>`` inside it (``prepare``,
    ``launch.<program>``, ``sync``, ``lease_tick``, ``merge``, ``drain``,
    ``retry``).  They land in the profiler's trace on the same clock as
    the device's events, so a device-idle gap is named by the phase the
    host was in; with no profiler session running they record nothing;
  * ``MetricsSnapshot`` — the typed result of ``client.metrics()``, with
    ``render_text`` producing Prometheus text exposition format for
    ``client.metrics_text()``.

Counters make no device access.  Gauges (pending-log depth, free-queue
occupancy, live servers, ``fq_spill``) are NOT sampled on the hot path:
backends surface them lazily at snapshot time via ``telemetry_gauges()``
(one device fetch), so enabling telemetry never adds a device sync to an
op body.  The op path's own device->host reads go through ``sync``,
which names and counts them (``<op>.syncs``) and adds none.
"""
from __future__ import annotations

import contextlib
import json
import math
import threading
from typing import NamedTuple, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

MODES = ("off", "counters")
SPAN_PREFIX = "histore"
_NO_SPAN = contextlib.nullcontext()     # "off" mode: one shared no-op

# log2 buckets over microseconds: bucket 0 is < 1 µs, bucket i >= 1 is
# [2^(i-1), 2^i) µs; 48 buckets reach ~1.6e8 s — any op fits
N_BUCKETS = 48


class LatencySnapshot(NamedTuple):
    """Percentile summary of one op's latency histogram (seconds)."""
    count: int
    total: float
    mean: float
    p50: float
    p95: float
    p99: float
    max: float


class LatencyHistogram:
    """Log-bucketed latency histogram with an allocation-free record
    path: a preallocated int64 bucket array plus three scalars.  NOT
    thread-safe on its own — ``Telemetry`` serializes access."""

    __slots__ = ("buckets", "n", "total", "max")

    def __init__(self):
        self.buckets = np.zeros((N_BUCKETS,), np.int64)
        self.n = 0
        self.total = 0.0
        self.max = 0.0

    def record(self, seconds: float) -> None:
        us = seconds * 1e6
        i = int(us).bit_length() if us >= 1.0 else 0
        if i >= N_BUCKETS:
            i = N_BUCKETS - 1
        self.buckets[i] += 1
        self.n += 1
        self.total += seconds
        if seconds > self.max:
            self.max = seconds

    def percentile(self, q: float) -> float:
        """Upper bucket edge containing the q-quantile (conservative:
        never under-reports), clipped to the exact observed max."""
        if self.n == 0:
            return 0.0
        target = max(1, math.ceil(q * self.n))
        c = 0
        for i in range(N_BUCKETS):
            c += int(self.buckets[i])
            if c >= target:
                return min(2.0 ** i * 1e-6, self.max)
        return self.max

    def snapshot(self) -> LatencySnapshot:
        n = self.n
        return LatencySnapshot(
            count=n, total=self.total,
            mean=self.total / n if n else 0.0,
            p50=self.percentile(0.50), p95=self.percentile(0.95),
            p99=self.percentile(0.99), max=self.max)


class MetricsSnapshot(NamedTuple):
    """Typed result of ``client.metrics()``: a point-in-time copy —
    mutating the live telemetry after a snapshot never changes it."""
    mode: str
    counters: dict
    gauges: dict
    latency: dict          # op name -> LatencySnapshot

    def to_dict(self) -> dict:
        return {"mode": self.mode, "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "latency": {k: s._asdict() for k, s in
                            sorted(self.latency.items())}}


class _Call:
    """The span of one client call.  While it is open, the spans and
    syncs this thread opens are named after its op (``histore.<op>.*``):
    the backend learns the op from the call, not from an argument."""

    __slots__ = ("_local", "_op", "_ann", "_prev")

    def __init__(self, local: threading.local, op: str, ann):
        self._local = local
        self._op = op
        self._ann = ann
        self._prev = ""

    def __enter__(self):
        self._prev = getattr(self._local, "op", "")
        self._local.op = self._op
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self._local.op = self._prev
        return False


class Telemetry:
    """The per-backend metrics plane.  All mutators early-return in
    "off" mode before touching any state, so the off-mode hot path is a
    single attribute load + branch and a snapshot can never drift."""

    __slots__ = ("mode", "enabled", "_lock", "_counters", "_hists",
                 "_local")

    def __init__(self, mode: str = "counters"):
        if mode not in MODES:
            raise ValueError(
                f"cfg.telemetry must be one of {MODES}, got {mode!r}")
        self.mode = mode
        self.enabled = mode != "off"
        self._lock = threading.Lock()   # ticker thread vs foreground
        self._counters: dict[str, int] = {}
        self._hists: dict[str, LatencyHistogram] = {}
        self._local = threading.local()   # the op of this thread's call

    # -- hot-path mutators -------------------------------------------------
    def count(self, name: str, n: int = 1) -> None:
        if not self.enabled or n == 0:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + int(n)

    def observe(self, op: str, seconds: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            h = self._hists.get(op)
            if h is None:
                h = self._hists[op] = LatencyHistogram()
            h.record(seconds)

    # -- spans -------------------------------------------------------------
    def call(self, op: str, lanes: Optional[int] = None):
        """Context of one client call, span ``histore.<op>``; ``lanes``
        (the request's key count) rides along as the span's metadata."""
        if not self.enabled:
            return _NO_SPAN
        name = f"{SPAN_PREFIX}.{op}"
        ann = (TraceAnnotation(name) if lanes is None
               else TraceAnnotation(name, lanes=int(lanes)))
        return _Call(self._local, op, ann)

    def span(self, phase: str):
        """Context of one phase of the current call: span
        ``histore.<op>.<phase>`` (``histore.<phase>`` outside a call)."""
        if not self.enabled:
            return _NO_SPAN
        return TraceAnnotation(self._name(phase))

    def sync(self, x):
        """``jax.device_get(x)``: one blocking device->host read of the
        op path, under a ``sync`` span and counted as ``<op>.syncs``."""
        if not self.enabled:
            return jax.device_get(x)
        op = getattr(self._local, "op", "")
        if op:
            self.count(f"{op}.syncs")
        with TraceAnnotation(self._name("sync")):
            return jax.device_get(x)

    def _name(self, phase: str) -> str:
        op = getattr(self._local, "op", "")
        return (f"{SPAN_PREFIX}.{op}.{phase}" if op
                else f"{SPAN_PREFIX}.{phase}")

    # -- read side ---------------------------------------------------------
    def counter(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def snapshot(self, gauges: Optional[dict] = None) -> MetricsSnapshot:
        with self._lock:
            return MetricsSnapshot(
                mode=self.mode, counters=dict(self._counters),
                gauges=dict(gauges or {}),
                latency={k: h.snapshot() for k, h in self._hists.items()})


def render_text(snap: MetricsSnapshot) -> str:
    """Prometheus text exposition format for a snapshot: counters as
    ``histore_<name>_total``, gauges as ``histore_<name>``, latency
    histograms as one summary family with per-op labels.  A dotted
    counter name (``get.syncs``) is exposed with underscores
    (``histore_get_syncs_total``), as the format requires."""
    lines = [f"# histore telemetry (mode={snap.mode})"]
    for name in sorted(snap.counters):
        m = name.replace(".", "_")
        lines.append(f"# TYPE histore_{m}_total counter")
        lines.append(f"histore_{m}_total {snap.counters[name]}")
    for name in sorted(snap.gauges):
        lines.append(f"# TYPE histore_{name} gauge")
        lines.append(f"histore_{name} {snap.gauges[name]}")
    if snap.latency:
        lines.append("# TYPE histore_op_latency_seconds summary")
        for op in sorted(snap.latency):
            s = snap.latency[op]
            for q, v in (("0.5", s.p50), ("0.95", s.p95),
                         ("0.99", s.p99)):
                lines.append(f'histore_op_latency_seconds'
                             f'{{op="{op}",quantile="{q}"}} {v:.9g}')
            lines.append(f'histore_op_latency_seconds_count'
                         f'{{op="{op}"}} {s.count}')
            lines.append(f'histore_op_latency_seconds_sum'
                         f'{{op="{op}"}} {s.total:.9g}')
    return "\n".join(lines) + "\n"


def dump_metrics(snap: MetricsSnapshot, path) -> None:
    """Write a snapshot as JSON — the batteries drop one into
    ``test-logs/`` so a hung or failed 8-device run ships its counter
    state with the CI failure artifacts."""
    with open(path, "w") as f:
        json.dump(snap.to_dict(), f, indent=2, default=str)
