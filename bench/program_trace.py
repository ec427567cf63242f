"""The program's own spans and modules in a traced run.

HiStore's client and backend mark each call with a profiler annotation,
``histore.<op>``, and each phase inside it: ``histore.<op>.prepare``,
``.launch.<program>``, ``.sync``, ``.lease_tick``, ``.merge``, ``.drain``
and ``.retry`` (``repro.core.telemetry``).  Every store program runs
under a module of its own name, ``jit_histore_<program>``.  This module
reduces one trace to the record that the readers of those metrics read:

* ``spans[name]``: count, seconds, and device-busy seconds inside, for
  every ``histore.*`` span inside the window;
* ``modules[name]``: the executions of each module on the ``XLA
  Modules`` line inside the window, and their device seconds, by module
  name without its fingerprint;
* ``idle_gaps``: the device's idle seconds inside the window, each
  stretch put down to the innermost ``histore.*`` span open over it,
  else to the innermost harness span (``call.get``, ``frontend``), else
  to ``none``.

Busy time and device seconds are averaged over the devices, as in
``bench/trace.py``.  The harness's own record keeps only its spans, so a
reader finds this record through ``record(rec)``: ``rec["histore"]``
where the harness put one there, else the trace that the traced run
left under ``.bench_out/trace``, reduced once, and only if its window
is the record's own.  A trace without these spans (a program that does
not make them) gives readers nothing to read.

    python3 bench/program_trace.py <trace.xplane.pb[.gz]>

prints the record of one trace as JSON, idle gaps first.
"""
from __future__ import annotations

import functools
import glob
import gzip
import heapq
import json
import os
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT)]

from bench.trace import (  # noqa: E402
    SPAN_PREFIXES, SPANS, Busy, busy_line, clip, union)

PREFIX = "histore."
TRACE_DIR = ROOT / ".bench_out" / "trace"


def _harness(name: str) -> bool:
    return name in SPANS or name.startswith(SPAN_PREFIXES)


def read(path):
    """(host spans, device module lines) of one trace: host spans
    ``[(name, start_ns, end_ns)]``, the program's and the harness's;
    ``{device plane: {"XLA Modules": [(name, start_ns, end_ns)]}}``
    (``XLA Ops`` where a device has no modules line)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    host, dev = [], {}
    for plane in pd.planes:
        if plane.name.startswith(("/device:TPU:", "/device:GPU:")):
            lines = {ln.name: ln for ln in plane.lines}
            for name in ("XLA Modules", "XLA Ops"):
                if name in lines:
                    dev[plane.name] = {name: [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in lines[name].events]}
                    break
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    n = e.name
                    if n.startswith(PREFIX) or _harness(n):
                        host.append((n, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return host, dev


def _segments(spans):
    """Piecewise labels of time: ``[(t0, t1, name)]`` where some span is
    open, each stretch labelled by the innermost ``histore.*`` span open
    over it (the latest started, of two the first to end), else the
    innermost other span."""
    ev = []
    for i, (_, s, e) in enumerate(spans):
        ev.append((s, 1, i))
        ev.append((e, 0, i))
    ev.sort()
    heap, closed, out, prev = [], set(), [], None
    for t, start, i in ev:
        if prev is not None and t > prev:
            while heap and heap[0][-1] in closed:
                heapq.heappop(heap)
            if heap:
                out.append((prev, t, spans[heap[0][-1]][0]))
        if start:
            n, s, e = spans[i]
            heapq.heappush(heap, (not n.startswith(PREFIX), -s, e, i))
        else:
            closed.add(i)
        prev = t
    return out


def idle_gaps(spans, busy: Busy, ws: float, we: float) -> dict:
    """{label: seconds} of the device's idle time in [ws, we]."""
    idle = list(zip(np.concatenate([[ws], busy.e]),
                    np.concatenate([busy.s, [we]])))
    idle = [(a, b) for a, b in idle if b > a]
    segs = _segments(spans)
    gaps = defaultdict(float)
    j = 0
    for a, b in idle:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s0, s1, name = segs[k]
            d = min(b, s1) - max(a, s0)
            if d > 0:
                gaps[name] += float(d) / 1e9
                covered += d
            k += 1
        if b - a - covered > 0:
            gaps["none"] += float(b - a - covered) / 1e9
    return dict(gaps)


def reduce(host, dev) -> dict:
    """The record of one traced window (see the module's docstring); {}
    without a window or a device."""
    win = [(s, e) for n, s, e in host if n == "window"]
    if not win or not dev:
        return {}
    ws, we = win[0]
    per_dev, modules = [], defaultdict(lambda: {"count": 0,
                                                "device_s": 0.0})
    for _, lines in sorted(dev.items()):
        ev = busy_line(lines)
        per_dev.append(Busy(clip(union([(s, e) for _, s, e in ev]),
                                 ws, we)))
        if "XLA Modules" not in lines:
            continue
        for n, s, e in ev:
            lo, hi = max(s, ws), min(e, we)
            if hi > lo:
                m = modules[n.split("(", 1)[0]]
                m["count"] += 1
                m["device_s"] += (hi - lo) / 1e9 / len(dev)
    inner = [(n, s, e) for n, s, e in host
             if n != "window" and s >= ws and e <= we]
    spans = defaultdict(lambda: {"count": 0, "seconds": 0.0, "busy_s": 0.0})
    ours = [x for x in inner if x[0].startswith(PREFIX)]
    if ours:
        a = np.array([s for _, s, _ in ours], float)
        b = np.array([e for _, _, e in ours], float)
        bi = np.mean([p.inside(a, b) for p in per_dev], axis=0)
        for (n, s, e), x in zip(ours, bi):
            d = spans[n]
            d["count"] += 1
            d["seconds"] += (e - s) / 1e9
            d["busy_s"] += float(x) / 1e9
    n_dev = max(len(per_dev), 1)
    gaps = idle_gaps(inner, per_dev[0], ws, we)
    return {
        "window_s": (we - ws) / 1e9,
        "busy_s": float(np.mean([p.c[-1] for p in per_dev])) / 1e9,
        "spans": {k: dict(v) for k, v in spans.items()},
        "modules": {k: {"count": round(v["count"] / n_dev),
                        "device_s": v["device_s"]}
                    for k, v in modules.items()},
        "idle_gaps": sorted(gaps.items(), key=lambda x: -x[1]),
    }


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int) -> dict:
    return reduce(*read(path))


def record(rec: dict):
    """The program's record of the traced run whose harness record is
    ``rec``, or None where there is none to read."""
    if "histore" in rec:
        return rec["histore"] or None
    if not rec.get("window_s"):
        return None
    found = glob.glob(str(TRACE_DIR / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    if len(found) != 1:
        return None
    r = _reduce_file(found[0], os.stat(found[0]).st_mtime_ns)
    if not r or abs(r["window_s"] - rec["window_s"]) > 1e-9:
        return None
    return r


# -- what the per-layer readers take from the record ---------------------------
def per_call(rec: dict, op: str, value):
    """``value(record)`` per ``histore.<op>`` call, or None where the
    record, the call or the value is missing."""
    r = record(rec)
    call = (r or {}).get("spans", {}).get(PREFIX + op)
    if not call or not call["count"]:
        return None
    v = value(r)
    return None if v is None else v / call["count"]


def span_total(r: dict, name: str, field: str = "seconds") -> float:
    """A field of span ``name`` summed over the window (0 where none)."""
    return r["spans"].get(name, {}).get(field, 0)


def main(argv) -> int:
    path = Path(argv[0])
    if path.suffix == ".gz":
        with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as f:
            f.write(gzip.decompress(path.read_bytes()))
            f.flush()
            r = reduce(*read(f.name))
    else:
        r = reduce(*read(path))
    print(json.dumps({"idle_gaps": r.get("idle_gaps"),
                      "window_s": r.get("window_s"),
                      "busy_s": r.get("busy_s"),
                      "modules": r.get("modules"),
                      "spans": r.get("spans")}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
