"""The traced run: a ``jax.profiler`` trace of the window, reduced to the
record the per-layer metric readers read.

The harness marks its own spans with ``jax.profiler.TraceAnnotation``, so
they land in the trace on the same clock as the device's events:
``window``, ``frontend``, ``wait.arrival``, ``call.<group>`` around each
client call, and ``op.<program>`` around the dispatch of each program
the backend launches.  No span adds a sync.  Device busy time is the
union of the intervals of the device's ``XLA Modules`` line (its ``XLA
Ops`` line where there is none).

The store's programs share one module name (``jit__lambda``: each is a
jitted lambda), so a program's device time is found by matching: one
device runs its modules in launch order, so the n-th execution of a
store module is the n-th ``op`` launch.  The match stands only where the
counts agree and no module (name and fingerprint) serves two programs;
otherwise no program time is reported.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from collections import defaultdict

import numpy as np

SPANS = ("window", "frontend", "wait.arrival")
SPAN_PREFIXES = ("call.", "op.")


def is_span(name: str) -> bool:
    return name in SPANS or name.startswith(SPAN_PREFIXES)


class Tracer:
    """Starts and stops the profiler around the window, in ``out_dir``."""

    def __init__(self, out_dir):
        self.out_dir = str(out_dir)

    def span(self, name):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)

    def stop(self) -> str:
        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.out_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.out_dir}")
        return found[0]


def read_xplane(path):
    """(host spans, device events) of one trace: host spans as
    [(name, start_ns, end_ns)]; device events as
    {device plane: {line: [(name, start_ns, end_ns)]}}."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    host, dev = [], {}
    for plane in pd.planes:
        if plane.name.startswith(("/device:TPU:", "/device:GPU:")):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                                    for e in line.events]
            dev[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if is_span(e.name):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return host, dev


def union(intervals) -> np.ndarray:
    """Disjoint sorted [k, 2] array covering the given intervals."""
    iv = np.asarray(sorted(intervals), float).reshape(-1, 2)
    if not len(iv):
        return iv
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


class Busy:
    """Covered length of a disjoint interval set inside any [a, b]."""

    def __init__(self, iv: np.ndarray):
        self.s = iv[:, 0]
        self.e = iv[:, 1]
        self.c = np.concatenate([[0.0], np.cumsum(self.e - self.s)])

    def before(self, t) -> np.ndarray:
        t = np.asarray(t, float)
        if not len(self.s):
            return np.zeros_like(t)
        i = np.searchsorted(self.s, t, "right")   # intervals starting <= t
        j = np.maximum(i - 1, 0)
        part = np.where(i > 0, np.minimum(t, self.e[j]) - self.s[j], 0.0)
        return self.c[j] + np.maximum(part, 0.0)

    def inside(self, a, b) -> np.ndarray:
        return self.before(b) - self.before(a)


_HLO = re.compile(r"^%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")


def op_label(hlo: str) -> str:
    """A device op's name, opcode and shapes out of its HLO text:
    ``sort.4 sort (pred[4096], s32[4096])``."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:120]
    shape = re.sub(r"\{[^{}]*\}", "", m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape}"[:120]


def busy_line(lines: dict) -> list:
    for name in ("XLA Modules", "XLA Ops"):
        if lines.get(name):
            return lines[name]
    return []


def match_programs(host, lines: dict, modules, ws, we) -> dict:
    """{program: {"count", "device_s"}} for the ``op.<program>``
    launches that start inside [ws, we], from the store-module executions
    of one device's ``lines``, matched in order; {} where they do not
    match."""
    launches = sorted((s, n[3:]) for n, s, _ in host if n.startswith("op."))
    execs = sorted((s, e, n) for n, s, e in lines.get("XLA Modules", [])
                   if n.split("(", 1)[0] in modules)
    if not launches or len(launches) != len(execs):
        return {}
    owner, out = {}, {}
    for (t, prog), (s, e, n) in zip(launches, execs):
        if owner.setdefault(n, prog) != prog:
            return {}
        if ws <= t <= we:
            d = out.setdefault(prog, {"count": 0, "device_s": 0.0})
            d["count"] += 1
            d["device_s"] += (e - s) / 1e9
    return out


def reduce_trace(host, dev, top: int = 10, modules=()) -> dict:
    """The record of one traced window.

    ``spans[name]``: count, seconds, device-busy seconds inside;
    ``programs[name]``: launches and device seconds of each store
    program whose module name is in ``modules`` (``match_programs``,
    averaged over the devices; {} unless every device matches);
    ``busy_s`` and ``window_s`` (busy averaged over the devices);
    ``device_ops``: the device operations
    that took most time; ``idle_gaps``: the device's idle seconds by the
    innermost harness span open in each gap."""
    win = [(s, e) for n, s, e in host if n == "window"]
    if not win or not dev:
        return {}
    ws, we = win[0]
    busies, per_dev = [], []
    for plane, lines in sorted(dev.items()):
        ev = busy_line(lines)
        iv = clip(union([(s, e) for _, s, e in ev]), ws, we)
        per_dev.append(Busy(iv))
        busies.append(float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0)
    busy = per_dev[0] if per_dev else None
    spans = defaultdict(lambda: {"count": 0, "seconds": 0.0,
                                 "busy_s": 0.0})
    inner = [(n, s, e) for n, s, e in host if n != "window"
             and s >= ws and e <= we]
    if inner:
        a = np.array([s for _, s, _ in inner], float)
        b = np.array([e for _, _, e in inner], float)
        bi = np.mean([p.inside(a, b) for p in per_dev], axis=0)
        for (n, s, e), x in zip(inner, bi):
            d = spans[n]
            d["count"] += 1
            d["seconds"] += (e - s) / 1e9
            d["busy_s"] += float(x) / 1e9
    ops = defaultdict(float)
    for plane, lines in dev.items():
        for n, s, e in lines.get("XLA Ops", []):
            lo, hi = max(s, ws), min(e, we)
            if hi > lo:
                ops[op_label(n)] += (hi - lo) / 1e9 / len(dev)
    gaps = defaultdict(float)
    if busy is not None:
        edges = np.concatenate([[ws], busy.e]), np.concatenate([busy.s, [we]])
        starts = sorted(inner, key=lambda x: x[1])
        st = np.array([s for _, s, _ in starts], float)
        for g0, g1 in zip(*edges):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            label = "none"
            j = int(np.searchsorted(st, mid, "right"))
            best = -np.inf
            for n, s, e in starts[max(0, j - 64):j]:
                if s <= mid <= e and s > best:
                    label, best = n, s
            gaps[label] += (g1 - g0) / 1e9
    matched = [match_programs(host, lines, set(modules), ws, we)
               for _, lines in sorted(dev.items())]
    programs = {}
    if matched and all(matched) and all(m.keys() == matched[0].keys()
                                        for m in matched):
        programs = {k: {"count": v["count"],
                        "device_s": float(np.mean(
                            [m[k]["device_s"] for m in matched]))}
                    for k, v in matched[0].items()}
    return {
        "window_s": (we - ws) / 1e9,
        "programs": programs,
        "busy_s": float(np.mean(busies)) / 1e9,
        "spans": {k: dict(v) for k, v in spans.items()},
        "device_ops": sorted(ops.items(), key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(gaps.items(), key=lambda x: -x[1])[:top],
    }


# -- what the per-layer readers take from the record ---------------------------
def program_s(rec: dict, program: str):
    """The matched device seconds of one store program, or None."""
    p = rec.get("programs", {}).get(program)
    return p["device_s"] if p and p["count"] else None


def program_ms_per_call(rec: dict, program: str, group: str):
    """A program's device milliseconds per client call of ``group``."""
    s = program_s(rec, program)
    call = rec.get("spans", {}).get(f"call.{group}")
    if s is None or not call or not call["count"]:
        return None
    return s / call["count"] * 1e3
