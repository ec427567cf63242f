"""Run one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>
    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --sweep 1000,2000,4000     # one set-up, then rising offered rates

Set-up draws the records and the window's whole schedule from ``--seed``,
loads the store through ``client.put``, warms every call size the window
can use, and only then opens the window.  The window serves the schedule
open loop (``bench/frontend.py``); afterwards every answer that was kept
is compared with the plain reference (``bench/reference.py``), the
acknowledged writes are read back, and the last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``,
each compared number beside its limit.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import frontend as fe  # noqa: E402
from bench import roofline, spec, ycsb  # noqa: E402
from bench.reference import Reference, value_of  # noqa: E402

READBACK_CALLS = 4        # calls of read-back after the window
READBACK_SCANS = 256      # scans of read-back, one per inserted key
KEEP_VALUE_BYTES = 256 * 2 ** 20   # GET values kept for the check


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def tpu_devices(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devs)}")
    return devs[:n]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept, so
    only a cell's first run in a checkout compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs lowered while armed (a cache hit still lowers)."""

    _live = None

    def __init__(self):
        from jax import monitoring

        self.armed = False
        self.count = 0
        if CompileCounter._live is None:
            monitoring.register_event_duration_secs_listener(
                CompileCounter._on_event)
        CompileCounter._live = self

    @staticmethod
    def _on_event(name, secs, **kw):
        c = CompileCounter._live
        if c is not None and c.armed and name.endswith(
                "jaxpr_to_mlir_module_duration"):
            c.count += 1


def rngs(seed: int):
    """Independent streams for keys, schedule, front end and check."""
    ss = np.random.SeedSequence(int(seed))
    return [np.random.default_rng(s) for s in ss.spawn(4)]


def groups_of(traffic: dict) -> set:
    return {fe.GROUP_OF_KIND[ycsb.KINDS[k]]
            for k, p in traffic["proportions"].items() if p > 0}


def warm_sizes(sched, group: str, keys_per_call: int) -> list:
    """Call sizes the window can use for ``group``: every allowed size
    up to the group's operation count."""
    kinds = [k for k, g in fe.GROUP_OF_KIND.items() if g == group]
    n = int(np.isin(sched.kind, kinds).sum())
    return [s for s in fe.call_sizes(keys_per_call) if s <= n]


# -- end-to-end metrics (host clock) -------------------------------------------
def _p(lat, q):
    return float(np.percentile(lat, q)) * 1e3 if len(lat) else None


END_TO_END = {
    "ops_per_s": lambda w, s: w.completed_in_window() / w.seconds,
    "read_p99_ms": lambda w, s: _p(w.latencies([ycsb.READ]), 99),
    "write_p99_ms": lambda w, s: _p(w.latencies([ycsb.UPDATE]), 99),
    "scan_p95_ms": lambda w, s: _p(w.latencies([ycsb.SCAN]), 95),
    "setup_s": lambda w, s: s,
}


# -- the comparison ---------------------------------------------------------------
def compare(window, keys, loaded: int, words: int) -> tuple:
    """Replay the calls in the order they were made against the
    reference; return (checks, failed ops, reference at the end)."""
    ref = Reference(keys, loaded)
    c = {"get_found_miss": 0, "get_value_diff": 0, "put_unacked": 0,
         "scan_diff": 0}
    failed = 0
    for call in window.calls:
        if call.group == "put":
            ok = np.asarray(call.result[0])
            bad = int((~ok).sum())
            c["put_unacked"] += bad
            if bad:
                lost = set(call.recs[~ok].tolist())
                failed += sum(r in lost for r in
                              window.sched.rec[call.ops].tolist())
            ref.write(call.recs[ok], call.versions[ok])
        elif call.group == "get":
            found, vals, _ = call.result
            found = np.asarray(found)
            want = ref.expect_get(call.recs)
            c["get_found_miss"] += int((found != (want >= 0)).sum())
            if vals is not None:
                got = np.asarray(vals)
                exp = value_of(keys[call.recs], want, words)
                c["get_value_diff"] += int(
                    ((got != exp).any(axis=1) & found).sum())
        else:
            kk, cnt, complete = call.result
            op = call.ops[0]
            want = ref.expect_scan(int(keys[call.recs[0]]),
                                   int(window.sched.length[op]))
            got = np.asarray(kk)[:min(int(cnt), len(want))]
            c["scan_diff"] += int(not (bool(complete)
                                       and len(got) == len(want)
                                       and (got == want).all()))
    failed += window.unanswered()
    return c, failed, ref


def readback_records(window, loaded: int, B: int, rng) -> np.ndarray:
    """Records to read back after the window: those written in it, the
    latest first, then the load's last call, then a seeded sample of the
    loaded records; up to READBACK_CALLS full calls."""
    written = [c.recs for c in reversed(window.calls) if c.group == "put"]
    order = [np.concatenate(written)] if written else []
    order.append(np.arange(max(0, loaded - B), loaded))
    order.append(rng.integers(0, loaded, READBACK_CALLS * B))
    allr = np.concatenate(order)
    _, first = np.unique(allr, return_index=True)
    return allr[np.sort(first)][:READBACK_CALLS * B]


def check_after(server, window, keys, loaded, words, scans: bool,
                rng) -> dict:
    """What the store answers once the window has closed: every
    acknowledged write read back, the sorted replicas and the value
    mirror against the reference."""
    c, failed, ref = compare(window, keys, loaded, words)
    B = server.B
    recs = readback_records(window, loaded, B, rng)
    recs = recs[ref.version[recs] >= 0]
    rb_diff = mirror_diff = 0
    for s in range(0, len(recs), B):
        r = recs[s:s + B]
        (found, vals, addrs), _ = server.get(keys[r], True)
        found, vals, addrs = (np.asarray(found), np.asarray(vals),
                              np.asarray(addrs))
        exp = value_of(keys[r], ref.version[r], words)
        rb_diff += int((~found | (vals != exp).any(axis=1)).sum())
        mv = server.mirror_values(addrs[found])
        mirror_diff += int((mv != exp[found]).any(axis=1).sum())
    if scans:
        ins = np.arange(loaded, len(keys))
        ins = ins[ref.version[ins] >= 0][-READBACK_SCANS:]
        for r in ins:
            (kk, cnt, complete), _ = server.scan(int(keys[r]))
            want = ref.expect_scan(int(keys[r]), server.scan_limit)
            got = np.asarray(kk)[:int(cnt)]
            rb_diff += int(not (bool(complete) and len(got) == len(want)
                                and (got == want).all()))
    live = ref.live_keys()
    replica_diff = 0
    for rk in server.replica_keys():
        # keys missing or extra, and keys out of order
        replica_diff += len(np.setxor1d(rk, live)) + int(
            (np.diff(rk.astype(np.int64)) <= 0).sum())
    c.update(readback_diff=rb_diff, replica_diff=replica_diff,
             mirror_diff=mirror_diff, demoted=server.demotions(),
             unanswered=window.unanswered())
    c["_failed"] = failed
    c["_readback"] = int(len(recs))
    return c


# -- one run -----------------------------------------------------------------------
@dataclass
class Setup:
    server: object
    keys: np.ndarray
    scheds: list
    loaded: int
    B: int
    groups: set
    r_front: object
    r_check: object


def prepare(cell, devices, seed: int, seconds: float, rates,
            server_factory=None) -> Setup:
    """Set-up, shared by a run and a sweep: one window's schedule per
    offered rate, the records, the store loaded through ``client.put``,
    and every call size the schedules can use warmed."""
    from bench.store import Store

    r_keys, r_sched, r_front, r_check = rngs(seed)
    config, traffic = cell.config, cell.traffic
    loaded = int(config["records"])
    B = int(config["keys_per_call"])
    scheds, base = [], loaded
    for rate in rates:
        s = ycsb.schedule(r_sched, traffic, rate, seconds, base)
        base += int((s.kind == ycsb.INSERT).sum())
        scheds.append(s)
    keys = ycsb.draw_keys(r_keys, base)
    groups = groups_of(traffic)
    server = (server_factory or Store)(devices, config)
    server.load(keys[:loaded])
    for g in sorted(groups - {"scan"}):
        server.warm(keys[:loaded], {g},
                    max((warm_sizes(s, g, B) for s in scheds), key=len))
    if "scan" in groups:
        server.warm(keys[:loaded], {"scan"}, [])
    return Setup(server, keys, scheds, loaded, B, groups, r_front, r_check)


def longest_calls(window, n: int = 5) -> str:
    """The n calls that took longest, with when they were dispatched."""
    calls = sorted(window.calls, key=lambda c: c.t_dispatch - c.t_ready)
    return ", ".join(f"{c.group} {(c.t_ready - c.t_dispatch) * 1e3:.1f} ms "
                     f"at {c.t_dispatch:.2f} s" for c in calls[:n])


def run_cell(cell, devices, seed: int, seconds: float, trace: bool,
             server_factory=None, t_start: float = None, log=print,
             out_dir: Path = None) -> dict:
    """Set up, serve one window, compare; returns the result line."""
    from bench.store import applied_entries

    t_start = T_START if t_start is None else t_start
    config = cell.config
    words = int(config["store"]["value_words"])
    su = prepare(cell, devices, seed, seconds, [cell.rate], server_factory)
    server, keys, sched, loaded = su.server, su.keys, su.scheds[0], su.loaded
    n_read = int((sched.kind == ycsb.READ).sum())
    keep = min(1.0, KEEP_VALUE_BYTES / max(1, n_read * 4 * words))
    tracer, span, applied, modules = None, fe.no_span, [], set()
    if trace:
        from bench.trace import Tracer
        tracer = Tracer((out_dir or cell.root / ".bench_out") / "trace")
        span = tracer.span
        modules = server.instrument(span, applied)
        tracer.start()
    front = fe.FrontEnd(server, keys, su.B, keep, su.r_front, span)
    counter = CompileCounter()
    counter.armed = True
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with span("window"):
        window = front.serve(sched, seconds, t0)
    counter.armed = False
    record = {}
    if tracer is not None:
        from bench.trace import read_xplane, reduce_trace
        host, dev = read_xplane(tracer.stop())
        record = reduce_trace(host, dev, modules=modules)
    peak = server.memory_peak()
    log(f"window: {len(sched)} ops offered over {seconds} s at "
        f"{cell.rate} ops/s; calls {window.calls_by_group()}; backlog at "
        f"close {window.backlog_at_close()}; unanswered "
        f"{window.unanswered()}; programs compiled inside the window "
        f"{counter.count}")
    log(f"longest calls: {longest_calls(window)}")
    checks = check_after(server, window, keys, loaded, words,
                         "scan" in su.groups, su.r_check)
    failed = checks.pop("_failed")
    log(f"checked after the window: {checks.pop('_readback')} records "
        f"read back")
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    batch = server.cfg.async_apply_batch if applied else 0
    apply_entries = applied_entries(applied, batch) if applied else 0
    del server, su
    gc.collect()
    out = {"correct": all(v == 0 for v in checks.values()),
           "attempted": len(sched), "failed": int(failed)}
    if trace:
        rec = dict(record)
        rec["calls"] = window.calls
        rec["peak_hbm_bytes_per_s"] = roofline.peak(
            d.device_kind)["hbm_bytes_per_s"] if d.platform == "tpu" \
            else None
        rec["get_bytes"] = sum(roofline.get_bytes(len(c.recs), words)
                               for c in window.calls if c.group == "get")
        rec["apply_bytes"] = roofline.apply_bytes(apply_entries)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"], cell.root)(rec) if record \
                else None
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = record.get("busy_s", 0.0)
        device["window_s"] = record.get("window_s", 0.0)
        out["device"] = device
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in record.get("device_ops", [])],
            "idle_gaps": [[n, s] for n, s in record.get("idle_gaps", [])]}
    else:
        metrics = {}
        for m in cell.end_to_end:
            v = END_TO_END[m["name"]](window, setup_s)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
    out["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    return out


def sustained(w) -> dict:
    """Whether a window's queue held: the median latency of operations
    due in its last quarter is at most 1.5 times (plus 20 ms) that of
    those due in its second quarter, and every operation due was
    answered.  Past the knee the queue grows all through the window and
    the later median runs away."""
    lat = w.done - w.sched.due
    q = np.floor(w.sched.due / w.seconds * 4).astype(int)
    m2 = float(np.nanmedian(lat[q == 1])) if (q == 1).any() else 0.0
    m4 = float(np.nanmedian(lat[q == 3])) if (q == 3).any() else 0.0
    return {"p50_q2_ms": m2 * 1e3, "p50_q4_ms": m4 * 1e3,
            "sustained": bool(w.unanswered() == 0
                              and m4 <= 1.5 * m2 + 0.02)}


def sweep(cell, devices, seed: int, seconds: float, rates, log=print):
    """One set-up, then one window per offered rate, rising; prints one
    line per rate, and stops after two rates in a row that were not
    sustained.  The knee is the highest sustained rate."""
    su = prepare(cell, devices, seed, seconds, rates)
    front = fe.FrontEnd(su.server, su.keys, su.B, 0.0, su.r_front)
    rows = []
    for rate, s in zip(rates, su.scheds):
        if len(rows) >= 2 and not (rows[-1]["sustained"]
                                   or rows[-2]["sustained"]):
            break
        w = front.serve(s, seconds, time.perf_counter(), grace=15.0)
        row = {"rate": rate, "ops_per_s": w.completed_in_window() / seconds,
               "backlog": w.backlog_at_close(), "calls": w.calls_by_group()}
        for name in ("read_p99_ms", "write_p99_ms", "scan_p95_ms"):
            v = END_TO_END[name](w, 0.0)
            if v is not None:
                row[name] = v
        row.update(sustained(w))
        log(json.dumps(row))
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated offered rates, ops/s")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        devices = tpu_devices(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache(ROOT)}", flush=True)
    log = lambda m: print(m, flush=True)  # noqa: E731
    if args.sweep:
        rates = [float(r) for r in args.sweep.split(",")]
        sweep(cell, devices, args.seed, args.seconds, rates, log)
        return 0
    out = run_cell(cell, devices, args.seed, args.seconds, bool(args.trace),
                   log=log)
    print(f"correct: {out['correct']}", file=sys.stderr)
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
