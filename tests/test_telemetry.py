"""Ops telemetry plane: histograms, counters, profiler spans, the knob.

The counters are verified DIFFERENTIALLY: a seeded oracle trace is
replayed through an instrumented client and every telemetry counter must
equal the ground truth recomputed from the trace itself (op counts,
client-observed retries, demotions delivered only through severed
heartbeats — ``oracle_kills == 0``).  The "off" mode is held to a hard
contract: a snapshot taken before a workload equals one taken after.
Spans are read back from a real ``jax.profiler`` trace of a CPU run.
"""
import glob
import time
import warnings

import jax
import numpy as np
import pytest

from repro.configs.histore import scaled
from repro.core import kvstore as kv
from repro.core import telemetry as tm
from repro.core.client import (DistributedBackend, HiStoreClient,
                               LocalBackend)
from repro.sharding.smap import make_mesh

from oracle import FaultInjector, gen_ops, replay, splice_faults

CFG = scaled(log_capacity=1 << 10, async_apply_batch=256)


def _local_client(telemetry="counters", capacity=4096):
    cfg = scaled(log_capacity=1 << 10, async_apply_batch=256,
                 telemetry=telemetry)
    return HiStoreClient(LocalBackend(capacity, cfg), batch_quantum=16)


def _one_dev_client(cfg, **kw):
    mesh = make_mesh((len(jax.devices()),), (kv.AXIS,))
    return HiStoreClient(DistributedBackend(mesh, cfg, 512, capacity_q=64),
                         batch_quantum=16, **kw)


# ---------------------------------------------------------------------------
# Histogram unit behaviour
# ---------------------------------------------------------------------------
def test_histogram_percentiles_log_buckets():
    """p50/p95/p99 come from the log2 bucket walk: conservative (upper
    bucket edge) but clipped to the exact observed max."""
    h = tm.LatencyHistogram()
    for us in [1, 1, 2, 3, 100, 1000]:
        h.record(us * 1e-6)
    s = h.snapshot()
    assert s.count == 6
    assert s.max == pytest.approx(1e-3)
    # p50: 3rd of 6 samples lands in the [2,4)us bucket -> edge 4us
    assert s.p50 == pytest.approx(4e-6)
    # p99 -> last sample's bucket edge (1024us) clipped to max (1000us)
    assert s.p99 == pytest.approx(1e-3)
    assert s.mean == pytest.approx(s.total / 6)


def test_histogram_empty_and_submicro():
    h = tm.LatencyHistogram()
    assert h.snapshot() == tm.LatencySnapshot(0, 0.0, 0.0, 0.0, 0.0,
                                              0.0, 0.0)
    h.record(2e-7)                      # sub-microsecond -> bucket 0
    s = h.snapshot()
    assert s.count == 1 and s.p50 == pytest.approx(2e-7)  # clipped to max


def test_invalid_mode_rejected_at_construction():
    with pytest.raises(ValueError, match="telemetry"):
        tm.Telemetry("verbose")
    with pytest.raises(ValueError, match="telemetry"):
        _local_client(telemetry="on")


# ---------------------------------------------------------------------------
# Differential: counters vs the oracle trace ground truth
# ---------------------------------------------------------------------------
def test_counters_match_trace_ground_truth():
    """Replay a seeded mixed trace with a kill schedule; every counter
    must equal the value recomputed from the trace itself."""
    n_events = 16
    ops = gen_ops(3, "uniform", n_events=n_events, batch=16)
    schedule = [(n_events // 4, "fail", 0),
                (n_events // 2, "recover", 0)]
    trace = splice_faults(ops, schedule)
    client = _local_client()
    replay(client, trace)
    c = client.metrics().counters
    truth = {"put": 0, "get": 0, "delete": 0, "scan": 0}
    for ev in ops:
        if ev[0] == "scan":
            truth["scan"] += 1
        else:
            truth[ev[0]] += len(ev[1])
    assert c.get("put_ops", 0) == truth["put"] == client.stats["puts"]
    assert c.get("get_ops", 0) == truth["get"] == client.stats["gets"]
    assert c.get("delete_ops", 0) == truth["delete"]
    assert c.get("scan_ops", 0) == truth["scan"]
    assert c.get("retries", 0) == client.stats["retries"]
    assert c.get("index_demotions", 0) == 1     # the one scheduled kill
    assert c.get("index_recoveries", 0) == 1
    assert c.get("hops2_gets", 0) == 0          # healthy local data plane
    lat = client.metrics().latency
    assert lat["put"].count > 0 and lat["get"].count > 0


def test_detector_demotions_with_zero_oracle_kills():
    """The lease-detector differential: the only kill is a severed
    heartbeat, so demotions come from DETECTION — the injector proves no
    oracle fail_server ever ran."""
    cfg = scaled(log_capacity=1 << 10, async_apply_batch=256,
                 lease_misses=3, lease_clock="rounds")
    client = _one_dev_client(cfg)
    backend = client.backend
    inj = FaultInjector(client)
    keys = np.arange(1, 17)
    assert client.put(keys, keys).all_ok
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # 1-dev mask-only warning
        inj.sever(0)
    client.get(keys)                        # retries age the lease
    assert backend.detected == [0]
    inj.recover(0)
    c = client.metrics().counters
    assert inj.oracle_kills == 0
    assert c.get("index_demotions", 0) == 1
    assert c.get("index_recoveries", 0) == 1
    assert c.get("retries", 0) == client.stats["retries"] > 0
    assert c.get("lease_ticks", 0) > 0


def test_off_mode_records_nothing():
    """cfg.telemetry="off": a snapshot before the workload equals one
    after — no counters, no histograms, no gauges."""
    client = _local_client(telemetry="off")
    before = client.metrics()
    trace = gen_ops(5, "uniform", n_events=8, batch=16)
    replay(client, trace)
    after = client.metrics()
    assert before == after
    assert after.counters == {} and after.latency == {}
    assert after.gauges == {}
    assert client.stats["puts"] > 0     # the workload itself did run


# ---------------------------------------------------------------------------
# Gauges, exposition format, overhead
# ---------------------------------------------------------------------------
def test_gauges_reflect_backend_state():
    client = _local_client()
    keys = np.arange(1, 33)
    assert client.put(keys, keys).all_ok
    g = client.metrics().gauges
    assert g["live_index_servers"] == 1 + CFG.n_backups
    assert g["pending_log_ops"] == client.backend.pending_ops() > 0
    client.backend.fail_server(1)
    assert client.metrics().gauges["live_index_servers"] == CFG.n_backups
    client.backend.recover_server(1)


def test_gauges_distributed_device_counters():
    cfg = scaled(log_capacity=1 << 10, async_apply_batch=256)
    client = _one_dev_client(cfg)
    keys = np.arange(1, 33)
    assert client.put(keys, keys).all_ok
    g = client.metrics().gauges
    G = len(jax.devices())
    assert g["live_index_servers"] == G
    assert g["live_data_servers"] == G
    assert g["pending_log_ops"] > 0
    client.drain()
    assert client.metrics().gauges["pending_log_ops"] == 0


def test_prometheus_text_format():
    client = _local_client()
    keys = np.arange(1, 17)
    assert client.put(keys, keys).all_ok
    text = client.metrics_text()
    assert "# TYPE histore_put_ops_total counter" in text
    assert "histore_put_ops_total 16" in text
    assert "# TYPE histore_live_index_servers gauge" in text
    assert '# TYPE histore_op_latency_seconds summary' in text
    assert 'histore_op_latency_seconds{op="put",quantile="0.99"}' in text
    assert 'histore_op_latency_seconds_count{op="put"} 1' in text


def test_record_path_is_cheap_and_allocation_free():
    """The hot-path budget: record() touches a preallocated bucket array
    only — its array object identity never changes and a million records
    stay well under a second."""
    h = tm.LatencyHistogram()
    buckets = h.buckets
    t0 = time.perf_counter()
    for _ in range(100_000):
        h.record(3.2e-6)
    dt = time.perf_counter() - t0
    assert h.buckets is buckets and h.n == 100_000
    assert dt < 1.0, f"100k records took {dt:.3f}s"


def test_enabled_overhead_smoke():
    """Counters mode must not change the op path's complexity class: the
    same trace replayed with telemetry on stays within a loose envelope
    of the off-mode run (3x + absolute slack for scheduler noise)."""
    trace = gen_ops(7, "uniform", n_events=10, batch=16)
    timings = {}
    for mode in ("off", "counters"):
        client = _local_client(telemetry=mode)
        replay(client, trace)               # warm (compile)
        client2 = _local_client(telemetry=mode)
        t0 = time.perf_counter()
        replay(client2, trace)
        timings[mode] = time.perf_counter() - t0
    assert timings["counters"] <= timings["off"] * 3.0 + 0.5, timings


# ---------------------------------------------------------------------------
# Ticker error surfacing (the give-up latch)
# ---------------------------------------------------------------------------
def test_ticker_gave_up_is_latched_and_counted():
    """A ticker that dies after 3 consecutive tick errors must say so:
    ticker_errors/ticker_gave_up counters, start_ticker() returning
    False while latched, stop_ticker() clearing the latch."""
    wcfg = scaled(log_capacity=1 << 10, async_apply_batch=256,
                  lease_misses=3, lease_clock="wall",
                  lease_timeout_s=0.5, lease_interval_s=0.05)
    client = _one_dev_client(cfg=wcfg)
    backend = client.backend

    def boom(bump=False):
        raise RuntimeError("injected tick failure")

    backend._lease_tick = boom
    backend._last_traffic_t = time.monotonic() - 999.0
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the loop's RuntimeWarning
        assert client.start_ticker()
        t = backend._ticker
        t.join(timeout=30.0)
    assert not t.is_alive(), "3 consecutive errors must end the loop"
    c = client.metrics().counters
    assert c.get("ticker_errors", 0) == 3
    assert c.get("ticker_gave_up", 0) == 1
    assert backend._ticker_gave_up is True
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert client.start_ticker() is False, \
            "a gave-up ticker must not silently restart"
    client.stop_ticker()                    # explicit stop clears the latch
    assert backend._ticker_gave_up is False


# ---------------------------------------------------------------------------
# Profiler spans, sync counts and program names
# ---------------------------------------------------------------------------
RCFG = scaled(log_capacity=64, async_apply_batch=16, lease_misses=3,
              lease_clock="rounds")

GET_PHASES = ("prepare", "launch.get", "sync", "lease_tick",
              "launch.tick", "merge")


def _host_spans(trace_dir) -> list:
    """(name, start_ns, end_ns, stats) of every ``histore.*`` event on the
    host planes of the one trace written under ``trace_dir``."""
    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("histore."):
                    stats = {k: v for k, v in e.stats}
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, stats))
    return out


def _delta(client, before: dict) -> dict:
    after = client.metrics().counters
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def test_spans_nest_inside_the_get_call(tmp_path):
    """Under a profiler session a GET call is one ``histore.get`` span
    carrying its lane count, with every phase of the call inside it."""
    client = _one_dev_client(RCFG)
    keys = np.arange(1, 17)
    assert client.put(keys, keys).all_ok       # compile outside the trace
    client.get(keys)
    with jax.profiler.trace(str(tmp_path)):
        assert client.put(keys, keys).all_ok
        assert client.get(keys).all_found
        client.scan(1, 100, 16)
    spans = _host_spans(tmp_path)
    names = {n for n, *_ in spans}
    assert {"histore.put", "histore.get", "histore.scan",
            "histore.put.launch.put", "histore.scan.launch.scan"} <= names
    (call,) = [s for s in spans if s[0] == "histore.get"]
    assert call[3] == {"lanes": 16}
    inner = {n for n, s, e, _ in spans if n.startswith("histore.get.")
             and call[1] <= s and e <= call[2]}
    assert inner == {f"histore.get.{p}" for p in GET_PHASES}
    assert not [n for n, s, e, _ in spans if n.startswith("histore.get.")
                and not (call[1] <= s and e <= call[2])]


def test_off_mode_opens_no_span_and_counts_nothing(tmp_path):
    cfg = scaled(log_capacity=64, async_apply_batch=16,
                 telemetry="off")
    client = _one_dev_client(cfg)
    keys = np.arange(1, 17)
    assert client.put(keys, keys).all_ok
    with jax.profiler.trace(str(tmp_path)):
        assert client.get(keys).all_found
        assert client.put(keys, keys).all_ok
    assert _host_spans(tmp_path) == []
    assert client.metrics().counters == {}
    assert client.telemetry.span("merge") is client.telemetry.call("get")


def test_trace_mode_is_rejected():
    with pytest.raises(ValueError, match="telemetry"):
        tm.Telemetry("trace")
    with pytest.raises(ValueError, match="telemetry"):
        _local_client(telemetry="trace")


def test_healthy_get_counts_three_syncs():
    """need.any(), the heartbeat fetch and pending.any(): nothing more
    (the hops2 counter reads no device array on the healthy path)."""
    client = _one_dev_client(RCFG)
    keys = np.arange(1, 17)
    assert client.put(keys, keys).all_ok
    before = client.metrics().counters
    assert client.get(keys).all_found
    d = _delta(client, before)
    assert d["get.syncs"] == 3 and d["get.calls"] == 1
    assert d["get.lanes"] == 16 and d["get_ops"] == 16
    assert d["lease_ticks"] == 1 and "hops2_gets" not in d


def test_put_sync_count_is_pinned():
    """valid.sum(), the heartbeat fetch and pending.any()."""
    client = _one_dev_client(RCFG)
    keys = np.arange(1, 17)
    assert client.put(keys, keys).all_ok
    before = client.metrics().counters
    assert client.put(keys + 100, keys).all_ok
    d = _delta(client, before)
    assert d["put.syncs"] == 3 and d["put.calls"] == 1
    assert d["put.lanes"] == 16
    assert "drains" not in d and "apply_rounds" not in d


def test_a_put_that_forces_a_drain_counts_it():
    """A full log (64 entries) makes the next PUT drain first: one drain
    of 64 / 16 = 4 apply rounds, each read back through pending_ops()."""
    client = _one_dev_client(RCFG)
    keys = np.arange(1, 65)
    assert client.put(keys, keys).all_ok
    before = client.metrics().counters
    assert client.put(keys[:16] + 1000, keys[:16]).all_ok
    d = _delta(client, before)
    assert d["drains"] == 1 and d["apply_rounds"] == 4
    assert "gc_rounds" not in d
    # valid.sum(), 5 pending_ops(), 1 pending_frees(), 4 + 1 heartbeat
    # fetches (each apply round ages the leases), pending.any()
    assert d["put.syncs"] == 1 + 5 + 1 + 5 + 1


def test_store_programs_have_distinct_names():
    mesh = make_mesh((len(jax.devices()),), (kv.AXIS,))
    ops = kv.make_ops(mesh, RCFG, capacity_q=64)
    names = {f.__name__ for f in ops.values()}
    assert len(names) == len(ops) == 10
    assert names == {f"histore_{k}" for k in ops}
    store = kv.create(mesh, 512, RCFG)
    assert "module @jit_histore_tick" in ops["tick"].lower(store).as_text()


def test_dotted_counters_render_as_prometheus_names():
    client = _local_client()
    keys = np.arange(1, 17)
    assert client.put(keys, keys).all_ok
    text = client.metrics_text()
    assert "histore_put_calls_total 1" in text
    assert "histore_put_syncs_total 2" in text   # valid.sum, pending.any
    assert "put.calls" not in text
