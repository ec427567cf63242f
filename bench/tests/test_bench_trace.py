"""The reduction from a profiler trace to the per-layer record: on
synthetic intervals, and on a small trace recorded on a TPU v5e
(``bench/testdata/tiny.xplane.pb.gz``, written by record_trace.py)."""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from bench import spec
from bench import trace as tr

TINY = Path(__file__).resolve().parents[1] / "testdata" / "tiny.xplane.pb.gz"


def test_union_and_busy_inside():
    iv = tr.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert iv.tolist() == [[0, 3], [5, 9]]
    b = tr.Busy(iv)
    assert b.inside(0, 10).tolist() == 7.0
    assert b.inside(np.array([2.0, 4.0]), np.array([6.0, 4.5])).tolist() \
        == [2.0, 0.0]


def test_reduce_synthetic_window():
    host = [("window", 0, 100), ("call.get", 10, 40), ("op.get", 15, 30),
            ("wait.arrival", 50, 90), ("frontend", 40, 50)]
    dev = {"/device:TPU:0": {"XLA Modules": [("m", 16, 28), ("m", 60, 62)],
                             "XLA Ops": [("fusion", 16, 20),
                                         ("sort", 20, 28),
                                         ("fusion", 60, 62)]}}
    rec = tr.reduce_trace(host, dev)
    assert rec["window_s"] == pytest.approx(100e-9)
    assert rec["busy_s"] == pytest.approx(14e-9)
    assert rec["spans"]["op.get"]["busy_s"] == pytest.approx(12e-9)
    assert rec["spans"]["call.get"]["count"] == 1
    assert dict(rec["device_ops"])["sort"] == pytest.approx(8e-9)
    gaps = dict(rec["idle_gaps"])
    assert gaps["wait.arrival"] == pytest.approx((60 - 50 + 90 - 62) * 1e-9)
    assert sum(gaps.values()) == pytest.approx(86e-9)
    read = spec.metric_reader("idle_share.read")
    assert read(rec) == pytest.approx(86.0)


def test_a_reader_with_nothing_to_read_returns_none():
    for name in ("device_ms.get", "get_roofline", "client_host_ms.scan",
                 "device_ms.apply", "apply_roofline", "idle_share.scan"):
        assert spec.metric_reader(name)({}) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The tiny ``paper-32b.ycsb-a`` window traced on one TPU v5e."""
    import gzip

    p = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    p.write_bytes(gzip.decompress(TINY.read_bytes()))
    return tr.read_xplane(str(p))


def test_recorded_trace_planes(recorded):
    host, dev = recorded
    assert list(dev) == ["/device:TPU:0"]
    assert len(dev["/device:TPU:0"]["XLA Modules"]) == 1068
    names = {n for n, _, _ in host}
    assert {"window", "frontend", "call.get", "call.put", "op.get",
            "op.put", "op.tick"} <= names


STORE_MODULES = {"jit__lambda", "jit__tick_body"}


def test_recorded_trace_reduces(recorded):
    rec = tr.reduce_trace(*recorded, modules=STORE_MODULES)
    assert rec["window_s"] == pytest.approx(0.633715305)
    assert 0 < rec["busy_s"] < rec["window_s"]
    assert sum(s for _, s in rec["idle_gaps"]) == pytest.approx(
        rec["window_s"] - rec["busy_s"], rel=1e-6)
    spans = rec["spans"]
    assert spans["call.get"]["count"] == spans["op.get"]["count"] == 12
    assert spans["call.put"]["count"] == spans["op.put"]["count"] == 12
    for s in spans.values():
        assert 0 <= s["busy_s"] <= s["seconds"]
    # a call holds its program and the client's own small programs
    assert spans["call.get"]["busy_s"] >= spans["op.get"]["busy_s"]
    assert rec["device_ops"][0][0].startswith("sort.4 sort")
    rec["peak_hbm_bytes_per_s"] = 819e9
    rec["get_bytes"] = 12 * 64 * (4 + 96 + 64)
    for name in ("client_host_ms.get", "device_ms.get", "device_ms.put",
                 "get_roofline", "idle_share.read"):
        v = spec.metric_reader(name)(rec)
        assert v is not None and v >= 0, name
    assert spec.metric_reader("get_roofline")(rec) <= 100
    assert spec.metric_reader("device_ms.apply")(rec) is None
    assert spec.metric_reader("idle_share.scan")(rec) == \
        spec.metric_reader("idle_share.read")(rec)


def test_launches_match_executions_in_order(recorded):
    """Each of the 12 puts, gets and ticks is one execution, and each
    program runs under one module fingerprint of its own."""
    host, dev = recorded
    rec = tr.reduce_trace(host, dev, modules=STORE_MODULES)
    progs = rec["programs"]
    assert sorted(progs) == ["get", "put", "tick"]
    assert all(p["count"] == 12 for p in progs.values())
    assert progs["put"]["device_s"] > progs["get"]["device_s"] > 0
    assert tr.reduce_trace(host, dev)["programs"] == {}


def test_a_launch_record_that_does_not_match_reports_no_program():
    host = [("window", 0, 100), ("op.get", 10, 11), ("op.put", 20, 21),
            ("op.get", 30, 31)]
    mods = [("jit__lambda(1)", 12, 15), ("jit__lambda(2)", 22, 25),
            ("jit_add(9)", 26, 27), ("jit__lambda(1)", 32, 35)]
    dev = {"/device:TPU:0": {"XLA Modules": mods}}
    rec = tr.reduce_trace(host, dev, modules={"jit__lambda"})
    assert rec["programs"] == {"get": {"count": 2, "device_s": 6e-9},
                               "put": {"count": 1, "device_s": 3e-9}}
    # one launch too few, or one module serving two programs: nothing
    assert tr.reduce_trace(host[:-1], dev,
                           modules={"jit__lambda"})["programs"] == {}
    mods[1] = ("jit__lambda(1)", 22, 25)
    assert tr.reduce_trace(host, dev,
                           modules={"jit__lambda"})["programs"] == {}
