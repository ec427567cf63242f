"""The program's own spans (``histore.*``) and named modules, reduced for
the readers of ``host_ms.get``, ``lease_tick_ms.get``, ``device_ms.tick``,
``syncs.get`` and ``drain_ms.put``: on synthetic spans worked out by
hand, and on the small chip traces in ``bench/testdata/``."""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest

from bench import program_trace as pt
from bench import spec
from bench import trace as tr

DATA = Path(__file__).resolve().parents[1] / "testdata"
NEW = ("host_ms.get", "lease_tick_ms.get", "device_ms.tick", "syncs.get",
       "drain_ms.put")


def _get_call(t: int) -> list:
    """One GET call at ``t`` (ns) as the client marks it."""
    return [("call.get", t - 10, t + 210), ("histore.get", t, t + 200),
            ("histore.get.prepare", t, t + 10),
            ("histore.get.launch.get", t + 10, t + 20),
            ("histore.get.sync", t + 20, t + 100),
            ("histore.get.lease_tick", t + 100, t + 160),
            ("histore.get.launch.tick", t + 100, t + 110),
            ("histore.get.sync", t + 110, t + 150),
            ("histore.get.merge", t + 160, t + 180),
            ("histore.get.sync", t + 180, t + 190)]


HOST = ([("window", 0, 1000)] + _get_call(100) + _get_call(500)
        + [("call.put", 790, 910), ("histore.put", 800, 900),
           ("histore.put.drain", 820, 860)])
MODULES = [("jit_histore_get(1)", 125, 190), ("jit_histore_tick(2)", 215, 245),
           ("jit_histore_get(1)", 525, 590), ("jit_histore_tick(2)", 615, 645),
           ("jit_histore_apply(3)", 825, 855)]
DEV = {"/device:TPU:0": {"XLA Modules": MODULES}}
# by hand: each GET call is 200 ns with 65 + 30 ns busy inside, its lease
# tick 60 ns, its tick program 30 ns, 3 syncs; the one PUT drains 40 ns
HAND = {"host_ms.get": (200 - 95) * 1e-6, "lease_tick_ms.get": 60e-6,
        "device_ms.tick": 30e-6, "syncs.get": 3.0, "drain_ms.put": 40e-6}


@pytest.fixture(scope="module")
def synthetic():
    return pt.reduce(HOST, DEV)


@pytest.mark.parametrize("name", NEW)
def test_new_reader_on_synthetic_spans(name, synthetic):
    v = spec.metric_reader(name)({"histore": synthetic})
    assert v == pytest.approx(HAND[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_new_reader_with_nothing_to_read_returns_none(name):
    read = spec.metric_reader(name)
    assert read({}) is None
    assert read({"histore": {}}) is None
    # a harness record from a program without histore spans
    assert read({"histore": pt.reduce(
        [("window", 0, 1000), ("call.get", 10, 20)], DEV)}) is None


def test_synthetic_modules_and_idle_gaps(synthetic):
    r = synthetic
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["modules"] == {
        "jit_histore_get": {"count": 2, "device_s": pytest.approx(130e-9)},
        "jit_histore_tick": {"count": 2, "device_s": pytest.approx(60e-9)},
        "jit_histore_apply": {"count": 1, "device_s": pytest.approx(30e-9)}}
    assert r["busy_s"] == pytest.approx(220e-9)
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(780e-9)
    # idle stretches go to the innermost histore span over them; the
    # harness's call.get only where no histore span is open
    want = {"call.get": 40, "histore.get.prepare": 20,
            "histore.get.launch.get": 20, "histore.get.sync": 70,
            "histore.get.launch.tick": 20, "histore.get.lease_tick": 20,
            "histore.get.merge": 40, "histore.get": 20, "call.put": 20,
            "histore.put": 60, "histore.put.drain": 10,
            "none": 90 + 180 + 80 + 90}
    assert gaps == {k: pytest.approx(v * 1e-9) for k, v in want.items()}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The tiny ``paper-32b.ycsb-a`` window traced on one TPU v5e by the
    parent program: no histore spans, store programs named jit__lambda."""
    p = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    p.write_bytes(gzip.decompress((DATA / "tiny.xplane.pb.gz").read_bytes()))
    return str(p)


def test_existing_readers_keep_their_values(tiny):
    """The readers that were there before read what they read before on
    the recorded trace (values of the harness as it was)."""
    rec = tr.reduce_trace(*tr.read_xplane(tiny),
                          modules={"jit__lambda", "jit__tick_body"})
    pinned = {"client_host_ms.get": 32.88652075,
              "device_ms.get": 0.021921666666666666,
              "device_ms.put": 0.11636958333333332,
              "idle_share.read": 99.58907020558703}
    for name, want in pinned.items():
        assert spec.metric_reader(name)(rec) == pytest.approx(
            want, rel=1e-12), name


def test_modules_sum_to_the_busy_time(tiny):
    r = pt.reduce(*pt.read(tiny))
    rec = tr.reduce_trace(*tr.read_xplane(tiny))
    assert r["window_s"] == rec["window_s"]
    assert r["busy_s"] == pytest.approx(rec["busy_s"], rel=1e-12)
    assert sum(m["device_s"] for m in r["modules"].values()) == \
        pytest.approx(rec["busy_s"], rel=1e-9)
    assert r["modules"]["jit__lambda"]["count"] == 24    # 12 gets, 12 puts
    assert r["spans"] == {}
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        rec["window_s"] - rec["busy_s"], rel=1e-9)
    for name in NEW:
        assert spec.metric_reader(name)({"histore": r}) is None


@pytest.fixture(scope="module")
def tiny_spans(tmp_path_factory):
    """The same tiny window traced on one TPU v5e with the program's own
    spans and store module names (``record_trace.py``)."""
    p = tmp_path_factory.mktemp("trace") / "tiny_spans.xplane.pb"
    p.write_bytes(gzip.decompress(
        (DATA / "tiny_spans.xplane.pb.gz").read_bytes()))
    return str(p)


def test_recorded_program_spans_nest_in_the_calls(tiny_spans):
    host, _ = pt.read(tiny_spans)
    calls = [x for x in host if x[0].startswith("call.")]
    ops = [x for x in host if x[0].startswith("histore.")
           and x[0].count(".") == 1]
    phases = [x for x in host if x[0].count(".") >= 2
              and x[0].startswith("histore.")]
    assert {n for n, *_ in ops} == {"histore.get", "histore.put"}
    for n, s, e in ops:       # each client call inside its harness call
        assert any(c == "call." + n[8:] and cs <= s and e <= ce
                   for c, cs, ce in calls), (n, s)
    for n, s, e in phases:    # each phase inside a call of its op
        op = "histore." + n.split(".")[1]
        assert any(o == op and os_ <= s and e <= oe
                   for o, os_, oe in ops), (n, s)


def test_recorded_modules_match_the_launches(tiny_spans):
    """Each program's module runs once per launch span in the window, and
    order matching still works with the named modules."""
    r = pt.reduce(*pt.read(tiny_spans))
    spans = r["spans"]
    for prog in ("get", "put", "tick"):
        launches = sum(v["count"] for k, v in spans.items()
                       if k.endswith(f".launch.{prog}"))
        assert launches > 0
        assert r["modules"][f"jit_histore_{prog}"]["count"] == launches
    rec = tr.reduce_trace(*tr.read_xplane(tiny_spans),
                          modules={"jit_histore_get", "jit_histore_put",
                                   "jit_histore_tick"})
    assert {k: v["count"] for k, v in rec["programs"].items()} == {
        p: r["modules"][f"jit_histore_{p}"]["count"]
        for p in ("get", "put", "tick")}
    for name in NEW[:-1]:       # no drain in so short a window
        v = spec.metric_reader(name)({"histore": r})
        assert v is not None and v > 0, name
    assert spec.metric_reader("syncs.get")({"histore": r}) == 3.0
    assert spec.metric_reader("drain_ms.put")({"histore": r}) == 0.0
