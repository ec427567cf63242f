"""Each cell's phases rehearsed on a CPU device at a tiny size against
the reference: set-up, warm-up, an open-loop window, the comparison.
The platform check is skipped here by calling ``run_cell`` directly."""
from __future__ import annotations

import json
import shutil
import time

import jax
import pytest

from bench import run, spec
from bench.tests.tiny import CELLS, tiny_cell

SEED = 2 ** 31 + 77          # larger than 32 signed bits hold


def _run(cell, seed=SEED, trace=False, **kw):
    logs = []
    out = run.run_cell(cell, jax.devices()[:1], seed, 1.0, trace,
                       t_start=time.perf_counter(), log=logs.append, **kw)
    return out, logs


@pytest.mark.parametrize("name", CELLS)
def test_cell_matches_the_reference(name):
    out, logs = _run(tiny_cell(name))
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m["name"] for m in
                                   tiny_cell(name).end_to_end}
    assert out["attempted"] == 400 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert "programs compiled inside the window 0" in logs[0]


def test_a_wrong_value_is_caught(monkeypatch):
    """The comparison is live: a reference that expects other values
    makes the run not correct."""
    real = run.value_of
    monkeypatch.setattr(run, "value_of",
                        lambda k, v, w: real(k, v + 1, w))
    out, _ = _run(tiny_cell("ycsb-1kb.ycsb-c"))
    assert not out["correct"]
    assert out["checks"]["get_value_diff"]["value"] > 0
    assert out["checks"]["readback_diff"]["value"] > 0


def test_traced_run_reports_the_same_checks():
    out, _ = _run(tiny_cell("paper-32b.ycsb-a"), trace=True)
    assert out["correct"], out["checks"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_new_mix_and_cell_need_no_edit(tmp_path):
    """A traffic file and a cell entry added beside the existing ones run
    with no existing file changed."""
    for d in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(spec.ROOT / "bench" / d, tmp_path / "bench" / d)
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "ycsb-1kb.ycsb-b", "config": "ycsb-1kb",
        "traffic": "ycsb-b", "chips": 1, "why": "read-mostly"})
    for m in bench["end_to_end"]:
        if m["name"] == "read_p99_ms":
            m["workloads"].append("ycsb-1kb.ycsb-b")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench" / "traffic" / "ycsb-b.json").write_text(json.dumps({
        "name": "ycsb-b", "proportions": {"read": 0.95, "update": 0.05},
        "request_distribution": "scrambled_zipfian",
        "zipfian_constant": 0.99}))
    (tmp_path / "bench" / "cells" / "ycsb-1kb.ycsb-b.json").write_text(
        json.dumps({"rate_ops_per_s": 500}))
    cell = tiny_cell("ycsb-1kb.ycsb-b", root=tmp_path)
    assert cell.traffic["proportions"]["read"] == 0.95
    assert [m["name"] for m in cell.end_to_end] == ["read_p99_ms",
                                                    "setup_s"]
    out, _ = _run(cell)
    assert out["correct"], out["checks"]
    assert sorted(out["metrics"]) == ["read_p99_ms", "setup_s"]


def test_main_refuses_a_cpu(capsys):
    """Without a TPU the command exits non-zero and prints no result."""
    assert jax.devices()[0].platform != "tpu"
    name = json.loads((spec.ROOT / "BENCHMARK.json").read_text())[
        "workloads"][0]["name"]
    rc = run.main(["--workload", name, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc != 0
    out = capsys.readouterr().out.strip().splitlines()
    assert not out or not out[-1].startswith("{")
