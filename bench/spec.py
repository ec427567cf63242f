"""Finds a cell's parts by name: its entry in ``BENCHMARK.json``, the
configuration file it names, ``bench/traffic/<traffic>.json``,
``bench/cells/<cell>.json`` (the offered rate its sweep found) and the
readers of its per-layer metrics, ``bench/metrics/<metric>.py``
(``metric_reader``).  A new cell, configuration, mix or metric is a new
file and a new entry; no existing file changes."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    rate: float
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)
    root: Path = ROOT


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"there are {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    params = json.loads(
        (root / "bench" / "cells" / f"{name}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, rate=float(params["rate_ops_per_s"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)],
                root=root)


def metric_reader(name: str, root: Path = ROOT):
    """``read(record) -> float | None`` of one per-layer metric, from
    ``bench/metrics/<name>.py``, or, for a quantity split by the cells
    it serves (``idle_share.read``, ``idle_share.scan``), from the file
    of the name before its first dot."""
    d = Path(root) / "bench" / "metrics"
    path = d / f"{name}.py"
    if not path.exists():
        path = d / f"{name.split('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
