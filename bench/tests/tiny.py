"""A cell cut to a size the CPU test run can hold: the same traffic,
store and front end, with a small store and call.  A cell that
BENCHMARK.json does not declare is built from its configuration and
mix, with the metrics that every cell reports, so every configuration
runs under every mix here, declared or not."""
from __future__ import annotations

import copy
import json

from bench import spec

TINY_STORE = {"capacity_per_group": 4096, "log_capacity": 512,
              "async_apply_batch": 512}
# every configuration under every mix
CELLS = ["ycsb-1kb.ycsb-c", "paper-32b.ycsb-e", "paper-32b.ycsb-a",
         "ycsb-1kb.ycsb-a"]


def _cell(name: str, root) -> spec.Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if name in {w["name"] for w in bench["workloads"]}:
        return spec.load_cell(name, root)
    config, traffic = name.split(".", 1)
    read = lambda p: json.loads((root / "bench" / p).read_text())  # noqa
    every = lambda ms: [m for m in ms if "workloads" not in m]  # noqa
    return spec.Cell(
        name=name, chips=1, config=read(f"configs/{config}.json"),
        traffic=read(f"traffic/{traffic}.json"), rate=0.0,
        end_to_end=every(bench["end_to_end"]),
        per_layer=every(bench["per_layer"]), root=root)


def tiny_cell(name: str, root=spec.ROOT, records: int = 1024,
              keys_per_call: int = 64, rate: float = 400.0):
    cell = _cell(name, root)
    cell.config = copy.deepcopy(cell.config)
    cell.config["store"].update(TINY_STORE)
    cell.config["records"] = records
    cell.config["keys_per_call"] = keys_per_call
    cell.rate = rate
    return cell
