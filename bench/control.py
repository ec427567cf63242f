"""The control: the plain reference put in the store's place, one write
call behind.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

``ControlStore`` answers every GET and SCAN from the state before the
last acknowledged write call (at the start of the window, the load's
last call), which breaks the configuration's guarantee that a GET
returns the last acknowledged write and a SCAN every key acknowledged
before it.  Its answers are device arrays, made on the chip by the same
value generator as the store's.  Each seed runs the cell's own traffic,
at its own size and rate, through the same front end and comparison as
a benchmark run; the control passes when every seed comes out not
correct.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402


class ControlStore:
    """Serves the front end from sorted numpy state, stale by one write
    call.  Same calls as ``bench.store.Store``."""

    def __init__(self, devices, config: dict):
        import jax.numpy as jnp

        from bench.store import value_fn

        self.devices = list(devices)
        self.words = int(config["store"]["value_words"])
        self.B = int(config["keys_per_call"])
        self.scan_limit = int(config["scan_limit"])
        self._values = value_fn(self.words)
        self._jnp = jnp
        self.k = np.zeros(0, np.int32)      # sorted keys ever seen
        self.ver = np.zeros(0, np.int64)    # their visible version, -1 none
        self.held = (np.zeros(0, np.int32), np.zeros(0, np.int64))

    def _apply(self, keys, versions) -> None:
        order = np.argsort(keys)
        keys, versions = keys[order], versions[order]
        pos = np.searchsorted(self.k, keys)
        have = pos < len(self.k)
        have[have] = self.k[pos[have]] == keys[have]
        self.ver[pos[have]] = versions[have]
        new = ~have
        if new.any():
            at = np.searchsorted(self.k, keys[new])
            self.k = np.insert(self.k, at, keys[new])
            self.ver = np.insert(self.ver, at, versions[new])

    def _lookup(self, keys):
        pos = np.minimum(np.searchsorted(self.k, keys), len(self.k) - 1)
        hit = self.k[pos] == keys
        return pos, np.where(hit, self.ver[pos], -1)

    def load(self, keys) -> None:
        keys = np.asarray(keys, np.int32)
        last = len(keys) - (len(keys) - 1) % self.B - 1
        self._apply(keys[:last], np.zeros(last, np.int64))
        self.held = (keys[last:], np.zeros(len(keys) - last, np.int64))

    def warm(self, keys, groups, sizes) -> None:
        for s in sizes:
            self._values(self._jnp.asarray(keys[:s]),
                         self._jnp.zeros(s, self._jnp.int32))

    def get(self, keys, keep_values: bool):
        pos, ver = self._lookup(np.asarray(keys, np.int32))
        jnp = self._jnp
        vals = self._values(jnp.asarray(keys, jnp.int32),
                            jnp.asarray(ver, jnp.int32))
        found = jnp.asarray(ver >= 0)
        return (found, vals if keep_values else None,
                jnp.asarray(pos.astype(np.int32))), vals

    def put(self, keys, versions):
        self._apply(*self.held)
        self.held = (np.asarray(keys, np.int32),
                     np.asarray(versions, np.int64))
        ok = self._jnp.ones(len(keys), bool)
        return (ok,), ok

    def scan(self, start_key: int):
        live = self.k[self.ver >= 0]
        got = live[np.searchsorted(live, start_key):][:self.scan_limit]
        kk = np.full(self.scan_limit, 2 ** 31 - 1, np.int32)
        kk[:len(got)] = got
        kk = self._jnp.asarray(kk)
        return (kk, np.int32(len(got)), True), kk

    def demotions(self) -> int:
        return 0

    def replica_keys(self) -> list:
        return [self.k[self.ver >= 0]]

    def mirror_values(self, addrs) -> np.ndarray:
        a = np.asarray(addrs)
        return np.asarray(self._values(self._jnp.asarray(self.k[a]),
                                       self._jnp.asarray(self.ver[a],
                                                         self._jnp.int32)))

    def memory_peak(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)


def main(argv=None) -> int:
    from bench import run, spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        devices = run.tpu_devices(cell.chips)
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache(ROOT)
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(cell, devices, seed, args.seconds, False,
                           server_factory=ControlStore,
                           t_start=time.perf_counter(), log=lambda m: None)
        checks = {k: v["value"] for k, v in out["checks"].items()}
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": out["correct"], "checks": checks}),
              flush=True)
        failed_all &= not out["correct"]
    print(f"control came out not correct on every seed: {failed_all}")
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
