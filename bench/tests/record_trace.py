"""Record the small chip trace that test_trace.py reduces.

    python3 bench/tests/record_trace.py <out_dir>

Runs the tiny ``paper-32b.ycsb-a`` cell (get, put and apply programs)
with ``--trace 1`` on the chip for half a second and copies the
profiler's ``.xplane.pb``, gzipped, to ``<out_dir>/tiny.xplane.pb.gz``;
it belongs in ``bench/testdata/``.
"""
from __future__ import annotations

import gzip
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    from bench import run
    from bench.tests.tiny import tiny_cell
    from bench.trace import Tracer

    out = Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    devices = run.tpu_devices(1)
    run.enable_compile_cache(ROOT)
    cell = tiny_cell("paper-32b.ycsb-a", rate=200.0)
    kept = {}
    stop = Tracer.stop

    def keep(self):
        kept["path"] = stop(self)
        return kept["path"]

    Tracer.stop = keep
    res = run.run_cell(cell, devices, 5, 0.5, True,
                       t_start=time.perf_counter(), out_dir=out / "work")
    (out / "tiny.xplane.pb.gz").write_bytes(
        gzip.compress(Path(kept["path"]).read_bytes(), 9))
    print(res["metrics"], res["device"], res["correct"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
