"""Compile a configuration's store programs for a described TPU v5e, no
chip needed, and print each program's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py paper-32b put,apply,scan

Run by hand before a configuration's first chip run: at a deployment's
size each program takes 30-75 s to compile on a CPU host, too long for
the test suite.  The persistent compilation cache is off: such a compile
could be written to it but not read back without a chip.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from bench.store import store_config
    from repro.core import kvstore as kv
    from repro.sharding.smap import make_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    config = json.loads((ROOT / "bench" / "configs"
                         / f"{argv[0]}.json").read_text())
    progs = argv[1].split(",")
    cfg = store_config(config)
    B = int(config["keys_per_call"])
    cap = int(config["store"]["capacity_per_group"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = make_mesh((1,), (kv.AXIS,), devices=topo.devices[:1])
    store = jax.eval_shape(functools.partial(kv.create, mesh, cap, cfg))
    q = NamedSharding(mesh, P(kv.AXIS))
    keys = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=q)
    valid = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=q)
    vals = jax.ShapeDtypeStruct((B, cfg.value_words), jnp.int32, sharding=q)
    one = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=q)
    ops = kv.make_ops(mesh, cfg, capacity_q=B,
                      scan_limit=int(config["scan_limit"]))
    args = {"put": (store, keys, vals, valid), "get": (store, keys, valid),
            "apply": (store,), "scan": (store, one, one)}
    for p in progs:
        t = time.perf_counter()
        m = ops[p].lower(*args[p]).compile().memory_analysis()
        print(json.dumps({
            "config": config["name"], "program": p,
            "compile_s": round(time.perf_counter() - t, 1),
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
