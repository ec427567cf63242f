"""The system under test: one ``HiStoreClient`` over one
``DistributedBackend`` on the given devices, as the configuration file
states it, and the calls the front end makes into it.

In a traced run every program the backend launches is wrapped in an
``op.<name>`` span around its dispatch, with no sync added, so the traced
window keeps the untraced one's schedule; ``bench/trace.py`` matches
these launches to the device's module executions in order.  The
untraced run calls the programs as they are.
"""
from __future__ import annotations

import re

import numpy as np

KEY_HI = 2 ** 31 - 2          # upper bound of every scan: all keys lie below


def store_config(config: dict):
    """The store's ``HiStoreConfig`` from the configuration file."""
    from repro.configs.histore import scaled

    p = dict(config["store"])
    p.pop("capacity_per_group")
    return scaled(**p)


def value_fn(words: int):
    """The jitted device-side value generator: ``reference.value_of``'s
    arithmetic, mod 2**32, zeros where the version is negative."""
    import jax
    import jax.numpy as jnp

    def values(keys, versions):
        k = keys.astype(jnp.uint32)
        v = versions.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        w = jnp.arange(words, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9)
        x = k[:, None] * jnp.uint32(2654435761) + w[None, :] + v[:, None]
        return jnp.where(versions[:, None] >= 0,
                         (x >> 1).astype(jnp.int32), 0)

    return jax.jit(values)


class Store:
    """Builds the store and serves the front end's calls."""

    def __init__(self, devices, config: dict):
        import jax
        import jax.numpy as jnp

        from repro.core import kvstore as kv
        from repro.core.client import DistributedBackend, HiStoreClient
        from repro.sharding.smap import make_mesh

        self.devices = list(devices)
        self.cfg = store_config(config)
        self.words = self.cfg.value_words
        self.B = int(config["keys_per_call"])
        self.scan_limit = int(config["scan_limit"])
        mesh = make_mesh((len(self.devices),), (kv.AXIS,),
                         devices=self.devices)
        self.backend = DistributedBackend(
            mesh, self.cfg,
            capacity_per_group=int(config["store"]["capacity_per_group"]),
            capacity_q=self.B, scan_limit=self.scan_limit)
        self.client = HiStoreClient(self.backend, batch_quantum=self.B,
                                    max_batch=self.B)
        self._values = value_fn(self.words)
        self._jnp = jnp

    # -- the calls the front end makes --------------------------------------
    def values(self, keys, versions):
        return self._values(self._jnp.asarray(keys, self._jnp.int32),
                            self._jnp.asarray(versions, self._jnp.int32))

    def get(self, keys, keep_values: bool):
        r = self.client.get(keys)
        keep = (r.found, r.values if keep_values else None, r.addrs)
        return keep, r.values

    def put(self, keys, versions):
        r = self.client.put(keys, self.values(keys, versions))
        return (r.ok,), r.ok

    def scan(self, start_key: int):
        r = self.client.scan(start_key, KEY_HI, self.scan_limit)
        ok = np.bool_(r.complete)
        return (r.keys, r.count, ok), r.keys

    # -- set-up -------------------------------------------------------------
    def load(self, keys) -> None:
        """Write every record at version 0 through ``client.put``, one
        full call at a time, then apply every log entry."""
        zeros = np.zeros(self.B, np.int32)
        for s in range(0, len(keys), self.B):
            ch = keys[s:s + self.B]
            r = self.client.put(ch, self.values(ch, zeros[:len(ch)]))
            bad = int((~np.asarray(r.ok)).sum())
            if bad:
                raise RuntimeError(f"load: {bad} writes not acknowledged "
                                   f"in the call at record {s}")
        self.client.drain()

    def warm(self, keys, groups, sizes) -> None:
        """Run one call of every size in ``sizes`` for each group the
        traffic uses, so that the window compiles nothing.  Warm-up writes
        rewrite loaded records with their loaded value."""
        for s in sizes:
            ch = keys[:s]
            if "get" in groups:
                self.get(ch, True)[1].block_until_ready()
            if "put" in groups:
                self.put(ch, np.zeros(s, np.int32))[1].block_until_ready()
        if "scan" in groups:
            self.scan(int(keys[0]))[1].block_until_ready()
        self.client.drain()

    # -- after the window ---------------------------------------------------
    def demotions(self) -> int:
        """Servers of either plane that the client demoted or masked."""
        st = self.backend.store
        return (int((~np.asarray(st.alive)).sum())
                + int((~np.asarray(st.data.alive)).sum())
                + len(self.backend.detected)
                + len(self.backend.detected_data))

    def replica_keys(self) -> list:
        """The live keys of every sorted replica, after a drain."""
        self.client.drain()
        st = self.backend.store.bsorted
        keys = np.asarray(st.keys)
        size = np.asarray(st.size)
        out = []
        for r in range(keys.shape[0]):
            for g in range(keys.shape[1]):
                out.append(keys[r, g, :int(size[r, g])])
        return out

    def mirror_values(self, addrs) -> np.ndarray:
        """The value mirror's copy at each address (one chip: the mirror
        of shard 0 lives on shard 0)."""
        data = self.backend.store.data
        dcap = data.vals.shape[1]
        a = np.asarray(addrs)
        m = data.mirror[0, 0]
        return np.asarray(m[self._jnp.asarray(a % dcap)])

    def memory_peak(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    # -- tracing ------------------------------------------------------------
    def instrument(self, span, applied: list) -> set:
        """Mark every launch of a backend program with an ``op.<name>``
        span around its dispatch, adding no sync, and return the module
        names its programs run under on the device (``bench/trace.py``
        matches launches to executions in order).  ``applied`` collects,
        per apply round, the log counters it starts from, read after the
        window (for its roofline)."""
        ops = dict(self.backend.ops)

        def wrap(name, fn):
            def run(*args):
                if name == "apply":
                    applied.append((args[0].blog.tail,
                                    args[0].blog.applied))
                with span(f"op.{name}"):
                    return fn(*args)
            return run

        self.backend.ops = {k: wrap(k, f) for k, f in ops.items()}
        return {module_name(f) for f in ops.values()}


def module_name(fn) -> str:
    """The name XLA gives the module of ``jax.jit(fn)``: ``jit(<name>)``
    with every character outside ``[\\w.-]`` made ``_``, trailing ones
    dropped (``jit__lambda``, ``jit__tick_body``)."""
    name = getattr(fn, "__name__", "<unnamed function>")
    return re.sub(r"[^\w.\-]", "_", f"jit({name})").rstrip("_")


def applied_entries(applied: list, batch: int) -> int:
    """Log entries the recorded apply rounds took: per round, each
    replica's pending entries up to one batch."""
    return sum(int(np.minimum(np.asarray(t) - np.asarray(a), batch).sum())
               for t, a in applied)
