"""HiStore (the paper's own system) deployment configuration.

These are the KV-store parameters used by the core library, the examples and
the paper-reproduction benchmarks.  Defaults mirror the paper's evaluation
setup scaled to this container: key 16 B (we use int64 keys + a 64-bit
signature pair — see DESIGN.md §Key codec), value 32 B, chained hash buckets
of 7+1 slots (64 B), skiplist → 128-fanout hierarchical sorted directory.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass


@dataclass(frozen=True)
class HiStoreConfig:
    # hash index ---------------------------------------------------------
    slots_per_bucket: int = 8      # paper: 7 slots + next ptr in a 64B bucket;
                                   # we pre-link chains so all 8 are key slots
    max_chain: int = 4             # pre-linked chain length (paper: dynamic)
    load_factor: float = 0.5       # buckets over-provisioned to avoid resizing
    # sorted index (skiplist → hierarchical directory) --------------------
    fanout: int = 128              # TPU lane width; one "express lane" hop
                                   # searches a 128-wide node branchlessly
    # index group ---------------------------------------------------------
    n_backups: int = 2             # replicas of the sorted index (paper §3.3)
    log_capacity: int = 1 << 16    # per-group append-only log entries
    # value store ----------------------------------------------------------
    value_words: int = 4           # 32 B values = 4 x int64 words
    n_value_replicas: int = 1      # mirror copies of each data shard; data
                                   # servers are their own failure domain
                                   # (paper §2), so value replication is
                                   # independent of n_backups
    # distribution ---------------------------------------------------------
    groups_per_device: int = 1
    # failure detection ----------------------------------------------------
    lease_misses: int = 3          # master switch: 0 disables detection
                                   # entirely (no heartbeat reads).  In
                                   # "rounds" mode it is also the bound:
                                   # observation rounds a server may miss
                                   # heartbeats before the client demotes
                                   # it to degraded routing
    lease_clock: str = "wall"      # "wall": leases age by elapsed
                                   # time.monotonic() — the paper §5
                                   # semantics; an idle client detects via
                                   # the background ticker.  "rounds": age
                                   # by observation rounds (deterministic
                                   # test mode — the exact lease_misses
                                   # detection bound)
    lease_timeout_s: float = 1.0   # wall mode: a heartbeat stalled this
                                   # long demotes the server
    lease_interval_s: float = 0.25  # wall mode: the client-side background
                                   # ticker issues a heartbeat-only tick
                                   # round whenever no foreground traffic
                                   # ran for this long
    # telemetry ------------------------------------------------------------
    telemetry: str = "counters"    # "off": record nothing (snapshots never
                                   # change); "counters" (the default): op
                                   # counters + log-bucketed latency
                                   # histograms, no device access, and
                                   # histore.* profiler spans that record
                                   # only under a profiler session
                                   # (core/telemetry.py)
    # batching -------------------------------------------------------------
    async_apply_batch: int = 4096  # log entries merged into the sorted index
                                   # per asynchronous apply
    # kernel dispatch -------------------------------------------------------
    use_kernels: str = "auto"      # "on": serve the index hot path (GET
                                   # probe, scan bounds, log->sorted merge)
                                   # through the Pallas kernels in
                                   # kernels/ops.py; "off": the pure-jnp
                                   # reference path; "auto" (default):
                                   # on a TPU the kernels that lower
                                   # there (kernels/ops.py TPU_KERNELS),
                                   # jnp for every other op and
                                   # everywhere else — the
                                   # HISTORE_USE_KERNELS env var ("on"/
                                   # "off") overrides auto, which is how
                                   # CI runs the interpret-mode kernel
                                   # leg without touching configs.  Both
                                   # paths are bit-exact by contract
                                   # (DESIGN.md §Kernelized index hot
                                   # path)

    def __post_init__(self):
        if self.use_kernels not in ("off", "on", "auto"):
            raise ValueError(
                f"use_kernels must be 'off', 'on' or 'auto', "
                f"got {self.use_kernels!r}")


DEFAULT = HiStoreConfig()


def scaled(**kw) -> HiStoreConfig:
    return dataclasses.replace(DEFAULT, **kw)
