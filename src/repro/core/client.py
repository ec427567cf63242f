"""HiStoreClient: one typed front door over the hybrid index.

The paper's client sees a single KV interface (GET/PUT/DELETE/SCAN) no
matter whether a request lands on the hash table, a skiplist replica, or a
degraded backup path.  This module is that front door for the repro:

    client = HiStoreClient(LocalBackend(4096, cfg))          # one node
    client = HiStoreClient(DistributedBackend(mesh, cfg))    # shard_map'd

    res = client.put(keys, values)       # PutResult(ok, addrs, retries)
    res = client.get(keys)               # GetResult(addrs, found, acc, vals)
    res = client.delete(keys)            # DeleteResult(ok, found, retries)
    res = client.scan(lo, hi, limit)     # ScanResult(keys, addrs, count)

Responsibilities the old per-layer surfaces pushed onto every caller:

  * fixed-shape batching — requests are padded to power-of-two batch sizes
    (and a multiple of the device count for the distributed backend), so
    the jitted ops stop recompiling per batch size; oversize requests are
    split into ``max_batch`` chunks;
  * overflow push-back — capacity overflow (exchange-buffer ok=False, the
    paper's RPC queue-full) becomes a bounded client-side retry loop with
    async-apply + GC-flush drains in between, instead of a
    silently-surfaced flag;
  * async-apply scheduling — the backups' log->sorted merges run every
    ``apply_every_n_ops`` mutating ops (the paper's worker threads),
    instead of callers hand-invoking drains;
  * migration policy — ``migrate_on_recover`` (default on) runs the
    background value migration after every recovery, restoring one-RTT
    GETs (``GetResult.hops`` back to 1); turn it off to measure the
    second-hop fetch cost the paper's data plane would otherwise pay.

Backends implement the ``Backend`` protocol (core/backend.py — serving
ops + telemetry gauges + lease/fault-injection hooks; re-exported here);
see DESIGN.md §Client API for the migration table from the old surfaces.
"""
from __future__ import annotations

import functools
import threading
import time
import warnings
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import data_plane as dpl
from repro.core.backend import Backend  # noqa: F401  (re-export)
from repro.core import index_group as ig
from repro.core import kvstore as kv
from repro.core import log as lg
from repro.core import telemetry as tm
from repro.core.hashing import key_dtype, key_inf, next_pow2
from repro.core.results import (DeleteResult, FailResult, GetResult,
                                PutResult, RecoverResult, ScanResult)

I32 = jnp.int32


# ---------------------------------------------------------------------------
# Local backend: one index group + the node's data shard, jitted ops
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(0, 7, 8))
def _local_put(cfg, g, vals, used, keys, vs, valid, backups_alive,
               primary_alive):
    dcap = vals.shape[0]
    # one slot per key per batch (last writer wins, like the hash insert);
    # overwrites update their old slot in place — the data-server GC —
    # so the shard reuses capacity instead of wrapping onto live slots
    winner = dpl.winner_mask(keys, valid)
    old_a, old_f = ig.owner_addr_probe(g, keys, cfg, primary_alive)
    inplace = winner & old_f & (old_a >= 0) & (old_a < dcap)
    used, slot, aok = dpl.alloc(used, winner & ~inplace)
    wslot = jnp.where(inplace, old_a, jnp.where(aok, slot, dcap))
    wmask = inplace | aok
    vals = vals.at[jnp.where(wmask, wslot, dcap)].set(vs, mode="drop")
    addr_lane = jnp.where(wmask, wslot, -1).astype(I32)
    addrs = dpl.spread_winner_addr(keys, valid, winner, addr_lane)
    landed = valid & (addrs >= 0)   # shard full -> un-acked, client retries
    g, ok, nrep = ig.put(g, keys, addrs, cfg, landed,
                         backups_alive=backups_alive, with_nrep=True)
    # un-acked fresh allocations roll back ONLY when no backup log
    # recorded the entry (same nrep == 0 rule as the distributed body: a
    # slot a replica log references must never return to the allocator)
    used = dpl.free_slots(used, slot, aok & ~ok & (nrep == 0))
    return g, vals, used, ok & landed, addrs, nrep


@functools.partial(jax.jit, static_argnums=(0, 5))
def _local_get(cfg, g, dvals, keys, valid, primary_alive):
    addr, found, acc = ig.get(g, keys, cfg, primary_alive=primary_alive)
    found = found & valid
    dcap = dvals.shape[0]
    slot = jnp.where(found & (addr >= 0) & (addr < dcap), addr, dcap)
    padded = jnp.concatenate(
        [dvals, jnp.zeros((1,) + dvals.shape[1:], dvals.dtype)])
    vals = padded[jnp.clip(slot, 0, dcap)]
    return (jnp.where(found, addr, -1).astype(I32), found,
            jnp.where(valid, acc, 0), vals, valid,
            valid.astype(I32))      # single shard: every read is one hop


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _local_delete(cfg, g, used, keys, valid, backups_alive, primary_alive):
    # data-server GC: a committed DELETE frees its value slot (the
    # returned found is already gated on the log acks; winner-deduped so
    # a double-delete within one batch frees exactly once)
    winner = dpl.winner_mask(keys, valid)
    old_a, old_f = ig.owner_addr_probe(g, keys, cfg, primary_alive)
    dcap = used.shape[0]
    g, found = ig.delete(g, keys, cfg, valid, backups_alive=backups_alive,
                         primary_alive=primary_alive)
    freed = winner & found & old_f & (old_a >= 0) & (old_a < dcap)
    used = dpl.free_slots(used, old_a, freed)
    return g, used, found & valid


class LocalBackend:
    """One index group (1 hash + n_backups sorted replicas + logs) plus the
    value shard a single-node deployment owns — slot-allocated and GC'd by
    the data plane's bitmap (data_plane.alloc/free_slots).  The client's
    routing hint: liveness is tracked host-side (the paper's client knows
    which servers are up), so healthy GETs compile the one-sided hash path
    only."""

    def __init__(self, capacity: int, cfg, value_words: Optional[int] = None):
        self.cfg = cfg
        self.telemetry = tm.Telemetry(getattr(cfg, "telemetry",
                                              "counters"))
        self.capacity = capacity
        self.group = ig.create(capacity, cfg)
        self.value_words = value_words or cfg.value_words
        self.vals = jnp.zeros((capacity, self.value_words), I32)
        self.used = jnp.zeros((capacity,), bool)
        self.batch_multiple = 1
        self.max_mutation_batch = cfg.log_capacity
        self._primary_alive = True
        self._backups_alive = [True] * cfg.n_backups
        self._pending_bound = 0   # host-side upper bound on log pending

    def _ensure_log_room(self, n: int):
        """Backup logs reject appends when their pending window is full;
        the client caps mutation chunks at log_capacity, so draining up
        front guarantees the whole batch fits (no bounced acks).  The
        host-side bound avoids a device sync per mutation; it only ever
        over-estimates, so at worst we drain early."""
        if self._pending_bound + n > self.cfg.log_capacity:
            self.drain()

    def put(self, keys, vals, valid):
        n = int(self.telemetry.sync(valid.sum()))
        self._ensure_log_room(n)
        self._pending_bound += n
        ba = tuple(self._backups_alive)
        hint = True if self._primary_alive else None
        self.group, self.vals, self.used, ok, addrs, nrep = _local_put(
            self.cfg, self.group, self.vals, self.used, keys, vals, valid,
            ba, hint)
        return ok, addrs, nrep

    def get(self, keys, valid):
        hint = True if self._primary_alive else None
        return _local_get(self.cfg, self.group, self.vals, keys, valid,
                          hint)

    def delete(self, keys, valid):
        n = int(self.telemetry.sync(valid.sum()))
        self._ensure_log_room(n)
        self._pending_bound += n
        ba = tuple(self._backups_alive)
        hint = True if self._primary_alive else None
        self.group, self.used, found = _local_delete(
            self.cfg, self.group, self.used, keys, valid, ba, hint)
        # room is guaranteed above (chunks capped at log_capacity + the
        # up-front drain), so every valid lane is acked this round
        return valid, found, valid.astype(I32) * sum(ba)

    def scan(self, lo, hi, limit: int):
        (k, a, n), self.group = ig.scan(self.group, lo, hi, limit, self.cfg)
        self._pending_bound = 0          # scan drained the logs
        # single node: the process answering IS the store — a scan that
        # returns at all covered its one group
        return k, a, n, jnp.ones((1,), bool)

    def apply_async(self):
        self.telemetry.count("apply_rounds")
        self.group = ig.apply_async(self.group, self.cfg)
        self._pending_bound = max(
            0, self._pending_bound - self.cfg.async_apply_batch)

    def drain(self):
        tel = self.telemetry
        with tel.span("drain"):
            tel.count("drains")
            self.group = ig.drain(self.group, self.cfg)
            self._pending_bound = 0

    def pending_ops(self) -> int:
        return int(self.telemetry.sync(
            lg.pending_count(self.group.blogs).max()))

    def telemetry_gauges(self) -> dict:
        """Snapshot-time gauges: the single node's liveness is host-side
        and its one shard has no free queue, so only the pending-log
        depth needs a device fetch."""
        return {
            "live_index_servers": (int(self._primary_alive)
                                   + sum(map(int, self._backups_alive))),
            "live_data_servers": 1,
            "pending_log_ops": self.pending_ops(),
            "freeq_pending": 0,
            "fq_spill": 0,
        }

    def migrate_values(self) -> int:
        return 0   # one shard: every value is already home

    def lease_stalled(self) -> bool:
        return False   # liveness is host-side: no leases to stall

    def fail_data_server(self, server: int = 0):
        raise NotImplementedError(
            "LocalBackend owns a single unreplicated value shard — no "
            "surviving copy could exist; data-server failures are "
            "modelled by DistributedBackend (cfg.n_value_replicas)")

    recover_data_server = fail_data_server

    def sever_server(self, server: int = 0):
        raise NotImplementedError(
            "heartbeat severing needs the distributed backend's "
            "lease detector; LocalBackend liveness is host-side")

    def sever_data_server(self, server: int = 0):
        raise NotImplementedError(
            "data-server heartbeat severing needs the distributed "
            "backend's lease detector; LocalBackend owns a single "
            "unreplicated shard")

    def fail_server(self, server: int = 0):
        self.group = ig.fail(self.group, server)
        if server == 0:
            self._primary_alive = False
        else:
            self._backups_alive[server - 1] = False
        self.telemetry.count("index_demotions")

    def recover_server(self, server: int = 0, online: bool = True):
        if server == 0:
            self.group = ig.recover_primary(self.group, self.cfg,
                                            online=online)
            self._primary_alive = True
        else:
            self.group = ig.recover_backup(self.group, server - 1,
                                           self.cfg, online=online)
            self._backups_alive[server - 1] = True
        self.telemetry.count("index_recoveries")


# ---------------------------------------------------------------------------
# Distributed backend: the shard_map'd store (one index group per device)
# ---------------------------------------------------------------------------
def _lease_ticker_loop(ref, stop: threading.Event) -> None:
    """Background ticker body (module-level: the thread must only hold a
    WEAK reference to the backend).  Polls at a fraction of the idle
    interval so a tick lands within one interval of the threshold being
    crossed; ``stop`` is this thread's own event, so a ticker orphaned
    by a timed-out stop_ticker() stays stopped even after
    start_ticker() installs a replacement; a garbage-collected backend
    ends the loop at the next wake-up."""
    fails = 0
    while True:
        be = ref()
        if be is None:
            return
        quantum = max(be.lease_interval_s / 5.0, 0.01)
        interval = be.lease_interval_s
        be = None                      # never hold the ref across a wait
        if stop.wait(quantum):
            return
        be = ref()
        if be is None:
            return
        try:
            if time.monotonic() - be._last_traffic_t < interval:
                continue
            with be._mu:
                # re-check under the lock: a foreground op may have
                # just run (its _lease_tick refreshed the timestamp)
                if time.monotonic() - be._last_traffic_t < interval:
                    continue
                with be.telemetry.call("ticker"):
                    be._lease_tick(bump=True)
            be.telemetry.count("ticker_rounds")
            fails = 0
        except Exception as e:   # noqa: BLE001 — a daemon thread must
            # not die silently on a transient dispatch error:
            # idle-client detection would be disabled with no signal
            fails += 1
            be.telemetry.count("ticker_errors")
            warnings.warn(
                f"lease ticker tick failed ({e!r}); "
                f"{'giving up' if fails >= 3 else 'retrying'}",
                RuntimeWarning)
            if fails >= 3:
                # a dead ticker means idle detection is OFF: latch the
                # give-up so start_ticker() stops claiming one is
                # running and the counters carry the signal
                be._ticker_gave_up = True
                be.telemetry.count("ticker_gave_up")
                return
        finally:
            be = None


class DistributedBackend:
    """Wraps the kvstore shard_map ops: routed two-sided PUT/DELETE with
    ppermute log replication, one-sided GET with second-hop fetch,
    all_gather'd SCAN, plus the value plane's GC flush and migration."""

    def __init__(self, mesh, cfg, capacity_per_group: int = 4096, *,
                 capacity_q: int = 64, scan_limit: int = 128):
        self.mesh = mesh
        self.cfg = cfg
        # the telemetry plane this backend (and the client over it)
        # reports through; validates cfg.telemetry before any device work
        self.telemetry = tm.Telemetry(getattr(cfg, "telemetry",
                                              "counters"))
        self.G = mesh.devices.size
        self.store = kv.create(mesh, capacity_per_group, cfg)
        self.ops = kv.make_ops(mesh, cfg, capacity_q=capacity_q,
                               scan_limit=scan_limit)
        self.capacity_q = capacity_q
        self.scan_limit = scan_limit
        self.batch_multiple = self.G
        self.value_words = cfg.value_words
        self.max_mutation_batch = cfg.log_capacity
        self._dead: set[int] = set()        # index servers masked dead
        self._data_dead: set[int] = set()   # data servers masked dead
        self._pending_bound = 0        # host-side upper bound, no dev sync
        # --- lease-based failure detection (paper §5) --------------------
        # every routed op bumps per-device heartbeat counters on the mesh
        # for BOTH planes (index hb + data hb); the client ages them here
        # and demotes a server to degraded routing once its lease expires
        # — no oracle fail_server/fail_data_server call anywhere in that
        # path.  Two clocks: "wall" (default — elapsed monotonic time
        # since the counter last advanced exceeds cfg.lease_timeout_s)
        # and "rounds" (the deterministic test mode: cfg.lease_misses
        # stalled observation rounds).  lease_misses == 0 disables
        # detection entirely in either mode.
        self.lease_misses = int(getattr(cfg, "lease_misses", 0) or 0)
        self.lease_clock = str(getattr(cfg, "lease_clock", "rounds"))
        self.lease_timeout_s = float(getattr(cfg, "lease_timeout_s", 0.0))
        self.lease_interval_s = float(
            getattr(cfg, "lease_interval_s", 0.0) or 0.25)
        # misconfiguration must fail loudly: silently-disabled detection
        # is the exact availability gap the liveness plane closes
        if self.lease_clock not in ("wall", "rounds"):
            raise ValueError(
                f"cfg.lease_clock must be 'wall' or 'rounds', got "
                f"{self.lease_clock!r}")
        if (self.lease_misses > 0 and self.lease_clock == "wall"
                and self.lease_timeout_s <= 0):
            raise ValueError(
                "wall-clock leases need cfg.lease_timeout_s > 0 "
                "(set lease_misses=0 to disable detection instead)")
        self._severed: set[int] = set()     # injector-crashed index srvs
        self._data_severed: set[int] = set()  # injector-crashed data srvs
        now = time.monotonic()
        self._last_hb = np.zeros((self.G,), np.int64)
        self._hb_misses = np.zeros((self.G,), np.int64)
        self._hb_t = np.full((self.G,), now, np.float64)   # last advance
        self._last_data_hb = np.zeros((self.G,), np.int64)
        self._data_hb_misses = np.zeros((self.G,), np.int64)
        self._data_hb_t = np.full((self.G,), now, np.float64)
        self.detected: list[int] = []       # index demotions the detector
        self.detected_data: list[int] = []  # data demotions the detector
        # the store and the lease state are shared with the background
        # ticker thread: one reentrant lock serializes every op
        self._mu = threading.RLock()
        self._last_traffic_t = now
        self._ticker: Optional[threading.Thread] = None
        self._ticker_stop: Optional[threading.Event] = None
        self._ticker_gave_up = False   # the loop died on repeated errors

    def _ensure_log_room(self, n: int):
        # drain up front when a batch might not fit the worst backup log
        # (chunks are capped at log_capacity, so after a drain the whole
        # batch is guaranteed to land; per-lane exchange overflow is still
        # acked honestly and retried by the client)
        if self._pending_bound + n > self.cfg.log_capacity:
            self.drain()

    def _degraded(self) -> bool:
        return bool(self._dead or self._data_dead)

    def _launch(self, name: str, *args, ops=None):
        """Dispatch store program ``name`` (of ``ops``, default this
        backend's) under a ``launch.<name>`` span.  Every program launch
        goes through here, so the trace names each dispatch."""
        with self.telemetry.span(f"launch.{name}"):
            return (ops or self.ops)[name](*args)

    # -- lease detector ----------------------------------------------------
    def _lease_expired(self, misses: np.ndarray, last_t: np.ndarray,
                       g: int, now: float) -> bool:
        """One server's lease verdict after a stalled observation: rounds
        mode counts stalled rounds against ``lease_misses``; wall mode
        measures elapsed monotonic time since the counter last advanced
        against ``lease_timeout_s`` (the paper's §5 semantics)."""
        if self.lease_clock == "wall":
            return now - last_t[g] >= self.lease_timeout_s
        return misses[g] >= self.lease_misses

    def _lease_tick(self, bump: bool = False):
        """Age the leases of BOTH planes after an observation round: a
        server whose heartbeat counter did not advance accumulates a
        stalled round (and its wall-clock stall timer keeps running); an
        expired lease demotes it to degraded routing.  ``bump`` runs the
        heartbeat-only tick op first — read-only rounds (GET) and the
        idle ticker age leases through it, mutating ops bump in-body."""
        if self.lease_misses <= 0:
            return
        tel = self.telemetry
        with tel.span("lease_tick"):
            tel.count("lease_ticks")
            if bump:
                self.store = self._launch("tick", self.store)
            now = time.monotonic()
            self._last_traffic_t = now
            # one combined device->host fetch for both planes' counters
            # (a second sequential sync would double the per-op
            # detection tax)
            hb, dhb = tel.sync((self.store.hb, self.store.data.hb))
            self._age_plane(hb, self._last_hb, self._hb_misses,
                            self._hb_t, self._dead, self._demote, now)
            self._age_plane(dhb, self._last_data_hb, self._data_hb_misses,
                            self._data_hb_t, self._data_dead,
                            self._demote_data, now)
            self._last_hb = hb
            self._last_data_hb = dhb

    def _age_plane(self, hb, last, misses, last_t, dead, demote,
                   now: float):
        """Age ONE plane's leases against its freshly-read counters —
        the single aging body both planes share, so every lease-state
        invariant (renewal resets, stall accounting, expiry) applies to
        index and data servers by construction."""
        for g in range(self.G):
            if g in dead:
                continue
            if hb[g] != last[g]:
                misses[g] = 0
                last_t[g] = now
            else:
                misses[g] += 1
                if self._lease_expired(misses, last_t, g, now):
                    demote(g, detected=True)

    def _demote(self, g: int, detected: bool = False):
        """Degraded routing for index server ``g`` — the client-side half
        of a failure, with no oracle call and no state wipe (whatever
        state the server lost, it lost when it crashed)."""
        self.store = self.store._replace(
            alive=self.store.alive.at[g].set(False))
        self._dead.add(g)
        self._hb_misses[g] = 0   # a demoted server no longer "stalls"
        if detected:
            self.detected.append(g)
        self.telemetry.count("index_demotions")

    def _demote_data(self, g: int, detected: bool = False):
        """Degraded routing for DATA server ``g``: GETs of its shard fail
        over to mirror-served fetches, PUTs displace one hop (the
        degraded put variant compiles in) — the value-plane half of the
        unified liveness view, again with no oracle call."""
        self.store = self.store._replace(data=self.store.data._replace(
            alive=self.store.data.alive.at[g].set(False)))
        self._data_dead.add(g)
        self._data_hb_misses[g] = 0
        if detected:
            self.detected_data.append(g)
        self.telemetry.count("data_demotions")

    def lease_stalled(self) -> bool:
        """Did the last observation round see a not-yet-demoted server's
        heartbeat stalled (either plane)?  The client's wall-mode retry
        pacing keys on this, so healthy push-back retries — capacity
        overflow with every heartbeat advancing — never pay the
        lease-timeout tax."""
        return bool((self._hb_misses > 0).any()
                    or (self._data_hb_misses > 0).any())

    # -- background ticker (idle-client wall-clock detection) --------------
    def start_ticker(self) -> bool:
        """Start the client-side background ticker thread: whenever no
        foreground traffic has run for ``cfg.lease_interval_s`` it issues
        a heartbeat-only tick round, so wall-clock leases expire (and
        failures are detected) with ZERO foreground ops.  No-op when
        detection is disabled.  Returns True if a ticker is running —
        and False when a previous ticker GAVE UP after repeated tick
        errors (``ticker_gave_up`` in the metrics): pretending one is
        running would silently disable idle detection.  ``stop_ticker()``
        clears the latch for an explicit restart."""
        if self.lease_misses <= 0:
            return False
        if self._ticker_gave_up:
            return False
        if self._ticker is not None and self._ticker.is_alive():
            return True
        stop = threading.Event()
        self._ticker_stop = stop
        # the thread holds only a WEAK reference to this backend (and a
        # finalizer sets its stop event): a client dropped without
        # stop_ticker() must not pin the device-resident store nor keep
        # dispatching tick ops for the rest of the process lifetime
        self._ticker = threading.Thread(
            target=_lease_ticker_loop, args=(weakref.ref(self), stop),
            name="histore-lease-ticker", daemon=True)
        weakref.finalize(self, stop.set)
        self._ticker.start()
        return True

    def stop_ticker(self) -> None:
        # an explicit stop also clears the give-up latch: the operator
        # acknowledged the dead ticker, a fresh start_ticker() may retry
        self._ticker_gave_up = False
        if self._ticker is None:
            return
        self._ticker_stop.set()
        self._ticker.join(timeout=60.0)
        if self._ticker.is_alive():
            # still inside a long first-tick jit compile; its own stop
            # event is set, so it exits at the next loop check — and a
            # fresh start_ticker() gets a NEW event, so the straggler
            # can never be revived by it
            warnings.warn("lease ticker still draining a tick in flight "
                          "(exits at the next loop check)", RuntimeWarning)
        self._ticker = None
        self._ticker_stop = None

    # (the ticker body lives in the module-level _lease_ticker_loop so
    # the thread never holds a strong reference to the backend)

    def put(self, keys, vals, valid):
        with self._mu:
            n = int(self.telemetry.sync(valid.sum()))
            self._ensure_log_room(n)
            self._pending_bound += n
            # healthy cluster -> the lean variant; any masked-dead server
            # -> the variant with the old-slot replica probe (frees stay
            # exact at temporary primaries) and the off-dead-shard value
            # displacement
            self.store, ok, addrs, nrep = self._launch(
                "put_degraded" if self._degraded() else "put",
                self.store, keys, vals, valid)
            self._lease_tick()
            return ok, addrs, nrep

    def get(self, keys, valid):
        tel = self.telemetry
        with self._mu:
            addrs, found, acc, vals, routed, val_ok = self._launch(
                "get", self.store, keys, valid)
            found = found & valid
            hops = valid.astype(I32)
            # second hop (paper: the client reads the value from the data
            # server given the address): values written on another shard
            # during a degraded write — or homed on a crashed data server
            # — are fetched by address from the first effective-alive
            # holder (mirror failover); a fetch-overflow lane re-enters
            # the client's retry loop as un-routed
            need = found & ~val_ok
            if bool(tel.sync(need.any())):
                self.store, fvals, fok = self._launch(
                    "fetch", self.store, addrs, need)
                vals = jnp.where(need[:, None], fvals, vals)
                routed = routed & (~need | fok)
                hops = hops + need.astype(I32)
                # lanes the second hop answered this round (the rest
                # retry); read only here, so a healthy GET reads nothing
                tel.count("hops2_gets",
                          int(tel.sync((need & routed).sum())))
            self._lease_tick(bump=True)
            return addrs, found, acc, vals, routed & valid, hops

    def delete(self, keys, valid):
        with self._mu:
            n = int(self.telemetry.sync(valid.sum()))
            self._ensure_log_room(n)
            self._pending_bound += n
            # healthy cluster -> probe-free variant (all requests land on
            # true primaries); any masked-dead server -> the degraded
            # variant that answers found at temporary primaries via the
            # replica probe
            self.store, ok, found, nrep = self._launch(
                "delete_degraded" if self._degraded() else "delete",
                self.store, keys, valid)
            self._lease_tick()
            return ok, found & valid, nrep

    def scan(self, lo, hi, limit: int):
        with self._mu:
            kd = key_dtype()
            loa = jnp.full((self.G,), lo, kd)
            hia = jnp.full((self.G,), hi, kd)
            # the result width is a static shape: compile (and cache, via
            # make_ops' lru_cache) one scan op per distinct limit so a
            # caller asking for more than the construction-time default
            # is honored
            ops = None if limit == self.scan_limit else kv.make_ops(
                self.mesh, self.cfg, capacity_q=self.capacity_q,
                scan_limit=limit)
            k, a, covered, self.store = self._launch(
                "scan", self.store, loa, hia, ops=ops)
            n = (k != key_inf(k.dtype)).sum().astype(I32)
            self._pending_bound = 0          # scan drained the logs
            self._lease_tick()
            return k, a, n, covered

    def apply_async(self):
        with self._mu:
            self.telemetry.count("apply_rounds")
            self.store = self._launch("apply", self.store)
            self._pending_bound = max(
                0, self._pending_bound - self.cfg.async_apply_batch)
            self._lease_tick()

    def gc_round(self):
        """One routed flush of the pending free queues (slots freed on a
        remote shard travel home and become allocatable)."""
        with self._mu:
            self.telemetry.count("gc_rounds")
            self.store = self._launch("gc", self.store)
            self._lease_tick()

    def pending_frees(self) -> int:
        with self._mu:
            return int(self.telemetry.sync(
                lg.pending_count(self.store.data.freeq).sum()))

    def drain(self):
        tel = self.telemetry
        with self._mu, tel.span("drain"):
            tel.count("drains")
            while self.pending_ops() > 0:
                self.apply_async()
            self._pending_bound = 0
            # flush the free queues until empty or stuck (frees addressed
            # to a masked-dead data shard stay queued; the recovery sweep
            # reclaims them if the queue itself is lost)
            prev = -1
            while True:
                cur = self.pending_frees()
                if cur == 0 or cur == prev:
                    break
                prev = cur
                self.gc_round()

    def pending_ops(self) -> int:
        with self._mu:
            return int(self.telemetry.sync(
                jnp.max(self.store.blog.tail - self.store.blog.applied)))

    def migrate_values(self) -> int:
        """Background value migration (host-side): move degraded-write
        strays home and patch index addresses; the pass's log barrier
        runs as incremental shard_map'd apply rounds.  Returns values
        moved."""
        with self._mu:
            self.store, moved = kv.migrate_values(
                self.store, self.cfg,
                apply_fn=functools.partial(self._launch, "apply"))
            return moved

    def _wipe_capability(self, what: str) -> bool:
        # wiping needs a surviving copy to exist; a 1-device mesh folds
        # every replica onto the failing device, so the failure degrades
        # to mask-only there — surfaced explicitly (FailResult.wiped +
        # warning) instead of silently weaker semantics
        if self.G > 1:
            return True
        warnings.warn(
            f"single-device mesh: {what} degrades to mask-only (every "
            "replica lives on the failing device, so no surviving copy "
            "could exist; state is masked, NOT wiped)", RuntimeWarning,
            stacklevel=3)
        return False

    def fail_server(self, server: int) -> FailResult:
        with self._mu:
            wiped = self._wipe_capability("fail_server")
            self.store = kv.fail_server(self.store, server, wipe=wiped)
            self._dead.add(server)
            # a known-dead server no longer "stalls": stale misses must
            # not latch lease_stalled() and tax healthy retries
            self._hb_misses[server] = 0
            self._hb_t[server] = time.monotonic()
            return FailResult(server, wiped)

    def sever_server(self, server: int) -> FailResult:
        """Crash ``server`` WITHOUT updating the routing view: its
        heartbeats stop and its state is destroyed, but ``alive`` still
        says up — only the lease detector (or an operator-initiated
        recovery) brings the client's view back in line.  This is the
        fault injector's kill switch for detector schedules; the oracle
        ``fail_server`` stays for tests that want instant masking."""
        with self._mu:
            wiped = self._wipe_capability("sever_server")
            self.store = kv.sever_server(self.store, server, wipe=wiped)
            self._severed.add(server)
            return FailResult(server, wiped)

    def recover_server(self, server: int, online: bool = True,
                       re_replicate: bool = True) -> RecoverResult:
        """Rebuild ``server`` and re-admit it.  ``online`` (default)
        snapshot-clones and lets the pending-log delta stream into the
        rebuilt replicas through the ordinary apply rounds while
        foreground traffic continues; ``re_replicate`` then verifies
        every live holder against the group authorities and rebuilds
        divergent copies (the multi-failure window closer)."""
        with self._mu:
            if server in self._severed and server not in self._dead:
                # operator-initiated recovery implies the failure is
                # known: align routing even if the lease had not expired
                self._demote(server)
            # a RecoveryError propagates with the host-side sever/dead
            # tracking untouched (kv.recover_server is functional, so the
            # store is unchanged too): the server stays routed-dead and
            # severed until a recovery actually succeeds
            self.store = kv.recover_server(self.store, server, self.cfg,
                                           online=online)
            n_reb = 0
            if re_replicate:
                self.store, n_reb = kv.re_replicate(self.store, self.cfg)
            self._severed.discard(server)
            self._dead.discard(server)
            self._hb_misses[server] = 0
            self._hb_t[server] = time.monotonic()
            self.telemetry.count("index_recoveries")
            return RecoverResult(server, online, n_reb, self.pending_ops())

    def fail_data_server(self, server: int) -> FailResult:
        with self._mu:
            wiped = self._wipe_capability("fail_data_server")
            self.store = kv.fail_data_server(self.store, server,
                                             wipe=wiped)
            self._data_dead.add(server)
            self._data_hb_misses[server] = 0   # see fail_server
            self._data_hb_t[server] = time.monotonic()
            return FailResult(server, wiped)

    def sever_data_server(self, server: int) -> FailResult:
        """Crash ``server``'s DATA server WITHOUT updating the routing
        view — the value plane's counterpart of ``sever_server``: its
        data heartbeats stop and its shard state is destroyed, but
        ``data.alive`` still says up.  Reads of its shard fail over to
        the mirrors per-op at once; writes nack and retry until the
        lease detector demotes it (mirror-served GETs + displaced PUTs,
        zero oracle kills)."""
        with self._mu:
            wiped = self._wipe_capability("sever_data_server")
            self.store = kv.sever_data_server(self.store, server,
                                              wipe=wiped)
            self._data_severed.add(server)
            return FailResult(server, wiped)

    def recover_data_server(self, server: int):
        """Rebuild ``server``'s data shard from its mirrors and re-admit
        it — works the same from the oracle-masked and the lease-DETECTED
        state (the detector found the failure; re-provisioning the
        machine is the operator's move)."""
        with self._mu:
            if server in self._data_severed and \
                    server not in self._data_dead:
                # operator recovery implies the failure is known: align
                # the routing view even if the lease had not expired yet
                self._demote_data(server)
            self.store = kv.recover_data_server(
                self.store, server, self.cfg,
                apply_fn=functools.partial(self._launch, "apply"))
            self._data_severed.discard(server)
            self._data_dead.discard(server)
            self._data_hb_misses[server] = 0
            self._data_hb_t[server] = time.monotonic()
            self.telemetry.count("data_recoveries")

    def telemetry_gauges(self) -> dict:
        """Snapshot-time gauges for ``client.metrics()``: the store's
        device-resident counters (live servers per plane, pending-log
        depth, free-queue occupancy, ``fq_spill``) fetched in one go —
        this is the ONLY place telemetry touches the device, so enabling
        it adds no sync to any op body."""
        with self._mu:
            return kv.device_counters(self.store)


# ---------------------------------------------------------------------------
# The client
# ---------------------------------------------------------------------------
class HiStoreClient:
    """Typed GET/PUT/DELETE/SCAN over a pluggable backend (see module
    docstring).  Thread-compatible with eager callers: all state lives in
    the backend; the client only holds policy."""

    def __init__(self, backend: Backend, *, batch_quantum: int = 64,
                 max_batch: int = 16384, max_retries: int = 8,
                 apply_every_n_ops: Optional[int] = None,
                 migrate_on_recover: bool = True):
        self.backend = backend
        m = max(getattr(backend, "batch_multiple", 1), 1)
        self._multiple = m
        # padded sizes: power-of-two, rounded up to a multiple of the
        # backend's device count (works for non-power-of-two meshes too)
        q0 = next_pow2(max(batch_quantum, 1))
        self.batch_quantum = -(-q0 // m) * m
        self.max_batch = (-(-max(max_batch, self.batch_quantum)
                            // self.batch_quantum) * self.batch_quantum)
        # mutation chunks must fit the backup-log ring after a drain, or
        # the backends' room guarantee (and the acks) would be a lie
        cap = getattr(backend, "max_mutation_batch", None)
        if cap:
            cap = max(self.batch_quantum,
                      cap // self.batch_quantum * self.batch_quantum)
            self.max_batch = min(self.max_batch, cap)
        self.max_retries = max_retries
        self.apply_every_n_ops = apply_every_n_ops
        self.migrate_on_recover = migrate_on_recover
        self._mutations_since_apply = 0
        self.stats = {"puts": 0, "gets": 0, "deletes": 0, "scans": 0,
                      "retries": 0, "applies": 0, "migrated": 0}
        # the backend OWNS the telemetry plane (constructed from its
        # cfg.telemetry knob) so detector/ticker events and client op
        # metrics land in one snapshot; lease-less custom backends
        # without one get an inert "off" instance
        self.telemetry = (getattr(backend, "telemetry", None)
                          or tm.Telemetry("off"))

    # -- public ops --------------------------------------------------------
    def put(self, keys, values=None) -> PutResult:
        tel = self.telemetry
        with tel.call("put", np.size(keys)):
            with tel.span("prepare"):
                keys = self._as_keys(keys)
                q = keys.shape[0]
                if q == 0:
                    return PutResult(jnp.zeros((0,), bool),
                                     jnp.zeros((0,), I32), 0,
                                     jnp.zeros((0,), I32))
                vals = self._as_values(values, q)
            t0 = time.perf_counter()
            oks, addrs, reps, retries = [], [], [], 0
            for s in range(0, q, self.max_batch):
                o, a, rep, r = self._put_chunk(keys[s:s + self.max_batch],
                                               vals[s:s + self.max_batch])
                oks.append(o)
                addrs.append(a)
                reps.append(rep)
                retries = max(retries, r)
            self.stats["puts"] += q
            if tel.enabled:
                tel.count("put_ops", q)
                tel.count("put.calls")
                tel.observe("put", time.perf_counter() - t0)
            self._note_mutations(q)
            return PutResult(jnp.concatenate(oks), jnp.concatenate(addrs),
                             retries, jnp.concatenate(reps))

    def get(self, keys) -> GetResult:
        tel = self.telemetry
        with tel.call("get", np.size(keys)):
            with tel.span("prepare"):
                keys = self._as_keys(keys)
            q = keys.shape[0]
            if q == 0:
                W = getattr(self.backend, "value_words", 1)
                return GetResult(
                    jnp.zeros((0,), I32), jnp.zeros((0,), bool),
                    jnp.zeros((0,), I32), jnp.zeros((0, W), I32),
                    jnp.zeros((0,), bool), jnp.zeros((0,), I32))
            t0 = time.perf_counter()
            outs = [self._get_chunk(keys[s:s + self.max_batch])
                    for s in range(0, q, self.max_batch)]
            self.stats["gets"] += q
            res = GetResult(*[jnp.concatenate(p) for p in zip(*outs)])
            if tel.enabled:
                tel.count("get_ops", q)
                tel.count("get.calls")
                tel.observe("get", time.perf_counter() - t0)
            return res

    def delete(self, keys) -> DeleteResult:
        tel = self.telemetry
        with tel.call("delete", np.size(keys)):
            with tel.span("prepare"):
                keys = self._as_keys(keys)
            q = keys.shape[0]
            if q == 0:
                return DeleteResult(jnp.zeros((0,), bool),
                                    jnp.zeros((0,), bool), 0,
                                    jnp.zeros((0,), I32))
            t0 = time.perf_counter()
            oks, founds, reps, retries = [], [], [], 0
            for s in range(0, q, self.max_batch):
                o, f, rep, r = self._delete_chunk(
                    keys[s:s + self.max_batch])
                oks.append(o)
                founds.append(f)
                reps.append(rep)
                retries = max(retries, r)
            self.stats["deletes"] += q
            if tel.enabled:
                tel.count("delete_ops", q)
                tel.count("delete.calls")
                tel.observe("delete", time.perf_counter() - t0)
            self._note_mutations(q)
            return DeleteResult(jnp.concatenate(oks),
                                jnp.concatenate(founds), retries,
                                jnp.concatenate(reps))

    def scan(self, lo, hi, limit: Optional[int] = None) -> ScanResult:
        kd = key_dtype()
        if limit is None:
            limit = getattr(self.backend, "scan_limit", 128)
        if limit <= 0:
            kd_inf = jnp.zeros((0,), kd)
            return ScanResult(kd_inf, jnp.zeros((0,), I32),
                              jnp.zeros((), I32), True, ())
        tel = self.telemetry
        with tel.call("scan", limit):
            return self._scan(lo, hi, limit, kd)

    def _scan(self, lo, hi, limit: int, kd) -> ScanResult:
        tel = self.telemetry
        t0 = time.perf_counter()
        with tel.span("prepare"):
            lo, hi = jnp.asarray(lo, kd), jnp.asarray(hi, kd)
        k, a, n, covered = self.backend.scan(lo, hi, limit)
        cov = tel.sync(covered)
        self.stats["scans"] += 1
        # scan-completeness retry: a group with zero live, unsevered
        # holders answered nothing.  Each rescan is an observation round
        # — paced by _retry_pause under wall-clock leases — so the
        # bounded retries let the lease detector demote the crashed
        # holders (the routing view aligns — retry-AFTER-detection);
        # coverage itself only returns once the operator recovers them,
        # so afterwards we report honestly instead of looping
        budget = min(self.max_retries,
                     max(getattr(self.backend, "lease_misses", 0), 0) + 1)
        tries = 0
        while not cov.all() and tries < budget:
            # rescans only help while the detector is still watching a
            # stalled heartbeat; once detection settles (holders already
            # demoted — or oracle-failed), coverage can only return via
            # recovery, so report honestly after ONE round, not five
            if not self.backend.lease_stalled():
                break
            tries += 1
            with tel.span("retry"):
                self.stats["retries"] += 1
                tel.count("retries")
                self._retry_pause(budget)
            k, a, n, covered = self.backend.scan(lo, hi, limit)
            cov = tel.sync(covered)
        missing = tuple(int(g) for g in np.nonzero(~cov)[0].tolist())
        if tel.enabled:
            tel.count("scan_ops")
            tel.count("scan.calls")
            tel.observe("scan", time.perf_counter() - t0)
            if missing:
                tel.count("incomplete_scans")
        lim = min(limit, k.shape[0])
        return ScanResult(k[:lim], a[:lim],
                          jnp.minimum(n, lim).astype(I32),
                          not missing, missing)

    def apply(self) -> None:
        """One asynchronous log->sorted merge round on every backup."""
        self.stats["applies"] += 1
        self.backend.apply_async()

    def drain(self) -> None:
        """Apply ALL pending log entries (SCAN serializability barrier)."""
        self.backend.drain()

    def migrate(self) -> int:
        """Run the background value migration now (degraded-write strays
        move home; GETs drop back to hops == 1).  Returns values moved."""
        moved = self.backend.migrate_values()
        self.stats["migrated"] += moved
        return moved

    def fail_server(self, server: int):
        return self.backend.fail_server(server)

    def sever_server(self, server: int):
        """Crash a server the lease detector must DISCOVER (heartbeats
        severed, routing view untouched) — the fault injector's switch
        for oracle-free failure schedules; LocalBackend raises (its
        liveness is host-side)."""
        return self.backend.sever_server(server)

    def recover_server(self, server: int, **kw):
        """Rebuild + re-admit a server.  Keyword knobs are forwarded to
        the backend (``online=False`` for stop-the-world recovery,
        ``re_replicate=False`` to skip the post-recovery verify on the
        distributed backend)."""
        r = self.backend.recover_server(server, **kw)
        if self.migrate_on_recover:
            self.migrate()
        return r

    def fail_data_server(self, server: int):
        return self.backend.fail_data_server(server)

    def sever_data_server(self, server: int):
        """Crash a DATA server the lease detector must DISCOVER (data
        heartbeats severed, routing view untouched) — the fault
        injector's value-plane switch for oracle-free failure schedules
        schedules; LocalBackend raises (single unreplicated shard)."""
        return self.backend.sever_data_server(server)

    def recover_data_server(self, server: int) -> None:
        self.backend.recover_data_server(server)
        if self.migrate_on_recover:
            self.migrate()

    def start_ticker(self) -> bool:
        """Start the backend's background lease ticker (idle-client
        wall-clock failure detection).  Returns True when one is
        running; False for backends without leases (LocalBackend tracks
        liveness host-side)."""
        fn = getattr(self.backend, "start_ticker", None)
        return bool(fn()) if fn else False

    def stop_ticker(self) -> None:
        fn = getattr(self.backend, "stop_ticker", None)
        if fn:
            fn()

    # -- telemetry ---------------------------------------------------------
    def metrics(self) -> tm.MetricsSnapshot:
        """Typed point-in-time snapshot of the telemetry plane: op
        counters, per-op latency percentiles, and the backend's
        device-side gauges (live servers, pending-log depth, free-queue
        occupancy, ``fq_spill``).  The gauge fetch is the only device
        access telemetry ever makes — and only here, never per op."""
        gauges = {}
        fn = getattr(self.backend, "telemetry_gauges", None)
        if fn is not None and self.telemetry.enabled:
            gauges = fn()
        return self.telemetry.snapshot(gauges=gauges)

    def metrics_text(self) -> str:
        """The snapshot in Prometheus text exposition format."""
        return tm.render_text(self.metrics())

    # -- batching / retry internals ---------------------------------------
    def _as_keys(self, keys):
        k = jnp.asarray(keys, key_dtype())
        if k.ndim == 0:
            k = k[None]
        return k

    def _as_values(self, values, q):
        W = getattr(self.backend, "value_words", 1)
        if values is None:
            return jnp.zeros((q, W), I32)
        v = jnp.asarray(values, I32)
        if v.ndim == 0:
            v = v[None]
        if v.ndim == 1:
            v = jnp.tile(v[:, None], (1, W))
        return v

    def _padded_len(self, q: int) -> int:
        p = max(self.batch_quantum, next_pow2(q))
        p = -(-p // self._multiple) * self._multiple
        return min(self.max_batch, p)

    def _pad(self, keys):
        q = keys.shape[0]
        p = self._padded_len(q)
        kp = jnp.zeros((p,), keys.dtype).at[:q].set(keys)
        valid = jnp.zeros((p,), bool).at[:q].set(True)
        return kp, valid

    def _make_room(self):
        """Push-back response between retry rounds: one log->sorted merge
        (frees backup-log ring room) and one GC flush (frees value slots
        still queued on a remote shard)."""
        self.backend.apply_async()
        gc = getattr(self.backend, "gc_round", None)
        if gc:
            gc()

    def _retry_pause(self, budget: Optional[int] = None):
        """Wall-clock leases expire by ELAPSED TIME, not retry count: on
        fast hardware an unpaced retry loop would exhaust max_retries in
        milliseconds, long before a crashed server's lease can expire —
        returning failures the rounds clock used to recover.  Pace the
        loop (the RPC client's backoff) so its remaining retry budget
        spans at least one lease timeout, keeping detection-within-the-
        loop true in BOTH clock modes.  Paces ONLY while the detector is
        actually watching a stalled heartbeat — a healthy push-back
        retry (capacity overflow) stays millisecond-fast.  No-op in
        rounds mode, with detection off, and for lease-less backends."""
        be = self.backend
        if getattr(be, "lease_clock", "") != "wall":
            return
        if getattr(be, "lease_misses", 0) <= 0:
            return
        if not be.lease_stalled():
            return
        # the first stalled round goes unpaced (the stall is only
        # observable after it), so spread the timeout over budget-1
        n = max(budget if budget is not None else self.max_retries, 2)
        time.sleep(be.lease_timeout_s / (n - 1))

    def _put_chunk(self, keys, vals):
        tel = self.telemetry
        q = keys.shape[0]
        with tel.span("prepare"):
            kp, pending = self._pad(keys)
            vp = jnp.zeros((kp.shape[0], vals.shape[1]), vals.dtype
                           ).at[:q].set(vals)
            ok_all = jnp.zeros_like(pending)
            addr_all = jnp.full(kp.shape, -1, I32)
            rep_all = jnp.zeros(kp.shape, I32)
        retries = 0
        while True:
            tel.count("put.lanes", kp.shape[0])
            ok, addrs, nrep = self.backend.put(kp, vp, pending)
            with tel.span("merge"):
                newly = pending & ok
                ok_all = ok_all | newly
                addr_all = jnp.where(newly, addrs, addr_all)
                rep_all = jnp.where(newly, nrep, rep_all)
                pending = pending & ~ok
            if not bool(tel.sync(pending.any())) \
                    or retries >= self.max_retries:
                break
            retries += 1
            with tel.span("retry"):
                self.stats["retries"] += 1
                tel.count("retries")
                tel.count("pushbacks")   # capacity push-back on a mutation
                self._retry_pause()
                self._make_room()
        return ok_all[:q], addr_all[:q], rep_all[:q], retries

    def _delete_chunk(self, keys):
        tel = self.telemetry
        q = keys.shape[0]
        with tel.span("prepare"):
            kp, pending = self._pad(keys)
            acked = jnp.zeros_like(pending)
            found_all = jnp.zeros_like(pending)
            rep_all = jnp.zeros(kp.shape, I32)
        retries = 0
        while True:
            tel.count("delete.lanes", kp.shape[0])
            ack, found, nrep = self.backend.delete(kp, pending)
            with tel.span("merge"):
                newly = pending & ack
                acked = acked | newly
                found_all = found_all | (newly & found)
                rep_all = jnp.where(newly, nrep, rep_all)
                pending = pending & ~ack
            if not bool(tel.sync(pending.any())) \
                    or retries >= self.max_retries:
                break
            retries += 1
            with tel.span("retry"):
                self.stats["retries"] += 1
                tel.count("retries")
                tel.count("pushbacks")
                self._retry_pause()
                self._make_room()
        return acked[:q], found_all[:q], rep_all[:q], retries

    def _get_chunk(self, keys):
        tel = self.telemetry
        q = keys.shape[0]
        with tel.span("prepare"):
            kp, pending = self._pad(keys)
            addr_all = jnp.full(kp.shape, -1, I32)
            found_all = jnp.zeros_like(pending)
            acc_all = jnp.zeros(kp.shape, I32)
            hops_all = jnp.zeros(kp.shape, I32)
        vals_all = None
        retries = 0
        while True:
            tel.count("get.lanes", kp.shape[0])
            addrs, found, acc, vals, routed, hops = self.backend.get(
                kp, pending)
            with tel.span("merge"):
                if vals_all is None:
                    vals_all = jnp.zeros_like(vals)
                newly = pending & routed
                addr_all = jnp.where(newly, addrs, addr_all)
                found_all = found_all | (newly & found)
                acc_all = jnp.where(newly, acc, acc_all)
                hops_all = jnp.where(newly, hops, hops_all)
                vals_all = jnp.where(newly[:, None], vals, vals_all)
                pending = pending & ~routed
            if not bool(tel.sync(pending.any())) \
                    or retries >= self.max_retries:
                break
            retries += 1
            with tel.span("retry"):
                self.stats["retries"] += 1
                tel.count("retries")
                self._retry_pause()
        # lanes still pending exhausted the retry budget: reported as
        # un-routed so push-back is distinguishable from a genuine miss
        return (addr_all[:q], found_all[:q], acc_all[:q], vals_all[:q],
                (~pending)[:q], hops_all[:q])

    def _note_mutations(self, n: int):
        if not self.apply_every_n_ops:
            return
        self._mutations_since_apply += n
        if self._mutations_since_apply >= self.apply_every_n_ops:
            self._mutations_since_apply = 0
            self.apply()
