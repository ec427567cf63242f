"""HiStore: the distributed key-value store over index groups.

Topology (one group per device; cfg.groups_per_device generalises):
  device g is the PRIMARY server of group g (hash table + primary log + the
  group's data-server shard) and the BACKUP server for groups g-1 (replica
  0) and g-2 (replica 1): backup arrays use the SHIFTED layout — slice
  [r, p] stores replica r of group (p - r - 1) mod G, so placing slice p on
  device p puts every replica on a different failure domain, and log
  replication is a ppermute by r+1 hops.  The value plane (slot allocator,
  mirror replication, free queues) is the ``data`` field — see
  data_plane.py; data servers are a failure domain separate from the index
  servers (paper §2).

Ops (all shard_map'd over the 1-D "kv" mesh axis; see verbs.py for the
RDMA-verb mapping):
  put    — route to owner; owner allocates a free slot on its data shard
           (overwrites free the old slot first — the data-server GC),
           stores + mirrors the value, appends its log, pushes the entries
           to the LIVE backup logs (ppermute; dead holders are skipped),
           updates the hash table, acks with the replica count actually
           written.  A full shard rejects the lane (client retries after
           a GC round).
  put_degraded — as put, plus the replica probe that finds the old slot at
           a temporary primary, and one-hop value displacement when the
           owner's own data shard is masked dead.
  get    — one-sided: route, owner-side gather-only probe, value gather,
           reverse route.  Primary dead -> the query is routed to a backup
           holder, which consults its pending log + sorted replica; values
           stored on another shard are flagged for a second-hop fetch.
  fetch  — second-hop value read: route by address to the first LIVE data
           holder of the owning shard (primary copy, then its mirrors).
  delete — route to owner; owner appends a tombstone to its log, pushes it
           to the live backup logs (ppermute), tombstones the hash slot,
           frees the value slot (queued for the gc op when remote), acks
           (degraded found answered from the replica + pending log).
           The tombstone compacts out of the sorted replicas on apply.
  scan   — backup-side: every device fully drains and range-queries the
           replicas it holds, results are all_gathered and merged.
  apply_async — one batched log->sorted merge round on every backup.
  gc     — one routed flush round of the pending free queues (frees whose
           slot lives on another shard travel home and clear the bit).
  tick   — heartbeat-only round: every device bumps its per-server
           heartbeat counters — index AND data plane (as every routed op
           does in-body); the client ages the counters host-side
           (elapsed wall-clock time by default, observation rounds in
           the deterministic test mode) and demotes a server to degraded
           routing when its lease expires — failure DETECTION without an
           oracle caller (DESIGN.md §Failure detection).  An idle
           client's background ticker thread issues tick rounds so
           detection needs no foreground traffic.
  fail_server / sever_server / recover_server / re_replicate /
  parity_report — host-side failure control plane: fail WIPES the
           device's index state with the client told at once; sever
           wipes it but only STOPS ITS HEARTBEATS (the client must
           detect); recover snapshot-clones from survivors and lets the
           pending log delta stream into the rebuilt replicas through
           the ordinary apply rounds while foreground traffic continues
           (online catch-up; falls back to the hash + the keys stored
           with the data items on multi-failure, raising the typed
           RecoveryError only when truly no copy exists); re_replicate
           verifies every live holder against the group authorities and
           rebuilds divergent copies (DESIGN.md §Fault tolerance).
  fail_data_server / sever_data_server / recover_data_server /
  migrate_values — the value plane's control plane (data_plane.py):
           oracle kill, lease-detected kill (heartbeats cut, routing
           view untouched), mirror-rebuild recovery, and the background
           migration that moves degraded-write values home and patches
           index addresses (second-hop fetch elision).

All mutating ops take a ``valid`` lane mask so the client can pad request
batches to fixed shapes (DESIGN.md §Client); invalid lanes are routed
nowhere, consume no exchange capacity, and mutate nothing.  External
callers should not call these ops directly — go through
repro.core.client.HiStoreClient, which adds overflow retry, batch padding
and the async-apply policy.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import data_plane as dp
from repro.core import hash_index as hix
from repro.core import log as lg
from repro.core import sorted_index as six
from repro.core.hashing import fmix32, key_inf
from repro.kernels import ops as kops
from repro.core.verbs import (exchange, replicate_shift, route_build,
                              route_return)

I32 = jnp.int32
AXIS = "kv"

RecoveryError = dp.RecoveryError   # typed multi-failure recovery error


class KVStore(NamedTuple):
    hash: hix.HashIndex       # leaves [G, ...]
    plog: lg.UpdateLog        # leaves [G, ...]
    bsorted: six.SortedIndex  # leaves [R, G, ...] (shifted layout)
    blog: lg.UpdateLog        # leaves [R, G, ...]
    data: dp.DataPlane        # value plane (shard + allocator + mirrors)
    alive: jnp.ndarray        # [G] bool — the CLIENT's routing view of
    #                           index-server liveness (flipped by the
    #                           oracle kill switch OR the lease detector)
    sever: jnp.ndarray        # [G] bool — heartbeats severed: the server
    #                           has crashed but the client has not noticed
    #                           yet; lanes delivered there are nacked (the
    #                           RPC-timeout analogue) and its heartbeat
    #                           counter stops advancing
    hb: jnp.ndarray           # [G] int32 heartbeat counters — each device
    #                           bumps its own inside every routed op; the
    #                           client ages them host-side (leases)


def create(mesh, capacity_per_group: int, cfg, key_dt=None) -> KVStore:
    G = mesh.devices.size
    R = cfg.n_backups
    rep = lambda t, n: jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy(), t)
    one_hash = hix.create(capacity_per_group, cfg)
    one_plog = lg.create(cfg.log_capacity, key_dt)
    one_sorted = six.create(capacity_per_group, key_dt)
    one_blog = lg.create(cfg.log_capacity, key_dt)
    store = KVStore(
        hash=rep(one_hash, G),
        plog=rep(one_plog, G),
        bsorted=rep(rep(one_sorted, G), R),
        blog=rep(rep(one_blog, G), R),
        data=dp.create(G, capacity_per_group, cfg, key_dt),
        alive=jnp.ones((G,), bool),
        sever=jnp.zeros((G,), bool),
        hb=jnp.zeros((G,), I32),
    )
    return jax.device_put(store, store_sharding(mesh))


def store_sharding(mesh):
    from jax.sharding import NamedSharding

    # group axis position differs: hash/plog/data shard dim0; bsorted/blog
    # shard dim1; alive/sever replicated, hb sharded (each device owns its
    # own heartbeat counter).
    return KVStore(
        hash=hix.HashIndex(*[NamedSharding(mesh, P(AXIS))] * 4),
        plog=lg.UpdateLog(*[NamedSharding(mesh, P(AXIS))] * 5),
        bsorted=six.SortedIndex(*[NamedSharding(mesh, P(None, AXIS))] * 3),
        blog=lg.UpdateLog(*[NamedSharding(mesh, P(None, AXIS))] * 5),
        data=dp.sharding(mesh, AXIS),
        alive=NamedSharding(mesh, P()),
        sever=NamedSharding(mesh, P()),
        hb=NamedSharding(mesh, P(AXIS)),
    )


def _specs():
    return KVStore(
        hash=hix.HashIndex(*[P(AXIS)] * 4),
        plog=lg.UpdateLog(*[P(AXIS)] * 5),
        bsorted=six.SortedIndex(*[P(None, AXIS)] * 3),
        blog=lg.UpdateLog(*[P(None, AXIS)] * 5),
        data=dp.specs(AXIS),
        alive=P(),
        sever=P(),
        hb=P(AXIS),
    )


def owner_group(keys, G: int):
    """Group routing hash — decorrelated from the bucket hash."""
    from repro.core.hashing import key_mix
    h1, h2 = key_mix(keys)
    return (fmix32(h2 ^ jnp.uint32(0xA5A5A5A5)) % jnp.uint32(G)).astype(I32)


def _first_alive_holder(g, alive):
    """Device to contact for group g: primary g, else backup holders."""
    G = alive.shape[0]
    cand = jnp.stack([g % G, (g + 1) % G, (g + 2) % G])
    ok = alive[cand]
    pick = jnp.argmax(ok)          # first alive in priority order
    return cand[pick]


def _first_alive_data_holder(s, dalive, Rv: int):
    """Data server to contact for shard s: the shard itself, else the
    devices hosting its mirror copies (priority order).  Returns
    (holder, any_alive): when every holder is dead (loss beyond the
    configured value replication) the caller must leave the lane
    un-routed — a push-back, never a fabricated value."""
    G = dalive.shape[0]
    cand = jnp.stack([s % G] + [(s + r + 1) % G for r in range(Rv)])
    ok = dalive[cand]
    return cand[jnp.argmax(ok)], ok.any()


# ---------------------------------------------------------------------------
# shard_map bodies (one device's view; leading group axis is local size 1)
# ---------------------------------------------------------------------------
def _sq(tree):
    return jax.tree.map(lambda a: a[0], tree)


def _ex(tree, val):
    return jax.tree.map(lambda a, v: a.at[0].set(v), tree, val)


def _route_to_owner(store, keys, valid, G, capacity, extra=None):
    """Shared routing prologue of the mutating ops: invalid (padding) lanes
    get an out-of-range destination, so they occupy no exchange capacity
    and arrive nowhere."""
    dest_g = owner_group(keys, G)
    dest = jax.vmap(lambda g: _first_alive_holder(g, store.alive))(dest_g)
    dest = jnp.where(valid, dest, G)
    payloads = {"k": (keys, 0), "g": (jnp.where(valid, dest_g, -1), -1)}
    if extra:
        payloads.update(extra)
    return route_build(dest, payloads, G, capacity)


def _queue_remote_frees(data, rk, old_addr, mask):
    """Frees targeting another device's shard ride the per-device free
    queue until the gc op routes them home.  The queue holds
    log_capacity entries; entries addressed to a DEAD data shard wait
    out its outage here, so a long outage can FILL it.  The op bodies
    pre-gate on queue room (lanes that would need to queue a free are
    nacked for a client retry when no room exists — push-back, never a
    silent drop), so the append below cannot overflow; ``ok`` is still
    returned so any residual rejection lands in the ``fq_spill`` audit
    counter instead of vanishing."""
    freeq, ok = lg.append(_sq(data.freeq), jnp.zeros_like(rk), old_addr,
                          jnp.where(mask, 1, 0).astype(jnp.int8), mask)
    return _ex(data.freeq, freeq), ok


def _fq_pregate(data, may_queue):
    """Queue-full push-back: lanes that may need to queue a remote free
    are admitted only while the per-device free queue has room for them
    (cumulative rank within the batch).  Returns the per-lane admit
    mask."""
    fq = _sq(data.freeq)
    room = fq.keys.shape[0] - (fq.tail - fq.applied)
    qrank = jnp.cumsum(may_queue.astype(I32)) - 1
    return ~may_queue | (qrank < room)


def _bump_hb(store):
    """Heartbeat: every device advances its own INDEX-server counter and
    its own DATA-server counter inside each routed op — unless the
    respective server's heartbeats are severed (crashed).  Bumping both
    planes everywhere keeps either lease from stalling spuriously under
    a one-sided workload (e.g. a drain's apply rounds must not expire
    healthy data-server leases).  The client ages both counter arrays
    host-side (the unified liveness plane)."""
    me = jax.lax.axis_index(AXIS)
    d = store.data
    return store._replace(
        hb=store.hb + jnp.where(store.sever[me], 0, 1).astype(I32),
        data=d._replace(
            hb=d.hb + jnp.where(d.sever[me], 0, 1).astype(I32)))


def _put_body(cfg, G, capacity, store: KVStore, keys, vals, valid,
              degraded: bool):
    """Routed PUT.  ``degraded`` is the compile-time liveness hint (same
    contract as delete's): the healthy variant assumes every index server
    and data server is up, so it skips the replica probe (old-slot lookup
    at a temporary primary) and the one-hop value displacement; the
    backend picks the variant from its host-side liveness view."""
    me = jax.lax.axis_index(AXIS)
    bufs, slot, ok_route = _route_to_owner(
        store, keys, valid, G, capacity, {"v": (vals, 0)})
    recv = exchange(bufs, AXIS)
    rk, rv, rg = recv["k"], recv["v"], recv["g"]
    # a severed (crashed-but-undetected) server answers nothing: lanes
    # delivered here are dropped un-acked — the RPC-timeout the client
    # retries until its lease detector demotes this device
    valid = (rg >= 0) & ~store.sever[me]
    am_primary = rg == me
    data = store.data
    dcap = data.vals.shape[1]
    # effective data-server liveness: a severed (crashed-but-undetected)
    # data server accepts no writes — its lanes fail allocation and nack
    # for a client retry (the RPC timeout), until the lease detector
    # demotes it and the degraded variant displaces instead
    dalive_me = data.alive[me] & ~data.sever[me]
    winner = dp.winner_mask(rk, valid)
    # pre-batch address of the overwritten key: hash at the true primary,
    # replica + pending log at a temporary primary
    if degraded:
        old_a, old_f, _, old_ab, old_fb, _ = _index_probe(
            cfg, store, rk, me, G)
        old_a = jnp.where(am_primary, old_a, old_ab)
        old_f = jnp.where(am_primary, old_f, old_fb)
    else:
        old_a, old_f, _ = kops.probe(cfg, _sq(store.hash), rk)
    # --- owner side: place the value -------------------------------------
    # overwrite whose old slot is on MY live shard: update in place (no
    # allocator churn); new keys and remote-old strays: allocate fresh.
    # In-place writes land before the commit decision — like a real data
    # server's non-atomic value update, a lane nacked AFTER the write has
    # already exposed the new bytes at the old address; the client's
    # retry re-puts the same value, so the store converges, and the
    # window only exists when a backup ring rejects an append the
    # client's room guarantee should have prevented
    inplace = winner & old_f & (old_a // dcap == me) & dalive_me
    allocw = winner & ~inplace
    # free-queue push-back BEFORE anything commits: a lane that may need
    # to queue a remote free (moved overwrite whose old slot lives on
    # another shard; displaced write whose rollback would queue) is
    # admitted only while the queue has room — so queued frees can never
    # be dropped, only pushed back to the client's retry loop
    may_queue = allocw & old_f & (old_a >= 0) & (old_a // dcap != me)
    if degraded:
        may_queue = may_queue | (allocw & ~dalive_me)
    fq_ok = _fq_pregate(data, may_queue)
    allocw = allocw & fq_ok
    want = allocw & dalive_me
    used, slot_d, aok = dp.alloc(data.used[0], want)
    wslot = jnp.where(inplace, old_a % dcap, jnp.where(aok, slot_d, dcap))
    wmask = inplace | aok
    wtgt = jnp.where(wmask, wslot, dcap)
    dvals = data.vals[0].at[wtgt].set(rv, mode="drop")
    # the data item carries its KEY alongside the value (paper §2): an
    # index rebuild can fetch (key, addr) pairs back from the data
    # servers — the multi-failure recovery authority of last resort
    dkeys = data.keys[0].at[wtgt].set(rk, mode="drop")
    addr_lane = jnp.where(
        inplace, old_a,
        jnp.where(aok, me * dcap + slot_d, -1)).astype(I32)
    writes = [(wslot, rv, rk, wmask)]
    disp = jnp.zeros_like(valid)
    if degraded:
        # my own data shard is dead: displace the value one hop (the
        # neighbour's shard holds it until migrate_values brings it home)
        need_fwd = allocw & ~dalive_me
        f = replicate_shift({"v": rv, "k": rk, "need": need_fwd}, 1, AXIS)
        used, fslot, faok = dp.alloc(used, f["need"] & dalive_me)
        ftgt = jnp.where(faok, fslot, dcap)
        dvals = dvals.at[ftgt].set(f["v"], mode="drop")
        dkeys = dkeys.at[ftgt].set(f["k"], mode="drop")
        back = replicate_shift({"slot": fslot, "aok": faok}, G - 1,
                               AXIS)
        disp = need_fwd & back["aok"]
        addr_lane = jnp.where(disp, ((me + 1) % G) * dcap + back["slot"],
                              addr_lane).astype(I32)
        writes.append((fslot, f["v"], f["k"], faok))
    mirror, kmirror = data.mirror, data.kmirror
    for r in range(mirror.shape[0]):
        for ms, mv, mk, mm in writes:
            out = replicate_shift({"s": ms, "v": mv, "k": mk, "m": mm},
                                  r + 1, AXIS)
            tgt = jnp.where(out["m"] & dalive_me, out["s"], dcap)
            mirror = mirror.at[r, 0].set(
                mirror[r, 0].at[tgt].set(out["v"], mode="drop"))
            kmirror = kmirror.at[r, 0].set(
                kmirror[r, 0].at[tgt].set(out["k"], mode="drop"))
    # superseded duplicate lanes share their winner's address; a failed
    # allocation (-1) un-acks the whole duplicate group for a client retry
    addr = dp.spread_winner_addr(rk, valid, winner, addr_lane)
    landed = valid & (addr >= 0)
    # --- primary log -> backup logs -> hash, commit-gated ----------------
    ops = jnp.where(landed & am_primary, six.OP_PUT, 0).astype(jnp.int8)
    plog, ok_p = lg.append(_sq(store.plog), rk, addr, ops,
                           landed & am_primary)
    # the hash update is synchronous, so primary-log entries are applied
    # the moment the batch commits; advancing the prefix keeps the ring's
    # pending window from exhausting (entries stay on disk for recovery).
    plog = plog._replace(applied=plog.tail)
    blog, ok_rep, nrep, _ = _replicate_logs(
        store.blog, store.alive & ~store.sever, rk, addr, ops, landed,
        rg, me, G, six.OP_PUT)
    ok_commit = landed & ok_rep & ((am_primary & ok_p) | ~am_primary)
    new_hash, ok_h = hix.insert(_sq(store.hash), rk, addr, cfg,
                                ok_commit & am_primary)
    ok_req = ok_commit & (ok_h | ~am_primary)
    # --- data-server GC, commit-gated ------------------------------------
    # a committed move (new slot elsewhere) frees the old slot; an
    # un-acked lane rolls its fresh allocation back (the retry re-places)
    # ONLY when no log anywhere recorded its entry (nrep == 0): a slot a
    # replica log already references must never return to the allocator
    # — a dangling reference to re-allocatable memory is worse than a
    # leak the retry's last-writer-wins entry supersedes
    moved = winner & old_f & ~inplace & ok_req & (old_a >= 0)
    free_local = moved & (old_a // dcap == me) & dalive_me
    used = dp.free_slots(used, old_a % dcap, free_local)
    undo = ~ok_req & (nrep == 0)
    used = dp.free_slots(used, slot_d, aok & undo)
    undo_remote = disp & undo     # displaced slot lives on the neighbour
    qmask = (moved & ~free_local) | undo_remote
    qaddr = jnp.where(undo_remote, addr, old_a)
    freeq, fq_acc = _queue_remote_frees(data, rk, qaddr, qmask)
    fq_spill = data.fq_spill + (qmask & ~fq_acc).sum().astype(I32)
    ret = route_return({"ok": ok_req.astype(I32), "addr": addr,
                        "rep": nrep}, slot, AXIS)
    new_data = data._replace(
        vals=data.vals.at[0].set(dvals), used=data.used.at[0].set(used),
        keys=data.keys.at[0].set(dkeys), mirror=mirror, kmirror=kmirror,
        freeq=freeq, fq_spill=fq_spill)
    new_store = _bump_hb(store._replace(
        hash=_ex(store.hash, new_hash), plog=_ex(store.plog, plog),
        blog=blog, data=new_data))
    return (new_store, ret["ok"].astype(bool) & ok_route, ret["addr"],
            ret["rep"])


def _replicate_logs(blog, alive, rk, addr, ops, valid, rg, me, G, opcode):
    """Push an owner-side batch of log entries to the backup logs.
    Returns (blog, ok, nrep, ok_local):

      ok[i]   — False when a backup-log append for owner-lane i was
                rejected by a LIVE backup (ring full) — ppermuted back to
                the owner so the ack can carry the push-back instead of
                silently losing replicas.
      nrep[i] — how many replica logs actually recorded the entry.  Dead
                backups are skipped (the paper's observation that PUT
                speeds up under a backup failure), so nrep < n_backups is
                the honest report of reduced replication.
      ok_local[i] — True unless MY OWN backup-log append for a
                temporary-primary lane was rejected.  The degraded free /
                rollback decisions key on it: a retry's replica probe
                consults exactly this log, so "recorded locally" is the
                one predicate that keeps slot frees idempotent across
                retries (free the old slot / keep the new one iff the
                entry the probe will see exists).

    Healthy path: replicate the primary's entries (``ops``) to the r+1-hop
    backup holders via ppermute.  Degraded path (paper §4.3): requests
    routed to me as a BACKUP holder (primary dead) — I act as temporary
    primary, append to my backup log for that group, and forward
    replica-0 entries one hop to the replica-1 holder."""
    R = blog.tail.shape[0]
    ok = jnp.ones(rk.shape, bool)
    ok_local = jnp.ones(rk.shape, bool)
    nrep = jnp.zeros(rk.shape, I32)
    alive_me = alive[me]
    for r in range(R):
        pk = replicate_shift(rk, r + 1, AXIS)
        pa = replicate_shift(addr, r + 1, AXIS)
        po = replicate_shift(ops, r + 1, AXIS)
        should = (po > 0) & alive_me          # dead holders skip the append
        one = jax.tree.map(lambda a: a[r, 0], blog)
        one, okr = lg.append(one, pk, pa, po, should)
        ok = ok & replicate_shift(okr, (G - (r + 1)) % G, AXIS)
        nrep = nrep + replicate_shift(
            (should & okr).astype(I32), (G - (r + 1)) % G, AXIS)
        blog = jax.tree.map(lambda full, v, r=r: full.at[r, 0].set(v),
                            blog, one)
    for r in range(R):
        mine_as_backup = valid & (rg == (me - r - 1) % G) & (rg != me)
        opsb = jnp.where(mine_as_backup, opcode, 0).astype(jnp.int8)
        one = jax.tree.map(lambda a: a[r, 0], blog)
        one, okb = lg.append(one, rk, addr, opsb, mine_as_backup)
        ok = ok & okb
        ok_local = ok_local & okb
        nrep = nrep + (mine_as_backup & okb).astype(I32)
        blog = jax.tree.map(lambda full, v, r=r: full.at[r, 0].set(v),
                            blog, one)
    if R >= 2:
        ops0 = jnp.where(valid & (rg == (me - 1) % G) & (rg != me),
                         opcode, 0).astype(jnp.int8)
        fk = replicate_shift(rk, 1, AXIS)
        fa = replicate_shift(addr, 1, AXIS)
        fo = replicate_shift(ops0, 1, AXIS)
        fshould = (fo > 0) & alive_me
        one = jax.tree.map(lambda a: a[1, 0], blog)
        one, okf = lg.append(one, fk, fa, fo, fshould)
        ok = ok & replicate_shift(okf, (G - 1) % G, AXIS)
        nrep = nrep + replicate_shift(
            (fshould & okf).astype(I32), (G - 1) % G, AXIS)
        blog = jax.tree.map(lambda full, v: full.at[1, 0].set(v), blog, one)
    return blog, ok, nrep, ok_local


def _index_probe(cfg, store: KVStore, rk, me, G):
    """The fused index probe (hash chain walk + per-replica-slot backup
    probe in one kernel-dispatch call): the hash table answers lanes I
    own as true primary; for each replica slot I hold, the backup side
    consults its PENDING log first (newest wins), then the sorted
    replica — lane i is answered by replica r iff I hold replica r of
    lane i's owner group.  Returns (addr_p, found_p, acc_p, addr_b,
    found_b, acc_b); the caller combines the pair with its own
    ``am_primary`` mask."""
    R = store.blog.tail.shape[0]
    og = owner_group(rk, G)
    rep_sel = jnp.stack(
        [((me - r - 1) % G == og).astype(I32) for r in range(R)], axis=1)
    srt = jax.tree.map(lambda a: a[:, 0], store.bsorted)
    blg = jax.tree.map(lambda a: a[:, 0], store.blog)
    return kops.group_probe(cfg, _sq(store.hash), srt, blg, rk, rep_sel)


def _delete_body(cfg, G, capacity, store: KVStore, keys, valid,
                 degraded: bool):
    """Distributed DELETE: tombstone through primary log -> backup logs ->
    hash delete, mirroring _put_body minus the data-shard write; the
    value slot is freed immediately (the paper's data-server GC) — queued
    for the gc op when it lives on another shard.  The tombstones compact
    out of the sorted replicas at apply time.

    ``degraded`` is the compile-time analogue of the local layer's static
    primary_alive hint: with every server alive all requests land on true
    primaries, so the healthy variant skips the replica probe entirely;
    the backend picks the variant from its host-side liveness view."""
    me = jax.lax.axis_index(AXIS)
    bufs, slot, ok_route = _route_to_owner(store, keys, valid, G, capacity)
    recv = exchange(bufs, AXIS)
    rk, rg = recv["k"], recv["g"]
    # severed server: delivered lanes dropped un-acked (see _put_body)
    valid = (rg >= 0) & ~store.sever[me]
    addr = jnp.full(rk.shape, -1, I32)
    am_primary = rg == me
    data = store.data
    dcap = data.vals.shape[1]
    if degraded:
        # existence check BEFORE this batch's tombstones land: the
        # temporary primary consults its replica + pending log, so DELETE
        # reports found honestly even while the true primary is down
        old_a, old_f, _, addr_b, found_b, _ = _index_probe(
            cfg, store, rk, me, G)
        old_a = jnp.where(am_primary, old_a, addr_b)
        old_f = jnp.where(am_primary, old_f, found_b)
    else:
        old_a, old_f, _ = kops.probe(cfg, _sq(store.hash), rk)
        found_b = jnp.zeros(rk.shape, bool)   # no degraded lanes exist
    # free-queue push-back BEFORE the tombstone lands: a delete whose
    # value slot lives on another shard (or a dead one) must queue its
    # free — no room means the lane is nacked for a client retry, so the
    # free can never be silently dropped.  A nacked winner takes its
    # whole duplicate-key group with it (same rule as put's
    # spread_winner_addr): otherwise a loser lane would be re-elected
    # winner by the post-gate dedupe and append its free to the very
    # queue that had no room
    winner0 = dp.winner_mask(rk, valid)
    deff_me = data.alive[me] & ~data.sever[me]   # effective data liveness
    may_queue = (winner0 & old_f & (old_a >= 0)
                 & ~((old_a // dcap == me) & deff_me))
    bad = may_queue & ~_fq_pregate(data, may_queue)
    same = (rk[None, :] == rk[:, None]) & valid[None, :] & valid[:, None]
    valid = valid & ~(same & bad[None, :]).any(axis=1)
    ops = jnp.where(valid & am_primary, six.OP_DEL, 0).astype(jnp.int8)
    plog, ok_p = lg.append(_sq(store.plog), rk, addr, ops,
                           valid & am_primary)
    plog = plog._replace(applied=plog.tail)
    new_hash, found = hix.delete(_sq(store.hash), rk, cfg,
                                 valid & am_primary)
    blog, ok_rep, nrep, ok_loc = _replicate_logs(
        store.blog, store.alive & ~store.sever, rk, addr, ops, valid, rg,
        me, G, six.OP_DEL)
    # data-server GC, commit-gated (winner-deduped so a double-delete in
    # one batch frees exactly once): a primary lane frees once the hash
    # tombstoned the entry — the slot is unreferenced from that moment,
    # whatever the replication ack says; a temporary-primary lane frees
    # once MY pending log recorded the tombstone — the one predicate the
    # retry's probe consults, so the free fires exactly once whether the
    # wider replication acked or not
    gate = jnp.where(am_primary, found, ok_loc & old_f)
    freed = dp.winner_mask(rk, valid) & gate & (old_a >= 0)
    free_local = freed & (old_a // dcap == me) & deff_me
    used = dp.free_slots(data.used[0], old_a % dcap, free_local)
    freeq, fq_acc = _queue_remote_frees(data, rk, old_a,
                                        freed & ~free_local)
    fq_spill = data.fq_spill + (
        freed & ~free_local & ~fq_acc).sum().astype(I32)
    ok_req = (valid & ok_rep
              & ((am_primary & ok_p) | ~am_primary)).astype(I32)
    found_req = jnp.where(am_primary, found, found_b & valid).astype(I32)
    ret = route_return({"ok": ok_req, "found": found_req, "rep": nrep},
                       slot, AXIS)
    new_store = _bump_hb(store._replace(
        hash=_ex(store.hash, new_hash), plog=_ex(store.plog, plog),
        blog=blog, data=data._replace(used=data.used.at[0].set(used),
                                      freeq=freeq, fq_spill=fq_spill)))
    return (new_store, ret["ok"].astype(bool) & ok_route,
            ret["found"].astype(bool), ret["rep"])


def _get_body(cfg, G, capacity, store: KVStore, keys, valid):
    me = jax.lax.axis_index(AXIS)
    dest_g = owner_group(keys, G)
    dest = jax.vmap(lambda g: _first_alive_holder(g, store.alive))(dest_g)
    dest = jnp.where(valid, dest, G)   # padding lanes: no capacity consumed
    bufs, slot, ok_route = route_build(
        dest, {"k": (keys, key_inf(keys.dtype))}, G, capacity)
    recv = exchange(bufs, AXIS)
    rk = recv["k"]
    # primary path (one-sided hash probe) + backup path (pending log +
    # sorted replica, per replica slot) in ONE fused dispatch call
    addr_p, found_p, acc_p, addr_b, found_b, acc_b = _index_probe(
        cfg, store, rk, me, G)
    am_primary = owner_group(rk, G) == me
    addr = jnp.where(am_primary, addr_p, addr_b)
    found = jnp.where(am_primary, found_p, found_b)
    acc = jnp.where(am_primary, acc_p, acc_b)
    # --- value gather: one-sided read from the LOCAL data shard ---------
    # a severed data server's bytes are gone: flag its addresses for the
    # second-hop fetch, which fails over to a surviving mirror per-op
    dcap = store.data.vals.shape[1]
    val_ok = (found & (addr // dcap == me) & store.data.alive[me]
              & ~store.data.sever[me])
    local_slot = jnp.where(val_ok, addr % dcap, dcap)
    vals = jnp.concatenate(
        [store.data.vals[0], jnp.zeros((1,) + store.data.vals.shape[2:],
                                       I32)]
    )[jnp.clip(local_slot, 0, dcap)]
    # remote addr (value written on a different shard during a degraded
    # write, or this shard's data server masked dead): flagged
    # val_ok=False for a second-hop _fetch_body read (paper: the client
    # reads the value from the data server given the address).
    # A severed (crashed-but-undetected) server answers nothing: its
    # lanes come back srv=0 and the client retries them as un-routed
    # (the RPC timeout) until the lease detector demotes the device.
    srv = jnp.where(store.sever[me], jnp.zeros(rk.shape, I32),
                    jnp.ones(rk.shape, I32))
    back = route_return({"addr": addr, "found": found.astype(I32),
                         "acc": acc, "val": vals,
                         "vok": val_ok.astype(I32), "srv": srv}, slot, AXIS)
    # ok_route is reported separately from found: an unrouted lane (queue
    # full) is a push-back the client retries, not a miss
    routed = ok_route & back["srv"].astype(bool)
    return (back["addr"], back["found"].astype(bool) & routed,
            back["acc"], back["val"], routed,
            back["vok"].astype(bool))


def _fetch_body(G, capacity, store: KVStore, addrs, valid):
    """Second-hop value read: route each request to the first LIVE data
    holder of the shard owning its address — the shard itself, else a
    device hosting one of its mirror copies — and gather the value: the
    paper's client-side one-sided READ from the data server.  The data
    servers are a separate failure domain from the index servers (paper
    §2), so a fetch is answered even when the device's INDEX state is
    masked dead, and the mirrors answer when the DATA server is — masked
    OR severed: the failover keys on effective liveness, so mirror-served
    reads start the moment the data server crashes, ahead of the slower
    lease demotion.  Returns the store too (the fetch round renews the
    answering data servers' heartbeats)."""
    data = store.data
    dcap = data.vals.shape[1]
    Rv = data.mirror.shape[0]
    deff = data.alive & ~data.sever
    shard = jnp.where(addrs >= 0, addrs // dcap, 0)
    dest, servable = jax.vmap(
        lambda s: _first_alive_data_holder(s, deff, Rv))(shard)
    dest = jnp.where(valid & (addrs >= 0) & servable, dest, G)
    bufs, slot, ok_route = route_build(dest, {"a": (addrs, -1)}, G, capacity)
    recv = exchange(bufs, AXIS)
    ra = recv["a"]
    me = jax.lax.axis_index(AXIS)
    rs = jnp.where(ra >= 0, ra // dcap, G)
    lslot = jnp.where(ra >= 0, ra % dcap, dcap)
    pad = lambda a: jnp.concatenate(
        [a, jnp.zeros((1,) + a.shape[1:], a.dtype)])
    vals = pad(data.vals[0])[jnp.clip(lslot, 0, dcap)]
    taken = rs == me
    for r in range(Rv):
        sel = (rs == (me - r - 1) % G) & ~taken
        mv = pad(data.mirror[r, 0])[jnp.clip(lslot, 0, dcap)]
        vals = jnp.where(sel[:, None], mv, vals)
        taken = taken | sel
    back = route_return({"val": vals}, slot, AXIS)
    # a lane whose every holder is dead reports un-routed (push-back the
    # client surfaces as routed=False), never a fabricated zero value
    return (_bump_hb(store), back["val"],
            ok_route & (servable | ~valid | (addrs < 0)))


def _gc_body(G, capacity, store: KVStore):
    """One flush round of the pending free queues: route each queued freed
    address to the data shard that owns it, which clears the allocator
    bit.  Frees whose destination shard is masked dead, or that overflow
    the exchange, are re-queued for a later round."""
    data = store.data
    dcap = data.vals.shape[1]
    freeq = _sq(data.freeq)
    B = min(freeq.keys.shape[0], G * capacity)
    k, a, o, freeq = lg.take_pending(freeq, B)
    pend = o > 0
    dest_s = jnp.where(pend & (a >= 0), a // dcap, G)
    deff = data.alive & ~data.sever   # a severed shard's allocator is gone
    deliver = pend & (dest_s < G) & deff[jnp.clip(dest_s, 0, G - 1)]
    dest = jnp.where(deliver, dest_s, G)
    bufs, _, okq = route_build(dest, {"a": (a, -1)}, G, capacity)
    recv = exchange(bufs, AXIS)
    ra = recv["a"]
    used = dp.free_slots(data.used[0],
                         jnp.where(ra >= 0, ra % dcap, dcap), ra >= 0)
    requeue = pend & ~(deliver & okq)
    # re-queueing can't overflow (the round took out at least as many
    # entries as it puts back), but any rejection is counted so a drop
    # could never pass the audit silently
    freeq, okr = lg.append(freeq, k, a,
                           jnp.where(requeue, 1, 0).astype(jnp.int8),
                           requeue)
    fq_spill = data.fq_spill + (requeue & ~okr).sum().astype(I32)
    return _bump_hb(store._replace(data=data._replace(
        used=data.used.at[0].set(used), freeq=_ex(data.freeq, freeq),
        fq_spill=fq_spill)))


def _apply_body(cfg, batch, store: KVStore):
    blog = store.blog
    bsorted = store.bsorted
    for r in range(store.blog.tail.shape[0]):
        one_log = jax.tree.map(lambda a: a[r, 0], blog)
        one_srt = jax.tree.map(lambda a: a[r, 0], bsorted)
        keys, addrs, ops, one_log = lg.take_pending(one_log, batch)
        one_srt = kops.merge(cfg, one_srt, keys, addrs, ops)
        blog = jax.tree.map(lambda f, v, r=r: f.at[r, 0].set(v), blog, one_log)
        bsorted = jax.tree.map(lambda f, v, r=r: f.at[r, 0].set(v),
                               bsorted, one_srt)
    return _bump_hb(store._replace(blog=blog, bsorted=bsorted))


def _tick_body(store: KVStore):
    """Heartbeat-only round: lets read-only traffic (GET/fetch) age the
    leases without mutating index state."""
    return _bump_hb(store)


def _scan_body(cfg, G, limit, store: KVStore, lo, hi):
    me = jax.lax.axis_index(AXIS)
    # drain my replicas, then range-query the ones I should serve.  The
    # ring bounds pending entries by log_capacity, so the round bound
    # guarantees a COMPLETE drain (SCAN serializability); the while_loop
    # exits as soon as this device's logs are empty, so a mostly-drained
    # store pays one merge round, not log_capacity/batch of them.  (No
    # collectives in the body, so per-device trip counts are safe.)
    rounds = max(1, -(-cfg.log_capacity // cfg.async_apply_batch))

    def _pending(st):
        return jnp.max(st.blog.tail - st.blog.applied)

    st, _ = jax.lax.while_loop(
        lambda c: (c[1] < rounds) & (_pending(c[0]) > 0),
        lambda c: (_apply_body(cfg, cfg.async_apply_batch, c[0]), c[1] + 1),
        (store, jnp.int32(0)))
    outs_k, outs_a = [], []
    # effective liveness: a severed holder cannot serve (its replica was
    # destroyed in the crash), and duty falls through to the next replica
    # immediately — the per-op failover a real scan client gets from an
    # RPC timeout, independent of the slower lease-based demotion
    eff = store.alive & ~store.sever
    for r in range(store.blog.tail.shape[0]):
        srt = jax.tree.map(lambda a: a[r, 0], st.bsorted)
        k, a, n = kops.range_query(cfg, srt, lo[0], hi[0], limit)
        g = (me - r - 1) % G
        # serve replica r of group g iff I'm alive and EVERY
        # lower-replica holder (devices g+1 .. g+r) is dead — exactly
        # one live holder serves whatever the dead/alive pattern (with
        # R >= 3 an alive-dead-alive ladder must not double-serve)
        prev_ok = jnp.zeros((), bool)
        for rp in range(r):
            prev_ok = prev_ok | eff[(g + rp + 1) % G]
        serve = eff[me] & ~prev_ok
        k = jnp.where(serve, k, key_inf(k.dtype))
        a = jnp.where(serve, a, -1)
        outs_k.append(k)
        outs_a.append(a)
    mk = jnp.stack(outs_k)          # [R, limit]
    ma = jnp.stack(outs_a)
    allk = jax.lax.all_gather(mk, AXIS).reshape(-1)   # [G*R*limit]
    alla = jax.lax.all_gather(ma, AXIS).reshape(-1)
    order = jnp.argsort(allk)
    # scan-completeness contract: group g is COVERED iff at least one of
    # its R holders is effective-alive (scans are backup-served; the
    # primary's hash cannot answer a range query).  A group with zero
    # live, unsevered holders was silently absent from the merge above —
    # the honest flag lets the client retry/report instead (eff is
    # replicated, so every device computes the identical mask)
    gidx = jnp.arange(G)
    covered = jnp.zeros((G,), bool)
    for r in range(store.blog.tail.shape[0]):
        covered = covered | eff[(gidx + r + 1) % G]
    return allk[order][:limit], alla[order][:limit], covered, _bump_hb(st)


# ---------------------------------------------------------------------------
# Public API (jit + shard_map wrappers)
# ---------------------------------------------------------------------------
def _smap(name, mesh, f, in_specs, out_specs):
    """``f`` shard_mapped over ``mesh`` and jitted under its own ``name``:
    the device trace's module then reads ``jit_<name>(<fingerprint>)``."""
    def program(*args):
        return f(*args)

    program.__name__ = program.__qualname__ = name
    return jax.jit(jax.shard_map(program, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


@functools.lru_cache(maxsize=32)
def make_ops(mesh, cfg, capacity_q: int = 64, scan_limit: int = 128):
    """Build the jitted distributed ops for a mesh.

    put(st, keys, vals, valid)  -> (st, ok, addrs, nrep)
    put_degraded(...)           -> as put, plus the old-slot replica probe
                                   at temporary primaries and the one-hop
                                   value displacement off dead data shards
                                   (use while any server is masked dead)
    get(st, keys, valid)        -> (addrs, found, accesses, vals, routed,
                                    val_ok)
    fetch(st, addrs, valid)     -> (st, vals, routed)  second-hop value
                                   read (returns the store: the round
                                   renews data-server heartbeats)
    delete(st, keys, valid)     -> (st, ok, found, nrep)
    delete_degraded(...)        -> as delete, plus the replica probe that
                                   answers found at a temporary primary
                                   (use while any server is masked dead)
    apply(st)                   -> st
    gc(st)                      -> st   one free-queue flush round
    scan(st, lo, hi)            -> (keys, addrs, covered, st) —
                                   covered[g] False when group g had no
                                   live, unsevered holder to serve it
                                   (the scan-completeness contract)
    tick(st)                    -> st   heartbeat-only round: read-heavy
                                   clients age their leases without a
                                   mutating op in flight

    Each program is jitted under the name ``histore_<key>``, so the
    device trace's modules tell them apart.
    """
    G = mesh.devices.size
    S = _specs()

    put, put_degraded = (
        _smap(name, mesh,
              lambda st, k, v, m, d=d: _put_body(cfg, G, capacity_q,
                                                 st, k, v, m, d),
              (S, P(AXIS), P(AXIS), P(AXIS)),
              (S, P(AXIS), P(AXIS), P(AXIS)))
        for name, d in (("histore_put", False),
                        ("histore_put_degraded", True)))
    get = _smap("histore_get", mesh,
                lambda st, k, m: _get_body(cfg, G, capacity_q, st, k, m),
                (S, P(AXIS), P(AXIS)),
                (P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS)))
    fetch = _smap("histore_fetch", mesh,
                  lambda st, a, m: _fetch_body(G, capacity_q, st, a, m),
                  (S, P(AXIS), P(AXIS)), (S, P(AXIS), P(AXIS)))
    delete, delete_degraded = (
        _smap(name, mesh,
              lambda st, k, m, d=d: _delete_body(cfg, G, capacity_q,
                                                 st, k, m, d),
              (S, P(AXIS), P(AXIS)),
              (S, P(AXIS), P(AXIS), P(AXIS)))
        for name, d in (("histore_delete", False),
                        ("histore_delete_degraded", True)))
    apply_async = _smap("histore_apply", mesh,
                        lambda st: _apply_body(cfg, cfg.async_apply_batch, st),
                        (S,), S)
    gc = _smap("histore_gc", mesh, lambda st: _gc_body(G, capacity_q, st),
               (S,), S)
    scan = _smap("histore_scan", mesh,
                 lambda st, lo, hi: _scan_body(cfg, G, scan_limit,
                                               st, lo, hi),
                 (S, P(AXIS), P(AXIS)), (P(), P(), P(), S))
    tick = _smap("histore_tick", mesh, _tick_body, (S,), S)
    return {"put": put, "put_degraded": put_degraded, "get": get,
            "fetch": fetch, "delete": delete,
            "delete_degraded": delete_degraded, "apply": apply_async,
            "gc": gc, "scan": scan, "tick": tick}


def device_counters(store: KVStore) -> dict:
    """Surface the store's device-resident counters as host ints for the
    telemetry snapshot: live servers per plane, heartbeat totals, the
    worst backup log's pending depth, plus the value plane's counters
    (``fq_spill``, free-queue occupancy).  Called only at snapshot time
    (``client.metrics()``), never from an op body — telemetry adds no
    device syncs to the hot path."""
    alive, hb, pending = jax.device_get(
        (store.alive, store.hb, store.blog.tail - store.blog.applied))
    import numpy as np
    out = {
        "live_index_servers": int(np.asarray(alive).sum()),
        "index_heartbeats": int(np.asarray(hb).sum()),
        "pending_log_ops": int(np.asarray(pending).max()),
    }
    out.update(dp.device_counters(store.data))
    return out


# ---------------------------------------------------------------------------
# Failure & recovery protocol (paper §4.3, host-side control plane)
# ---------------------------------------------------------------------------
def _wipe_index_state(store: KVStore, dev: int) -> KVStore:
    """Destroy the index state device ``dev`` held — the hash table +
    primary log of group ``dev`` and every sorted replica + backup log
    hosted on ``dev`` (the crash's data loss; the data shard survives:
    data servers are a separate failure domain, paper §2)."""
    INF = key_inf(store.bsorted.keys.dtype)
    h, s = store.hash, store.bsorted
    p_empty = lg.clear(jax.tree.map(lambda a: a[dev], store.plog))
    b_empty = lg.clear(jax.tree.map(lambda a: a[:, dev], store.blog))
    return store._replace(
        hash=hix.HashIndex(
            sig=h.sig.at[dev].set(0), fp=h.fp.at[dev].set(0),
            addr=h.addr.at[dev].set(-1), fill=h.fill.at[dev].set(0)),
        plog=jax.tree.map(lambda f, v: f.at[dev].set(v), store.plog,
                          p_empty),
        bsorted=six.SortedIndex(
            keys=s.keys.at[:, dev].set(INF),
            addrs=s.addrs.at[:, dev].set(-1),
            size=s.size.at[:, dev].set(0)),
        blog=jax.tree.map(lambda f, v: f.at[:, dev].set(v), store.blog,
                          b_empty))


def fail_server(store: KVStore, dev: int, wipe: bool = True) -> KVStore:
    """ORACLE kill switch: mask device ``dev``'s INDEX server dead with
    the client told immediately.  ``wipe`` (default) also destroys the
    index state it held, so recovery MUST rebuild from surviving copies
    (the honest failure model; fail_data_server is the data plane's own
    kill switch).  For failures the client must DISCOVER via its leases,
    use ``sever_server`` instead."""
    store = store._replace(alive=store.alive.at[dev].set(False))
    return _wipe_index_state(store, dev) if wipe else store


def sever_server(store: KVStore, dev: int, wipe: bool = True) -> KVStore:
    """Crash device ``dev``'s index server WITHOUT telling the client:
    its index state is destroyed (``wipe``) and its heartbeats stop, but
    ``alive`` — the client's routing view — still says up.  Requests
    delivered there are dropped un-acked (RPC timeouts the client
    retries) until the client's lease detector notices the stalled
    heartbeat counter and demotes the device to degraded routing — the
    paper's §5 failure-detection story, with no oracle fail_server
    call anywhere."""
    store = store._replace(sever=store.sever.at[dev].set(True))
    return _wipe_index_state(store, dev) if wipe else store




def fail_data_server(store: KVStore, dev: int, wipe: bool = True) -> KVStore:
    """Mask device ``dev``'s DATA server dead (see data_plane.py)."""
    return dp.fail_data_server(store, dev, wipe)


def sever_data_server(store: KVStore, dev: int,
                      wipe: bool = True) -> KVStore:
    """Crash device ``dev``'s DATA server without telling the client —
    the value plane's lease-detection kill switch (see data_plane.py)."""
    return dp.sever_data_server(store, dev, wipe)


def recover_data_server(store: KVStore, dev: int, cfg,
                        apply_fn=None) -> KVStore:
    """Rebuild device ``dev``'s data shard from its mirrors and mark-sweep
    the allocator (see data_plane.py); ``apply_fn`` turns the mark-sweep's
    log barrier into incremental shard_map'd catch-up rounds."""
    return dp.recover_data_server(store, dev, cfg, apply_fn)


def migrate_values(store: KVStore, cfg, apply_fn=None):
    """Background value migration: move degraded-write strays back to
    their owner group's shard and patch the index addresses, restoring
    one-RTT GETs (see data_plane.py).  ``apply_fn`` (the mesh's jitted
    apply op) turns the pass's log barrier into incremental shard_map'd
    catch-up rounds.  Returns (store, n_moved)."""
    return dp.migrate_values(store, cfg, owner_group, apply_fn)


# the shared eager drain primitive (one home for the semantics)
_drain_one = dp.drain_pair


def _set_slice(tree, val, idx):
    return jax.tree.map(lambda f, v: f.at[idx].set(v), tree, val)


def _fresh_hash_like(hs) -> hix.HashIndex:
    return hix.HashIndex(sig=jnp.zeros_like(hs.sig),
                         fp=jnp.zeros_like(hs.fp),
                         addr=jnp.full_like(hs.addr, -1),
                         fill=jnp.zeros_like(hs.fill))


def _hash_from_items(hs_like, keys, addrs, cfg):
    """Fresh hash table holding exactly the given host-side items."""
    import numpy as np

    from repro.core.hashing import pad_pow2
    kp, vm = pad_pow2(keys, 0)
    ap, _ = pad_pow2(np.asarray(addrs, np.int32), -1)
    new_hash, _ = hix.insert(_fresh_hash_like(hs_like), kp, ap, cfg, vm)
    return new_hash


def _sorted_from_items(srt_like, keys, addrs):
    """Fresh sorted replica holding exactly the given host-side items."""
    import numpy as np

    cap = int(srt_like.keys.shape[0])
    kd = np.asarray(srt_like.keys).dtype
    order = np.argsort(np.asarray(keys, kd), kind="stable")
    n = len(order)
    ks = np.full((cap,), np.iinfo(kd).max, kd)
    ads = np.full((cap,), -1, np.int32)
    ks[:n] = np.asarray(keys, kd)[order]
    ads[:n] = np.asarray(addrs, np.int32)[order]
    return six.SortedIndex(keys=jnp.asarray(ks), addrs=jnp.asarray(ads),
                           size=jnp.asarray(n, I32))


def _group_authority_items(store: KVStore, cfg, g: int, eff):
    """Host-side (keys, addrs) of group ``g`` from its best surviving
    authority: the primary's hash (keys fetched from the data items —
    the paper's rebuild-from-data), else a live drained sorted replica,
    else the data-plane slot scan.  Raises RecoveryError when none of
    the three can answer."""
    import numpy as np

    G = int(store.alive.shape[0])
    R = int(store.blog.tail.shape[0])
    if eff[g]:
        hs = jax.tree.map(lambda a: a[g], store.hash)
        vm = np.asarray(hix.valid_mask(hs))
        addrs = np.asarray(hs.addr)[vm]
        try:
            keys = dp.keys_for_addrs(store, addrs)
        except dp.RecoveryError as e:
            raise dp.RecoveryError(
                g, ["hash + data-plane keys"] + e.searched, e.blockers)
        return keys, addrs.astype(np.int32)
    for r in range(R):
        h = (g + r + 1) % G
        if not eff[h]:
            continue
        srt = jax.tree.map(lambda a: a[r, h], store.bsorted)
        blog = jax.tree.map(lambda a: a[r, h], store.blog)
        srt, _ = _drain_one(srt, blog, cfg)
        keys, addrs, valid = six.items(srt)
        v = np.asarray(valid)
        return np.asarray(keys)[v], np.asarray(addrs)[v]
    return dp.group_items_from_data(store, cfg, g, owner_group)


def recover_server(store: KVStore, dev: int, cfg,
                   online: bool = True) -> KVStore:
    """Recover device ``dev``'s index server from surviving copies
    (host-side control plane; eager, not shard_map'd):

      1. rebuild group ``dev``'s hash table from the first live sorted
         replica of that group — the paper's hash-from-skiplist rebuild;
      2. re-clone every sorted replica + backup log ``dev`` hosts from a
         surviving copy of the same group (skiplist-from-replica);
      3. clear a severed heartbeat and mark ``dev`` alive again.

    ``online`` (default) clones SNAPSHOTS — the source replica is NOT
    drained first; its pending UpdateLog delta is cloned alongside and
    streams into the rebuilt replicas through the ordinary incremental
    ``apply`` op while foreground PUT/GET/SCAN traffic continues.  The
    hash (synchronous by contract) is built from the snapshot plus a
    replay of the cloned pending window.  ``online=False`` keeps the
    stop-the-world drain-then-clone for comparison (fig13's
    catch-up-vs-stop-the-world mode).

    Multi-failure fallback: a group with no live sorted replica rebuilds
    from its primary's hash + the keys stored with the data items
    (paper: the skiplist rebuild fetches the keys from the data
    servers), else from a full data-plane slot scan; RecoveryError (with
    the searched sources and actionable blockers) is raised only when
    truly no copy exists."""
    import numpy as np

    G = int(store.alive.shape[0])
    R = int(store.blog.tail.shape[0])
    alive = np.asarray(store.alive)
    sever = np.asarray(store.sever)
    if bool(alive[dev]) and not bool(sever[dev]):
        return store
    # the recovered server heartbeats again; it stays routed-dead until
    # the rebuild below completes
    store = store._replace(sever=store.sever.at[dev].set(False),
                           alive=store.alive.at[dev].set(False))
    if G == 1:
        # single-server store: nothing was wiped (no surviving copy could
        # exist), recovery is just the liveness flip
        return store._replace(alive=store.alive.at[dev].set(True))
    eff = alive & ~sever
    eff[dev] = False

    def first_live_holder(group, exclude):
        for r in range(R):
            h = (group + r + 1) % G
            if h != exclude and eff[h]:
                return r, h
        return None

    # -- 1. hash rebuild for group ``dev`` --------------------------------
    src = first_live_holder(dev, dev)
    hs_like = jax.tree.map(lambda a: a[dev], store.hash)
    if src is not None:
        r, h = src
        srt = jax.tree.map(lambda a: a[r, h], store.bsorted)
        blog = jax.tree.map(lambda a: a[r, h], store.blog)
        if not online:
            srt, blog = _drain_one(srt, blog, cfg)
            store = store._replace(
                bsorted=_set_slice(store.bsorted, srt, (r, h)),
                blog=_set_slice(store.blog, blog, (r, h)))
        keys, addrs, valid = six.items(srt)
        # the valid mask keeps empty sorted-array slots out of the table
        # entirely (no appended-then-tombstoned junk eating chain room)
        new_hash, _ = hix.insert(_fresh_hash_like(hs_like), keys, addrs,
                                 cfg, valid)
        if online:
            new_hash = hix.replay_pending(new_hash, blog, cfg)
    else:
        # every replica holder dead: fall back to the data plane — the
        # keys stored with the values reconstruct (key, addr) for any
        # group (raises RecoveryError with blockers when it can't)
        k_np, a_np = dp.group_items_from_data(store, cfg, dev,
                                              owner_group)
        new_hash = _hash_from_items(hs_like, k_np, a_np, cfg)
    store = store._replace(hash=_set_slice(store.hash, new_hash, dev),
                           plog=_set_slice(
                               store.plog,
                               lg.create(store.plog.keys.shape[1],
                                         store.plog.keys.dtype), dev))
    # -- 2. sorted-replica rebuild for each group hosted on ``dev`` -------
    empty_blog = lg.create(store.plog.keys.shape[1],
                           store.plog.keys.dtype)
    for r2 in range(R):
        g = (dev - r2 - 1) % G
        src2 = first_live_holder(g, dev)
        if src2 is not None:
            r3, h3 = src2
            s_srt = jax.tree.map(lambda a: a[r3, h3], store.bsorted)
            s_blog = jax.tree.map(lambda a: a[r3, h3], store.blog)
            if not online:
                s_srt, s_blog = _drain_one(s_srt, s_blog, cfg)
                store = store._replace(
                    bsorted=_set_slice(store.bsorted, s_srt, (r3, h3)),
                    blog=_set_slice(store.blog, s_blog, (r3, h3)))
            # online: the clone carries the source's pending window; the
            # ordinary apply op streams it into BOTH copies identically
            store = store._replace(
                bsorted=_set_slice(store.bsorted, s_srt, (r2, dev)),
                blog=_set_slice(store.blog, s_blog, (r2, dev)))
        else:
            # no live replica of group g anywhere else: rebuild this
            # copy from the group's surviving authority (primary hash +
            # data-plane keys, else the data-plane scan) instead of the
            # old silent skip that left an empty replica serving scans
            k_np, a_np = _group_authority_items(store, cfg, g, eff)
            store = store._replace(
                bsorted=_set_slice(
                    store.bsorted,
                    _sorted_from_items(
                        jax.tree.map(lambda a: a[r2, dev], store.bsorted),
                        k_np, a_np), (r2, dev)),
                blog=_set_slice(store.blog, empty_blog, (r2, dev)))
    return store._replace(alive=store.alive.at[dev].set(True))


def re_replicate(store: KVStore, cfg) -> tuple:
    """Post-recovery re-replication pass (closes the multi-failure
    window): for every group, verify each LIVE holder's sorted replica
    against the group's authority — the primary's hash when alive, else
    the first live replica — and rebuild any copy that diverged, so R
    valid copies exist again before the next failure.  Verification
    drains COPIES (like parity_report): healthy replicas with pending
    catch-up debt compare clean and are left untouched, so the pass does
    not stop the online catch-up.  Returns (store, n_rebuilt)."""
    import numpy as np

    G = int(store.alive.shape[0])
    R = int(store.blog.tail.shape[0])
    eff = np.asarray(store.alive) & ~np.asarray(store.sever)
    rebuilt = 0
    for g in range(G):
        auth = None      # (keys, addrs) fetched lazily on first mismatch
        if eff[g]:
            hs = jax.tree.map(lambda a: a[g], store.hash)
            n_auth = int(hix.n_items(hs))
        else:
            src = None
            for r in range(R):
                h = (g + r + 1) % G
                if eff[h]:
                    src = (r, h)
                    break
            if src is None:
                continue       # nothing to verify against (recover first)
            srt = jax.tree.map(lambda a: a[src[0], src[1]], store.bsorted)
            blog = jax.tree.map(lambda a: a[src[0], src[1]], store.blog)
            srt, _ = _drain_one(srt, blog, cfg)
            keys, addrs, valid = six.items(srt)
            v = np.asarray(valid)
            auth = (np.asarray(keys)[v], np.asarray(addrs)[v])
            n_auth = len(auth[0])
        for r in range(R):
            h = (g + r + 1) % G
            if not eff[h] or (not eff[g] and src == (r, h)):
                continue
            srt = jax.tree.map(lambda a: a[r, h], store.bsorted)
            blog = jax.tree.map(lambda a: a[r, h], store.blog)
            dsrt, _ = _drain_one(srt, blog, cfg)
            keys, addrs, valid = six.items(dsrt)
            v = np.asarray(valid)
            rk, ra = np.asarray(keys)[v], np.asarray(addrs)[v]
            if eff[g]:
                a_h, f_h, _ = kops.probe(cfg, hs, keys)
                okk = (len(rk) == n_auth
                       and bool(np.asarray(f_h | ~valid).all())
                       and bool(np.asarray((a_h == addrs) | ~valid).all()))
            else:
                okk = (len(rk) == n_auth
                       and bool(np.array_equal(rk, auth[0]))
                       and bool(np.array_equal(ra, auth[1])))
            if okk:
                continue
            if auth is None:
                try:
                    auth = _group_authority_items(store, cfg, g, eff)
                except dp.RecoveryError:
                    break      # unverifiable right now (data shard dead)
            store = store._replace(
                bsorted=_set_slice(store.bsorted,
                                   _sorted_from_items(srt, *auth), (r, h)),
                blog=_set_slice(store.blog,
                                lg.create(store.plog.keys.shape[1],
                                          store.plog.keys.dtype), (r, h)))
            rebuilt += 1
    return store, rebuilt


def parity_report(store: KVStore, cfg, apply_fn=None) -> list:
    """Hash/sorted parity + value-slot audit (test/debug helper, eager).
    For every group g and replica r: drain a COPY of the replica, then
    check the replica's live item count equals the hash table's, every
    replica key is found in the hash, and the addresses agree.  A final
    ``value_slots`` entry audits the data plane's slot accounting (every
    live address allocated, nothing orphaned or double-referenced, no
    free-queue spill — see data_plane.value_slot_audit).  Returns a list
    of dicts with an ``agree`` bool; entries carry ``primary_alive`` /
    ``holder_alive`` — TRUE liveness (a severed-but-undetected server
    reports dead: the report is the omniscient test oracle, not the
    client's view) — so a mid-failure caller can restrict the assertion
    to live structures."""
    import numpy as np

    G = int(store.alive.shape[0])
    R = int(store.blog.tail.shape[0])
    alive = np.asarray(store.alive) & ~np.asarray(store.sever)
    out = []
    for g in range(G):
        hs = jax.tree.map(lambda a: a[g], store.hash)
        n_hash = int(hix.n_items(hs))
        for r in range(R):
            h = (g + r + 1) % G
            srt = jax.tree.map(lambda a: a[r, h], store.bsorted)
            blog = jax.tree.map(lambda a: a[r, h], store.blog)
            srt, _ = _drain_one(srt, blog, cfg)
            keys, addrs, valid = six.items(srt)
            n_sorted = int(valid.sum())
            a_h, f_h, _ = kops.probe(cfg, hs, keys)
            found_ok = bool(np.asarray(f_h | ~valid).all())
            addr_ok = bool(np.asarray((a_h == addrs) | ~valid).all())
            out.append({"group": g, "replica": r, "holder": h,
                        "primary_alive": bool(alive[g]),
                        "holder_alive": bool(alive[h]),
                        "n_hash": n_hash, "n_sorted": n_sorted,
                        "agree": (n_hash == n_sorted) and found_ok
                        and addr_ok})
    out.append(dp.value_slot_audit(store, cfg, apply_fn))
    return out
