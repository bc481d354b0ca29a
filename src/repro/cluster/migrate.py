"""Snapshot migration over the content-addressed store.

A migratable tenant (MMAP_CLEAN / PARTIAL / HIBERNATED) is, or can
cheaply become, a pile of disk state: a private REAP file holding the
working set in first-touch order, plus per-unit digests into the
deployment's refcounted CAS segment file.  Migration therefore ships
*metadata plus missing digests*, never a full snapshot:

  1. **Fence** — under the source engine's serve lock the instance fires
     ``MIGRATE`` and lands in the MIGRATING state: the governor can no
     longer deflate or TERMINATE it (the transitions are illegal), and
     requests/wakes block on the :class:`MigrationHandle` exactly like
     late arrivals block on a shared wake pipeline.
  2. **Flush** — a not-yet-hibernated source runs the normal full-deflate
     body (same code path, different state-machine event), then the REAP
     file's units are hashed into the source store so *everything* the
     tenant owns is content-addressed.  The REAP file itself is not
     shipped — only its key order is, so the target can rebuild it with
     identical streaming layout.
  3. **Ship** — the :class:`StorePeer` asks the target store which
     digests it lacks and transfers only those, at their stored
     compression level.  Base weights a same-deployment tenant already
     parked on the target cost zero bytes; per-session KV deltas are the
     usual payload.
  4. **Rebuild** — the target constructs a hibernated husk: factory
     shapes, adopted extent table (refcounts taken), REAP file rewritten
     from the local store in first-touch order, recorder + arrival-EWMA
     state installed, KV session page tables recreated Not-Present.
     The first wake on the target is byte-identical to an in-place wake.
  5. **Commit** — the source fires ``MIGRATE_DONE``, releases its store
     refs (segment GC: bytes another local tenant still references
     survive), deletes its REAP file, and records the forwarding address
     so stragglers raise ``TenantMigrated`` and get rerouted.

On any transfer/rebuild error the source fires ``MIGRATE_ABORT`` back to
HIBERNATE — its disk state was never touched, so it keeps serving
locally — and the :class:`StorePeer` sweeps whatever segments it had
already imported on the target (never-adopted imports are refcount-zero
orphans; leaking them would be a slow disk leak on every failed
transfer).  Once ``MIGRATE_DONE`` fires the commit is irrevocable: the
target owns the tenant, so source-side finalization (forwarding address,
terminate, store GC) runs to completion even if the commit callback or
cleanup itself fails — a crash there must never strand the tenant on
both nodes or neither.

The channel is a :class:`~repro.cluster.transport.Transport`: in-process
loopback by default, or a length-prefixed socket speaking the
:mod:`repro.cluster.wire` binary protocol for real multi-host moves.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from repro.cluster.faults import checkpoint
from repro.cluster.transport import (AuthError, LoopbackTransport,
                                     Transport, TransportError)
from repro.core.governor import MIGRATABLE_STATES
from repro.core.instance import ModelInstance
from repro.core.prefix import PREFIX_OWNER
from repro.core.state import ContainerState, Event
from repro.serving.paged_kv import KVSession, PagedKVCache

S = ContainerState

#: rough per-key wire cost of the metadata half of a migration (extent
#: records, recorder entries, KV page-table slots) — accounting only
_META_BYTES_PER_KEY = 64


class MigrationError(RuntimeError):
    pass


@dataclass
class TransferStats:
    """What one migration actually moved."""
    digests_total: int = 0
    digests_shipped: int = 0          # absent on the target: crossed the link
    bytes_shipped: int = 0            # stored (compressed) payload bytes sent
    bytes_dedup: int = 0              # stored bytes the target already held
    meta_bytes: int = 0               # extent/recorder/page-table metadata
    full_snapshot_bytes: int = 0      # naive verbatim snapshot (raw units)
    link_seconds: float = 0.0         # bytes over the modelled link bw
    seconds: float = 0.0              # wall time of the whole migration

    @property
    def wire_bytes(self) -> int:
        return self.bytes_shipped + self.meta_bytes


class StorePeer:
    """Transfer channel between two nodes' CAS stores, over a
    :class:`~repro.cluster.transport.Transport`.

    Both stores must share the deployment salt — the digest *is* the
    cluster-wide content address, so an unsalted-compatible peer would be
    a different deployment and shipping to it is refused (loopback
    compares salts directly; the socket transport proves possession via
    the keyed-nonce handshake, so the salt never crosses the wire).

    The peer remembers every digest it ships; if the migration aborts
    before the target adopts them, :meth:`release_remote` sweeps those
    refcount-zero imports so a failed transfer leaks nothing."""

    def __init__(self, src_store, dst_store=None, *,
                 transport: Optional[Transport] = None,
                 link_bw_bytes_s: float = 4 << 30,
                 chunk_bytes: int = 4 << 20):
        if src_store is None or (dst_store is None and transport is None):
            raise MigrationError("migration requires the dedup store on "
                                 "both nodes (ManagerConfig.dedup_store)")
        if transport is None:
            transport = LoopbackTransport(dst_store=dst_store)
        self.src = src_store
        self.transport = transport
        self.link_bw_bytes_s = link_bw_bytes_s
        self.chunk_bytes = chunk_bytes
        self.shipped: List[bytes] = []    # imported on target, not adopted
        try:
            transport.authenticate(src_store.salt)
        except AuthError as e:
            raise MigrationError(str(e)) from e

    def missing(self, digests) -> List[bytes]:
        return self.transport.missing_digests(list(digests))

    def ship(self, digests, stats: TransferStats) -> None:
        """Move the given digests' segments src -> dst, dedup-aware:
        only segments absent on the target cross the link, in chunks so
        the transport's flow control applies within one migration.  On
        failure the already-shipped chunks are swept on the target
        before the error propagates — no refcount leak mid-bundle."""
        digests = list(digests)
        stats.digests_total += len(digests)
        missing = self.missing(digests)
        stats.digests_shipped += len(missing)
        stats.bytes_dedup += self.src.stored_bytes_of(
            [d for d in digests if d not in set(missing)])
        sent = 0
        try:
            for chunk in self.src.export_segments_iter(
                    missing, chunk_bytes=self.chunk_bytes):
                self.shipped.extend(d for d, _, _, _ in chunk)
                sent += self.transport.send_segments(chunk)
            self.transport.barrier()
        except BaseException:
            self.release_remote()
            raise
        stats.bytes_shipped += sent
        stats.link_seconds += sent / max(self.link_bw_bytes_s, 1.0)

    def adopted(self) -> None:
        """The bundle landed and the target took refs: nothing to sweep."""
        self.shipped = []

    def release_remote(self) -> int:
        """Abort cleanup: free segments we imported on the target that
        were never adopted.  Best-effort — if the channel itself is dead
        the server's connection-teardown sweep reclaims them instead."""
        if not self.shipped:
            return 0
        digests, self.shipped = self.shipped, []
        try:
            return self.transport.sweep_orphans(digests)
        except (TransportError, OSError):
            return 0


class MigrationHandle:
    """Shared handle for one in-flight migration — ``inst.migration``.

    Requests and wakes landing on the MIGRATING tenant :meth:`wait` on
    it (the in-flight-request handoff), mirroring how late wake arrivals
    share the wake pipeline handle."""

    def __init__(self, instance_id: str, source: str, target: str):
        self.instance_id = instance_id
        self.source_node_id = source
        self.target_node_id = target
        self.stats = TransferStats()
        self.error: Optional[BaseException] = None
        #: True once ``MIGRATE_DONE`` fired — past this point the target
        #: owns the tenant and the source will finish its teardown even
        #: if a later step (commit callback, local GC) records an error
        self.committed = False
        self._done = threading.Event()

    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def ok(self) -> bool:
        return self._done.is_set() and self.error is None

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def _finish(self, error: Optional[BaseException] = None) -> None:
        self.error = error
        self._done.set()


@dataclass
class _Bundle:
    """The metadata half of a migration (in-process wire format)."""
    instance_id: str
    arch_key: str
    base_id: Optional[str]
    shared_paths: frozenset
    extents: Dict                      # key -> UnitMeta (digests)
    reap_order: List                   # REAP file keys, first-touch order
    stable: List                       # recorder stable set, ordered
    misses: Dict                       # recorder coldness counters (pruned)
    kv_sessions: List[Dict]
    last_used: float
    created_at: float
    #: the kept-alive compiled executables ride along — in this
    #: in-process simulation they transfer by reference, standing in for
    #: a node-shared persistent compilation cache; without them the
    #: migrant's first request would pay a cold-start-sized re-JIT,
    #: which is exactly the cost hibernation exists to avoid
    compiled: Dict = field(default_factory=dict)
    arrival: Optional[Tuple] = None    # governor EWMA (last_ts, gap)
    wire_keys: int = 0
    #: prefix-registry entries the tenant's sessions share, as pure
    #: metadata records — the target rebuilds pages from its own
    #: registry/store by digest, never from re-transferred payloads
    prefix_records: List[Dict] = field(default_factory=list)
    #: store extent table for the registry's CAS keys (pfx/pfxh) backing
    #: those records; adopted under the target's ``__prefix__`` client
    prefix_extents: Dict = field(default_factory=dict)

    def meta_bytes(self) -> int:
        return self.wire_keys * _META_BYTES_PER_KEY


def _export_bundle(src_node, inst: ModelInstance,
                   arch_key: str) -> _Bundle:
    """Steps 1½–2: flush the REAP file into the CAS store and snapshot
    every piece of metadata the target needs.  Runs with the instance
    fenced in MIGRATING."""
    # the coldness counters ship with the tenant; prune dead keys (closed
    # sessions' KV pages) FIRST or they would leak onto the target forever
    live = set(inst.units) | set(inst.reap_file.extents) \
        | set(inst.swap_file.extents)
    inst.recorder.prune_misses(live)

    reap_order = list(inst.reap_file.extents)
    # the full-deflate body already content-addressed the working set
    # (write-through); this is only the safety net for keys that missed
    # it, so an already-inventoried tenant pays zero re-hashing here
    missing_ws = [k for k in reap_order if k not in inst.swap_file]
    if missing_ws:
        data = inst.reap_file.read_batch()
        inst.swap_file.write_units([(k, data[k]) for k in missing_ws])

    kv_sessions: List[Dict] = []
    if inst.kv is not None:
        for sid, s in inst.kv.sessions.items():
            kv_sessions.append({
                "session_id": sid,
                "num_tokens": s.num_tokens,
                "token_ids": list(s.token_ids),
                "page_counts": [len(layer) for layer in s.pages],
                "host_shapes": dict(s.host_shapes),
                "host_keys": list(s.host_units),
                "prefix_digest": s.prefix_digest,
                "prefix_tokens": s.prefix_tokens,
            })

    store = src_node.manager.store
    extents = store.export_meta(inst.swap_file)
    reg = src_node.manager.prefix_registry
    prefix_records, prefix_extents = (
        reg.export_records(inst.instance_id) if reg is not None
        else ([], {}))
    gov = src_node.manager.governor
    bundle = _Bundle(
        instance_id=inst.instance_id,
        arch_key=arch_key,
        base_id=inst.base_id,
        shared_paths=frozenset(inst.shared_paths),
        extents=extents,
        reap_order=reap_order,
        stable=list(inst.recorder.stable),
        misses=dict(inst.recorder.misses),
        kv_sessions=kv_sessions,
        last_used=inst.last_used,
        created_at=inst.created_at,
        compiled=dict(inst.compiled),
        arrival=gov.arrivals.get(inst.instance_id),
        prefix_records=prefix_records,
        prefix_extents=prefix_extents,
    )
    bundle.wire_keys = (len(extents) + len(bundle.stable)
                        + len(bundle.misses) + len(prefix_extents)
                        + len(prefix_records)
                        + sum(sum(sd["page_counts"]) + len(sd["host_keys"])
                              for sd in kv_sessions))
    return bundle


def _rebuild_on_target(dst_node, bundle: _Bundle) -> ModelInstance:
    """Step 4: construct the hibernated husk on the target node."""
    mgr = dst_node.manager
    model_cfg, params = dst_node.factory(bundle.arch_key)
    shared_on = mgr.shared is not None and bundle.base_id is not None
    inst = ModelInstance(
        bundle.instance_id, model_cfg, params, pool=mgr.pool,
        spool_dir=mgr.cfg.spool_dir,
        shared_paths=bundle.shared_paths if shared_on else None,
        base_id=bundle.base_id if shared_on else None,
        store=mgr.store,
        metadata_bytes=mgr.cfg.husk_metadata_bytes,
        arch_key=bundle.arch_key)
    try:
        return _populate_target(mgr, inst, bundle)
    except BaseException:
        # abort mid-rebuild (store read error, disk full): the adopted
        # segment refs and half-built spool files must not leak on the
        # target — terminate releases the client (refcount GC) and
        # deletes the files
        inst.terminate()
        raise


def _populate_target(mgr, inst: ModelInstance,
                     bundle: _Bundle) -> ModelInstance:
    # adopt the shipped extent table (takes segment refs) BEFORE touching
    # the instance's own client — ModelInstance.__init__ created it empty
    mgr.store.adopt_extents(bundle.instance_id, bundle.extents)

    # the factory params are placeholder shapes: drop them so every unit
    # is Not-Present and the first wake restores the *migrated* bytes
    inst.sm.fire(Event.COLD_START)
    inst.sm.fire(Event.SIGSTOP)
    inst.drop_weights()
    inst.inflated = False
    inst.mmap_dropped = True          # wake re-maps via the registry

    inst.recorder.stable = {k: None for k in bundle.stable}
    inst.recorder.misses = dict(bundle.misses)
    inst.compiled.update(bundle.compiled)
    inst.last_used = bundle.last_used
    inst.created_at = bundle.created_at

    # rebuild the private REAP file from the local store, preserving the
    # first-touch order — the streamed wake pipeline depends on it
    if bundle.reap_order:
        data = inst.swap_file.read_units(bundle.reap_order)
        inst.reap_file.write_batch([(k, data[k]) for k in bundle.reap_order])

    # prefix registry: adopt the shipped pfx/pfxh extents (segment refs
    # under the target's __prefix__ client) and install the records as
    # spilled entries — pages revive lazily by digest, nothing prefills.
    # Entries the target's registry already holds cost metadata only.
    reg = mgr.prefix_registry
    if reg is not None and bundle.prefix_records:
        mgr.store.adopt_extents(PREFIX_OWNER, bundle.prefix_extents)
        reg.install_records(bundle.prefix_records)

    inst.kv = PagedKVCache(bundle.instance_id, inst.cfg, mgr.pool,
                           registry=reg)
    for sd in bundle.kv_sessions:
        s = KVSession(
            sd["session_id"],
            num_tokens=sd["num_tokens"],
            token_ids=list(sd["token_ids"]),
            pages=[[None] * c for c in sd["page_counts"]],
            host_units={k: None for k in sd["host_keys"]},
            host_shapes=dict(sd["host_shapes"]))
        digest = sd.get("prefix_digest")
        if reg is not None and digest is not None \
                and reg.get(digest) is not None:
            s.prefix_digest = digest
            s.prefix_tokens = int(sd.get("prefix_tokens", 0))
            reg.attach_session(digest, bundle.instance_id,
                               sd["session_id"])
        inst.kv.sessions[sd["session_id"]] = s
    if bundle.kv_sessions:
        inst.kv.dropped = True

    if bundle.arrival is not None:
        mgr.governor.arrivals[bundle.instance_id] = bundle.arrival
    return inst


def bundle_digests(bundle: _Bundle) -> frozenset:
    """Every CAS digest a bundle's tenant references (unit extents plus
    prefix-registry extents) — the content fingerprint replication and
    recovery operate on."""
    return frozenset(
        {m.digest for m in bundle.extents.values() if m.digest is not None}
        | {m.digest for m in bundle.prefix_extents.values()
           if m.digest is not None})


@dataclass
class ReplicaRecord:
    """One tenant's recovery replica as held by a non-home node: the
    full metadata bundle plus the digest set pinned in the holder's
    store.  ``receive_bundle(holder, rec.bundle)`` is the entire
    recovery path — the same code migration commits through."""
    bundle: _Bundle
    digests: frozenset
    source_node_id: str
    stored_bytes: int = 0

    @property
    def instance_id(self) -> str:
        return self.bundle.instance_id


def replicate_instance(src_node, dst_node, instance_id: str,
                       arch_key: str, *,
                       transport: Optional[Transport] = None
                       ) -> ReplicaRecord:
    """Copy a hibernated tenant's recovery substrate onto ``dst_node``
    without moving the tenant: ship the digests its bundle references
    (dedup-aware — shared base weights usually cost zero bytes), pin
    them in the holder's store so local GC cannot free them, and record
    the bundle.  The source stays the home; the replica only ever
    activates through :func:`receive_bundle` during crash recovery.

    Only HIBERNATE tenants replicate: their disk state is complete and
    frozen, so the bundle is a consistent snapshot by construction (a
    PARTIAL tenant's next deflate would invalidate it immediately)."""
    mgr = src_node.manager
    if transport is None:
        transport = LoopbackTransport(dst_node=dst_node)
    peer = StorePeer(mgr.store, transport=transport)

    lock = src_node.engine.instance_lock(instance_id)
    if not lock.acquire(blocking=False):
        raise MigrationError(f"{instance_id}: busy serving")
    try:
        inst = mgr.instances.get(instance_id)
        if inst is None:
            raise MigrationError(f"{instance_id}: not on node "
                                 f"{src_node.node_id}")
        if inst.state != S.HIBERNATE:
            raise MigrationError(
                f"{instance_id}: state {inst.state.value} — only "
                f"HIBERNATE tenants replicate")
        bundle = _export_bundle(src_node, inst, arch_key)
    finally:
        lock.release()

    digests = bundle_digests(bundle)
    stats = TransferStats()
    try:
        peer.ship(sorted(digests), stats)
        checkpoint("replicate.shipped", instance_id)
        stored = dst_node.store.pin_replicas(digests)
    except BaseException:
        peer.release_remote()
        raise
    peer.adopted()          # pinned: the pins are the references now
    rec = ReplicaRecord(bundle=bundle, digests=digests,
                        source_node_id=src_node.node_id,
                        stored_bytes=stored)
    dst_node.replicas[instance_id] = rec
    return rec


def receive_bundle(dst_node, bundle: _Bundle) -> ModelInstance:
    """Target-side bundle commit: rebuild the hibernated husk and admit
    it.  This is the single entry point both transports call — the
    loopback directly, the :class:`~repro.cluster.transport.StoreServer`
    as its ``BUNDLE`` handler — so socket and in-process migrations are
    byte-identical from here down."""
    rebuilt = _rebuild_on_target(dst_node, bundle)
    dst_node.manager.admit(rebuilt)
    return rebuilt


def migrate_instance(src_node, dst_node, instance_id: str, arch_key: str,
                     *, link_bw_bytes_s: float = 4 << 30,
                     transport: Optional[Transport] = None,
                     on_commit: Optional[Callable[[], None]] = None,
                     block: bool = True,
                     threaded: bool = True) -> MigrationHandle:
    """Migrate one idle tenant ``src_node -> dst_node``.

    The fence (state flip to MIGRATING) happens synchronously under the
    source serve lock — after this function returns the tenant is either
    MIGRATING (handle in flight) or the call raised.  The transfer runs
    on a thread (``threaded=False`` inlines it; ``block`` waits either
    way).  Raises :class:`MigrationError` if the tenant is busy serving
    or not on a migratable rung; a transfer failure raised with
    ``block=True`` carries the handle as ``exc.handle`` so callers can
    tell a refused fence from a failed target.

    ``transport`` defaults to in-process loopback against ``dst_node``;
    pass a connected :class:`~repro.cluster.transport.SocketTransport`
    to move the tenant to a remote :class:`StoreServer` instead (then
    ``dst_node`` may be ``None``).
    """
    mgr = src_node.manager
    if transport is None:
        if dst_node is None:
            raise MigrationError("migration needs a target node or a "
                                 "connected transport")
        transport = LoopbackTransport(dst_node=dst_node)
    target_id = transport.target_node_id or (
        dst_node.node_id if dst_node is not None else "remote")
    handle = MigrationHandle(instance_id, src_node.node_id, target_id)
    peer = StorePeer(mgr.store, transport=transport,
                     link_bw_bytes_s=link_bw_bytes_s)

    lock = src_node.engine.instance_lock(instance_id)
    if not lock.acquire(blocking=False):
        raise MigrationError(f"{instance_id}: busy serving")
    try:
        inst = mgr.instances.get(instance_id)
        if inst is None:
            raise MigrationError(f"{instance_id}: not on node "
                                 f"{src_node.node_id}")
        if inst.state not in MIGRATABLE_STATES:
            raise MigrationError(
                f"{instance_id}: state {inst.state.value} not migratable")
        mgr.hib.quiesce(inst)
        try:
            if inst.state == S.HIBERNATE:
                inst.sm.fire(Event.MIGRATE)   # disk state already complete
            else:
                # MMAP_CLEAN / PARTIAL: run the full-deflate body, landing
                # on MIGRATING instead of HIBERNATE — same flush, fenced
                mgr.hib.deflate(inst, event=Event.MIGRATE)
        except BaseException:
            # a MIGRATING tenant with no handle would block forever:
            # fall back to HIBERNATE before letting the error out
            if inst.state == S.MIGRATING:
                inst.sm.fire(Event.MIGRATE_ABORT)
            raise
        inst.migration = handle
    finally:
        lock.release()

    def _transfer() -> None:
        t0 = time.monotonic()
        st = handle.stats
        try:
            bundle = _export_bundle(src_node, inst, arch_key)
            checkpoint("migrate.exported", instance_id)
            st.meta_bytes = bundle.meta_bytes()
            st.full_snapshot_bytes = sum(
                m.nbytes for m in bundle.extents.values())
            # prefix segments ride the same dedup-aware transfer: a
            # target already holding the prompt's pages ships nothing
            digests = sorted(bundle_digests(bundle))
            peer.ship(digests, st)
            # fault point between import and adopt: a crash here leaves
            # refcount-zero imports on the target that the abort sweep
            # (or the server's connection teardown) must reclaim
            checkpoint("migrate.shipped", instance_id)
            # commit: target first (the tenant must exist somewhere at
            # every instant), then the source forgets + GCs
            peer.transport.send_bundle(bundle)
            peer.adopted()
            inst.sm.fire(Event.MIGRATE_DONE)
            handle.committed = True
            checkpoint("migrate.committed", instance_id)
        except BaseException as e:
            # abort: the source's disk state was never mutated
            # destructively — fall back to a plain hibernated tenant;
            # anything already imported on the target is swept
            try:
                peer.release_remote()
            finally:
                try:
                    if inst.state == S.MIGRATING:
                        inst.sm.fire(Event.MIGRATE_ABORT)
                finally:
                    inst.migration = None
                    st.seconds = time.monotonic() - t0
                    handle._finish(error=e)
            return
        # Past MIGRATE_DONE the commit is irrevocable — the target owns
        # the tenant.  Every source-side step below must be attempted
        # even if an earlier one fails (crash consistency: a commit
        # callback blowing up must not leave a DEAD husk holding store
        # refs and no forwarding address).
        commit_err: Optional[BaseException] = None
        try:
            mgr.detach(instance_id, target=target_id)
        except BaseException as e:
            commit_err = e
        if on_commit is not None:
            try:
                on_commit()
            except BaseException as e:
                commit_err = commit_err or e
        try:
            inst.terminate()       # store refs released (GC), REAP gone
        except BaseException as e:
            commit_err = commit_err or e
        st.seconds = time.monotonic() - t0
        handle._finish(error=commit_err)

    if threaded:
        t = threading.Thread(target=_transfer, daemon=True,
                             name=f"migrate-{instance_id}")
        t.start()
        if block:
            handle.wait()
    else:
        _transfer()
    if block and handle.error is not None:
        err = MigrationError(str(handle.error))
        err.handle = handle     # lets callers distinguish transfer
        raise err from handle.error  # failures from fence refusals
    return handle
