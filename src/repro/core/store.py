"""Content-addressed swap store: the Swapping Manager's de-dup table (§3.4),
extended across sandboxes.

The per-sandbox :class:`~repro.core.swap.SwapFile` stores every deflated
unit verbatim, so disk (and page-cache) footprint scales linearly with
tenant count even when tenants run the same model.  The paper's Swapping
Manager keeps a de-dup table so identical swapped-out units are stored
once; REAP-style snapshot work shows most restored pages are identical
across snapshots of one function, and the same holds across tenants that
share a base model.  The :class:`SwapStore` realises that disk tier:

  * units are hashed on deflate (salted BLAKE2b — the salt is generated
    per deployment, so content hashes never leak across deployments and a
    tenant cannot probe another deployment's store by hash);
  * zero/constant payloads are elided to metadata (no disk bytes at all —
    KV pages' unused tails and zero-init params cost nothing);
  * duplicate payloads across sessions *and tenants* are stored once in a
    refcounted segment file; terminating an instance decrefs its segments
    and frees the extents of any that hit refcount zero (GC), so one
    tenant's eviction never touches bytes another tenant still references;
  * cold payloads are transparently compressed: a unit that keeps missing
    the REAP working set keeps coming back through the page-fault tier,
    and its miss count selects a zlib level (:class:`StorePolicy`) —
    payloads only ever *sink* to higher compression, never decompress back
    up a tier.

The inflate path keeps the vectored ``preadv`` batching of the plain swap
files: requested units are dedup'd by digest, segment extents are sorted
and adjacent extents merged into runs (``repro.core.swap.read_extents``),
so a wake storm's fault set is still a handful of sequential disk passes.

Tenants that opt out of dedup (``ManagerConfig.dedup_store=False``) keep
the PR-1 private per-sandbox ``SwapFile`` — the store is interface-
compatible (:class:`StoreClient` duck-types ``SwapFile``), so every layer
above (``HibernationManager``, ``ModelInstance``, ``PagedKVCache``) is
agnostic to which tier backs it.
"""
from __future__ import annotations

import hashlib
import os
import threading
import time
import zlib
from dataclasses import dataclass
from typing import (Callable, Dict, Hashable, List, Optional, Sequence,
                    Tuple)

import numpy as np

from repro.core.swap import WriteReceipt, pwritev_full, read_extents


@dataclass
class StorePolicy:
    """Adaptive compression tiers.

    ``tiers`` maps a REAP-working-set miss count threshold to a zlib
    level; the highest threshold <= the unit's miss count wins.  Units
    below ``min_size`` are never compressed (header overhead dominates).
    A segment's level only increases (cold payloads sink); if compression
    does not save at least ``1 - min_ratio`` of the payload it stays raw
    (marginal wins are not worth paying inflate bandwidth on every wake —
    random float mantissas "compress" ~10-15% via exponent-byte structure)
    and the attempted level is remembered so hot loops don't re-deflate
    incompressible data.
    """
    tiers: Tuple[Tuple[int, int], ...] = ((0, 0), (2, 1), (4, 6), (8, 9))
    min_size: int = 512
    min_ratio: float = 0.8

    def level_for(self, miss_count: int, nbytes: int) -> int:
        if nbytes < self.min_size:
            return 0
        lvl = 0
        for thresh, level in self.tiers:
            if miss_count >= thresh:
                lvl = level
        return lvl


@dataclass
class _Segment:
    offset: int
    stored_nbytes: int           # on-disk bytes (post compression)
    raw_nbytes: int
    level: int                   # zlib level the payload is stored at (0=raw)
    refs: int = 0
    tried_level: int = 0         # highest level ever attempted (anti-thrash)
    #: set when a peer transfer installed this segment at refcount zero
    #: and no adopt_extents has claimed it yet — a transfer that dies
    #: between import and adopt leaves these, and the orphan sweep
    #: (:meth:`SwapStore.sweep_orphans`) reclaims them
    imported_at: Optional[float] = None
    #: CRC32 of the *stored* payload, computed when the bytes were last
    #: known-good (put/import/repair); every read path verifies it, so a
    #: flipped bit on disk surfaces as :class:`CorruptSegmentError`
    #: instead of silently feeding bad bytes to every sharer
    crc: int = 0
    #: replica pins (cluster anti-entropy): a pinned segment survives GC
    #: even at refcount zero — it is another node's recovery substrate
    pins: int = 0
    #: quarantined: a read/scrub found the on-disk bytes disagree with
    #: ``crc``.  The extent is kept (never handed back to the allocator)
    #: until a repair overwrites it or GC frees it; readers refuse it
    corrupt: bool = False


class CorruptSegmentError(RuntimeError):
    """On-disk payload failed its checksum and could not be repaired."""

    def __init__(self, msg: str, digest: bytes = b""):
        super().__init__(msg)
        self.digest = digest


@dataclass
class UnitMeta:
    """Per-(owner, key) record: either a constant fill or a digest into
    the shared segment table."""
    digest: Optional[bytes]      # None -> constant-elided
    fill: int                    # byte value when elided
    nbytes: int
    dtype: str
    shape: Tuple[int, ...]


class SwapStore:
    """One per deployment (``InstanceManager``): the shared, refcounted,
    content-addressed segment file all tenants' page-fault tiers ride."""

    def __init__(self, path: str, *, salt: Optional[bytes] = None,
                 policy: Optional[StorePolicy] = None):
        self.path = path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self.fd: Optional[int] = os.open(
            path, os.O_RDWR | os.O_CREAT | os.O_TRUNC, 0o600)
        #: per-deployment hash salt (security: content hashes are not
        #: comparable across deployments)
        self.salt = os.urandom(16) if salt is None else salt
        self.policy = policy or StorePolicy()
        self._segments: Dict[bytes, _Segment] = {}
        self._free: List[Tuple[int, int]] = []       # coalesced (off, nbytes)
        self._append_at = 0
        #: reads run outside the lock; extents freed while any read is in
        #: flight are quarantined here so a reader's snapshot can never be
        #: overwritten by a concurrent allocation
        self._active_reads = 0
        self._quarantine: List[Tuple[int, int]] = []
        self._clients: Dict[str, "StoreClient"] = {}
        self._lock = threading.RLock()
        #: cluster hook: ``repair_source(digest) -> (level, raw_nbytes,
        #: payload) | None`` fetches a known-good copy from a replica
        #: peer; the router wires it.  Repairs verify the content digest
        #: before installing, so a corrupt replica cannot "repair" us.
        self.repair_source: Optional[
            Callable[[bytes], Optional[Tuple[int, int, bytes]]]] = None
        self._scrubber: Optional["StoreScrubber"] = None
        self._scrub_cursor: bytes = b""
        # counters (store-wide; clients keep their own read/write counters)
        self.puts = 0
        self.dedup_hits = 0
        self.elisions = 0
        self.sink_events = 0                          # recompressions
        self.bytes_written = 0                        # on-disk bytes written
        self.writes = 0                               # write syscalls
        self.reads = 0                                # read syscalls
        self.corruptions = 0                          # checksum failures seen
        self.repairs = 0                              # segments restored
        self.import_rejects = 0                       # wire frames that failed
        #                                             # content verification

    # ------------------------------------------------------------- clients
    def client(self, owner: str) -> "StoreClient":
        with self._lock:
            c = self._clients.get(owner)
            if c is None:
                c = self._clients[owner] = StoreClient(self, owner)
            return c

    # ------------------------------------------------------------- hashing
    def keyed_digest(self, buf: bytes) -> bytes:
        """The store's salted content hash (keyed BLAKE2b-16).  Public so
        sibling subsystems that content-address by the same deployment
        salt — the prefix registry's token-hash keys — share one digest
        discipline instead of re-deriving it."""
        return hashlib.blake2b(buf, digest_size=16, key=self.salt).digest()

    def _digest(self, buf: bytes) -> bytes:
        return self.keyed_digest(buf)

    # ------------------------------------------------------------- extents
    def _alloc(self, n: int) -> int:
        """First-fit from the GC free list, else append."""
        for i, (off, sz) in enumerate(self._free):
            if sz >= n:
                if sz == n:
                    self._free.pop(i)
                else:
                    self._free[i] = (off + n, sz - n)
                return off
        off = self._append_at
        self._append_at += n
        return off

    def _release_extent(self, off: int, n: int) -> None:
        """Return an extent to the free list, coalescing neighbours.
        While reads are in flight the extent is quarantined instead: an
        unlocked reader may still be preadv-ing those bytes."""
        if n <= 0:
            return
        if self._active_reads:
            self._quarantine.append((off, n))
            return
        self._free.append((off, n))
        self._free.sort()
        merged: List[Tuple[int, int]] = []
        for o, s in self._free:
            if merged and merged[-1][0] + merged[-1][1] == o:
                merged[-1] = (merged[-1][0], merged[-1][1] + s)
            else:
                merged.append((o, s))
        # trailing free space shrinks the append frontier (and the file)
        if merged and merged[-1][0] + merged[-1][1] == self._append_at:
            o, _ = merged.pop()
            self._append_at = o
            os.ftruncate(self.fd, o)
        self._free = merged

    # ------------------------------------------------------------- encode
    def _encode(self, buf: bytes, level: int) -> Tuple[bytes, int]:
        if level > 0:
            comp = zlib.compress(buf, level)
            if len(comp) <= self.policy.min_ratio * len(buf):
                return comp, level
        return buf, 0

    def _install_payload(self, seg: _Segment, payload: bytes,
                         level: int) -> None:
        """Write a known-good payload into a fresh extent and point the
        segment at it (repair / sink commit).  The old extent is released
        (quarantine-aware) — a crash between pwrite and the metadata flip
        just leaves the new extent unreferenced; the old bytes are intact
        because nothing ever overwrites a live extent in place."""
        old_off, old_n = seg.offset, seg.stored_nbytes
        seg.offset = self._alloc(len(payload))
        seg.stored_nbytes = len(payload)
        seg.level = level
        seg.crc = zlib.crc32(payload)
        seg.corrupt = False
        pwritev_full(self.fd, [payload], seg.offset)
        self.bytes_written += len(payload)
        self.writes += 1
        self._release_extent(old_off, old_n)

    def _repair_locked(self, digest: bytes, seg: _Segment) -> bool:
        """Restore a quarantined segment from the replica peer hook.
        The fetched payload is verified end-to-end (content digest over
        the *decompressed* bytes), so a lying or equally-corrupt peer is
        rejected rather than installed."""
        src = self.repair_source
        if src is None:
            return False
        got = src(digest)
        if got is None:
            return False
        level, raw_nbytes, payload = got
        try:
            raw = zlib.decompress(payload) if level else payload
        except zlib.error:
            return False
        if self._digest(raw) != digest or len(raw) != raw_nbytes:
            return False
        self._install_payload(seg, payload, level)
        self.repairs += 1
        return True

    def _mark_corrupt(self, digest: bytes, seg: _Segment) -> None:
        if not seg.corrupt:
            seg.corrupt = True
            self.corruptions += 1

    def _restore_from_raw(self, seg: _Segment, raw: bytes) -> None:
        """Repair a quarantined segment from raw bytes already in hand
        (a dedup-hit writer is its own replica)."""
        payload, level = self._encode(raw, seg.level or seg.tried_level)
        self._install_payload(seg, payload, level)
        self.repairs += 1

    def _payload(self, seg: _Segment, digest: bytes = b"") -> bytes:
        blob = os.pread(self.fd, seg.stored_nbytes, seg.offset)
        self.reads += 1
        if zlib.crc32(blob) != seg.crc:
            self._mark_corrupt(digest, seg)
            if not self._repair_locked(digest, seg):
                raise CorruptSegmentError(
                    f"segment {digest.hex()} failed checksum "
                    f"({seg.stored_nbytes}B @ {seg.offset}); no replica "
                    f"could repair it", digest)
            blob = os.pread(self.fd, seg.stored_nbytes, seg.offset)
            self.reads += 1
        return zlib.decompress(blob) if seg.level else blob

    def _read_repaired(self, digest: bytes) -> bytes:
        """Slow path for :meth:`read`: quarantine + replica repair +
        re-read, under the lock."""
        with self._lock:
            seg = self._segments[digest]
            self._mark_corrupt(digest, seg)
            if not self._repair_locked(digest, seg):
                raise CorruptSegmentError(
                    f"segment {digest.hex()} failed checksum on read; "
                    f"no replica could repair it", digest)
            return self._payload(seg, digest)

    def _maybe_sink(self, seg: _Segment, want_level: int,
                    digest: bytes = b"") -> None:
        """Re-store a segment at a higher zlib level (cold payloads sink)."""
        if want_level <= max(seg.level, seg.tried_level) or \
                seg.raw_nbytes < self.policy.min_size:
            return
        raw = self._payload(seg, digest)
        seg.tried_level = want_level
        comp, level = self._encode(raw, want_level)
        if level == 0 or len(comp) >= seg.stored_nbytes:
            return                          # incompressible: stays put
        self._install_payload(seg, comp, level)
        self.sink_events += 1

    # ------------------------------------------------------------- put/get
    def put(self, client: "StoreClient", key: Hashable, arr: np.ndarray,
            miss_count: int = 0) -> WriteReceipt:
        arr = np.ascontiguousarray(arr)
        buf = arr.tobytes()
        r = WriteReceipt(logical_bytes=len(buf))
        with self._lock:
            self.puts += 1
            # constant-fill elision: zero pages (and any single-byte fill)
            # become pure metadata
            if len(buf) == 0 or buf.count(buf[:1]) == len(buf):
                self._drop_meta(client.extents.pop(key, None))
                client.extents[key] = UnitMeta(
                    None, buf[0] if buf else 0, len(buf),
                    str(arr.dtype), arr.shape)
                self.elisions += 1
                r.elided_bytes = len(buf)
                return r
            digest = self._digest(buf)
            old = client.extents.get(key)
            if old is not None and old.digest == digest:
                # rewrite-identical (every re-deflate of unchanged weights):
                # no disk IO, no refcount change
                self.dedup_hits += 1
                r.dedup_bytes = len(buf)
                seg = self._segments[digest]
                if seg.corrupt:
                    # the writer holds the raw bytes: cheapest repair there is
                    self._restore_from_raw(seg, buf)
                self._maybe_sink(seg,
                                 self.policy.level_for(miss_count, len(buf)),
                                 digest)
                client.extents[key] = UnitMeta(
                    digest, 0, len(buf), str(arr.dtype), arr.shape)
                return r
            self._drop_meta(client.extents.pop(key, None))
            seg = self._segments.get(digest)
            level = self.policy.level_for(miss_count, len(buf))
            if seg is None:
                payload, stored_level = self._encode(buf, level)
                seg = _Segment(self._alloc(len(payload)), len(payload),
                               len(buf), stored_level, refs=0,
                               tried_level=level, crc=zlib.crc32(payload))
                pwritev_full(self.fd, [payload], seg.offset)
                self.bytes_written += len(payload)
                self.writes += 1
                self._segments[digest] = seg
                r.stored_bytes = len(payload)
            else:
                self.dedup_hits += 1
                r.dedup_bytes = len(buf)
                if seg.corrupt:
                    self._restore_from_raw(seg, buf)
                self._maybe_sink(seg, level, digest)
            seg.refs += 1
            seg.imported_at = None      # a local writer now references it
            client.extents[key] = UnitMeta(
                digest, 0, len(buf), str(arr.dtype), arr.shape)
            return r

    def read(self, client: "StoreClient", keys: Sequence[Hashable]
             ) -> Dict[Hashable, np.ndarray]:
        """Vectored batch read: keys dedup by digest, segment extents are
        sorted and adjacent extents merged — one ``preadv`` per run.

        The lock is held only to snapshot the extent plan: the disk IO and
        zlib inflate run unlocked so concurrent tenants' wakes overlap
        (a wake storm must not serialize on the deployment-wide store).
        The snapshot stays valid because (a) the caller holds a ref on
        every segment it reads, so GC cannot free them, and (b) extents
        freed by *other* tenants' GC or by sinking are quarantined until
        in-flight reads drain (`_release_extent`)."""
        with self._lock:
            metas = [(k, client.extents[k]) for k in keys]
            by_digest: Dict[bytes, List[Tuple[Hashable, UnitMeta]]] = {}
            constants: List[Tuple[Hashable, UnitMeta]] = []
            for key, m in metas:
                if m.digest is None:
                    constants.append((key, m))
                else:
                    by_digest.setdefault(m.digest, []).append((key, m))
            plan = sorted(((d, self._segments[d].offset,
                            self._segments[d].stored_nbytes,
                            self._segments[d].level,
                            self._segments[d].crc) for d in by_digest),
                          key=lambda p: p[1])
            self._active_reads += 1
        out: Dict[Hashable, np.ndarray] = {}
        calls = nbytes = 0
        try:
            for key, m in constants:       # materialized outside the lock
                out[key] = np.frombuffer(
                    bytes([m.fill]) * m.nbytes if m.nbytes else b"",
                    m.dtype).reshape(m.shape).copy()
            bufs, calls = read_extents(self.fd,
                                       [(off, n) for _, off, n, _, _ in plan])
            for (d, _, _, level, crc), buf in zip(plan, bufs):
                # integrity gate: checksum verified before any sharer sees
                # the bytes; a mismatch quarantines the extent and repairs
                # from a replica peer inline (the wake then proceeds on
                # the repaired bytes — no caller ever observes bad data)
                if zlib.crc32(buf) != crc:
                    raw = self._read_repaired(d)
                else:
                    try:
                        raw = zlib.decompress(bytes(buf)) if level else buf
                    except zlib.error:
                        raw = self._read_repaired(d)
                for key, m in by_digest[d]:
                    out[key] = np.frombuffer(
                        raw, m.dtype, count=m.nbytes
                        // np.dtype(m.dtype).itemsize
                    ).reshape(m.shape).copy()
                    nbytes += m.nbytes
        finally:
            with self._lock:
                self._active_reads -= 1
                if not self._active_reads and self._quarantine:
                    pending, self._quarantine = self._quarantine, []
                    for off, n in pending:
                        self._release_extent(off, n)
                self.reads += calls
                client.reads += calls
                client.bytes_read += nbytes
        return out

    def read_iter(self, client: "StoreClient", keys: Sequence[Hashable],
                  chunk_bytes: int = 1 << 20):
        """Streaming variant of :meth:`read`: yields ``{key: array}`` dicts
        of ~``chunk_bytes`` (logical) each.  Every chunk snapshots its own
        extent plan under the lock and runs its IO + zlib inflate unlocked,
        so a long stream never starves concurrent tenants' wakes — the
        chunk granularity is what the wake pipeline double-buffers."""
        batch: List[Hashable] = []
        pending = 0
        for k in keys:
            batch.append(k)
            with self._lock:
                pending += client.extents[k].nbytes
            if pending >= chunk_bytes:
                yield self.read(client, batch)
                batch, pending = [], 0
        if batch:
            yield self.read(client, batch)

    # ------------------------------------------------------------- cluster
    def digests(self) -> frozenset:
        """Digests of every live segment — the node's content inventory
        the cluster router scores digest-overlap affinity against."""
        with self._lock:
            return frozenset(self._segments)

    def missing_digests(self, digests) -> List[bytes]:
        """Subset of ``digests`` this store does NOT hold — what a peer
        transfer must actually ship (dedup-aware migration: everything
        else is already on this node's disk).  Quarantined segments count
        as missing: asking the peer to re-ship one IS the repair."""
        with self._lock:
            return [d for d in digests
                    if d not in self._segments or self._segments[d].corrupt]

    def stored_bytes_of(self, digests) -> int:
        """On-disk (post-compression) bytes of the given segments."""
        with self._lock:
            return sum(self._segments[d].stored_nbytes for d in digests
                       if d in self._segments)

    def export_segments(self, digests
                        ) -> List[Tuple[bytes, int, int, bytes]]:
        """Read segments out as ``(digest, level, raw_nbytes, payload)``
        wire tuples.  Payloads ship at their stored compression level —
        a cold zlib-tier segment crosses the link compressed and lands on
        the target at the same tier."""
        out: List[Tuple[bytes, int, int, bytes]] = []
        with self._lock:          # sinking relocates extents: stay locked
            for d in digests:
                seg = self._segments[d]
                blob = os.pread(self.fd, seg.stored_nbytes, seg.offset)
                self.reads += 1
                if zlib.crc32(blob) != seg.crc:
                    # never ship bad bytes: quarantine, repair, re-read —
                    # or fail the export rather than poison the peer
                    self._mark_corrupt(d, seg)
                    if not self._repair_locked(d, seg):
                        raise CorruptSegmentError(
                            f"segment {d.hex()} failed checksum on "
                            f"export; no replica could repair it", d)
                    blob = os.pread(self.fd, seg.stored_nbytes, seg.offset)
                    self.reads += 1
                out.append((d, seg.level, seg.raw_nbytes, blob))
        return out

    def export_segments_iter(self, digests, chunk_bytes: int = 4 << 20):
        """Chunked :meth:`export_segments`: yields wire-tuple batches of
        ~``chunk_bytes`` stored payload each, so a multi-GB transfer
        streams through bounded memory and the transport can apply
        flow control per chunk instead of per migration."""
        batch: List[bytes] = []
        pending = 0
        for d in digests:
            with self._lock:
                seg = self._segments.get(d)
                size = seg.stored_nbytes if seg is not None else 0
            batch.append(d)
            pending += size
            if pending >= chunk_bytes:
                yield self.export_segments(batch)
                batch, pending = [], 0
        if batch:
            yield self.export_segments(batch)

    def import_segments(self, items: Sequence[Tuple[bytes, int, int, bytes]]
                        ) -> List[bytes]:
        """Install wire segments from a peer at refcount zero; the
        follow-up :meth:`adopt_extents` call takes the references.  The
        digest is the *cluster-wide* content address, so both stores must
        share a salt (the router seeds every node from one deployment
        salt).  Newly installed segments are stamped ``imported_at`` and
        stay orphans until adopted; returns their digests so the transfer
        channel can sweep them if the migration aborts mid-bundle.

        Every frame is verified end-to-end before install: the payload is
        inflated and its salted content hash must equal the digest it
        claims.  A frame corrupted or truncated on the wire is rejected
        (counted in ``import_rejects``) — the transfer then aborts at
        adopt time with the digest missing, instead of this store serving
        poisoned bytes to every future sharer.  A verified frame whose
        digest is already present *but quarantined* repairs it in place
        (re-shipping IS the anti-entropy repair; refs and pins are
        preserved)."""
        new: List[bytes] = []
        now = time.monotonic()
        with self._lock:
            for digest, level, raw_nbytes, payload in items:
                try:
                    raw = zlib.decompress(payload) if level else payload
                except zlib.error:
                    self.import_rejects += 1
                    continue
                if self._digest(raw) != digest or len(raw) != raw_nbytes:
                    self.import_rejects += 1
                    continue
                seg = self._segments.get(digest)
                if seg is not None:
                    if seg.corrupt:
                        self._install_payload(seg, payload, level)
                        self.repairs += 1
                    else:
                        self.dedup_hits += 1
                    continue
                seg = _Segment(self._alloc(len(payload)), len(payload),
                               raw_nbytes, level, refs=0, tried_level=level,
                               imported_at=now, crc=zlib.crc32(payload))
                pwritev_full(self.fd, [payload], seg.offset)
                self.bytes_written += len(payload)
                self.writes += 1
                new.append(digest)
                self._segments[digest] = seg
        return new

    def export_meta(self, client: "StoreClient") -> Dict[Hashable, "UnitMeta"]:
        """Snapshot one owner's extent table (the REAP-metadata half of a
        migration: keys, digests, dtypes, shapes — no payload bytes)."""
        with self._lock:
            return dict(client.extents)

    def adopt_extents(self, owner: str,
                      metas: Dict[Hashable, "UnitMeta"]) -> "StoreClient":
        """Rebuild a migrated tenant's client: its extent table is
        installed verbatim and a reference is taken on every segment it
        names.  Raises ``KeyError`` if a digest was never shipped —
        adoption must follow :meth:`import_segments`, never precede it."""
        with self._lock:
            missing = [m.digest for m in metas.values()
                       if m.digest is not None
                       and m.digest not in self._segments]
            if missing:
                raise KeyError(
                    f"adopt_extents({owner}): {len(missing)} digests "
                    f"absent — transfer incomplete")
            c = self.client(owner)
            for key, meta in metas.items():
                self._drop_meta(c.extents.pop(key, None))
                if meta.digest is not None:
                    seg = self._segments[meta.digest]
                    seg.refs += 1
                    seg.imported_at = None      # adopted: no longer orphan
                c.extents[key] = meta
            return c

    def pin_replicas(self, digests) -> int:
        """Pin segments as another node's recovery replica: a pinned
        segment survives GC even when every local tenant releases it —
        until the router unpins (holder rotation, tenant termination, or
        the replica being promoted by adoption).  ALL digests must be
        present (a partial pin is a lying replica); raises ``KeyError``
        otherwise.  Returns stored bytes pinned."""
        nbytes = 0
        with self._lock:
            missing = [d for d in digests if d not in self._segments]
            if missing:
                raise KeyError(
                    f"pin_replicas: {len(missing)} digests absent — "
                    f"replica incomplete")
            for d in digests:
                seg = self._segments[d]
                seg.pins += 1
                seg.imported_at = None      # pinned: not an orphan
                nbytes += seg.stored_nbytes
        return nbytes

    def unpin_replicas(self, digests) -> int:
        """Drop replica pins; segments left at refcount zero with no
        remaining pins are freed.  Returns on-disk bytes reclaimed."""
        freed = 0
        with self._lock:
            for d in digests:
                seg = self._segments.get(d)
                if seg is None:
                    continue
                seg.pins -= 1
                if seg.refs <= 0 and seg.pins <= 0:
                    del self._segments[d]
                    self._release_extent(seg.offset, seg.stored_nbytes)
                    freed += seg.stored_nbytes
        return freed

    def orphan_digests(self, max_age_s: float = 0.0) -> List[bytes]:
        """Imported-but-never-adopted segments at least ``max_age_s``
        old — what a dead transfer left behind."""
        cutoff = time.monotonic() - max_age_s
        with self._lock:
            return [d for d, s in self._segments.items()
                    if s.refs <= 0 and s.pins <= 0
                    and s.imported_at is not None
                    and s.imported_at <= cutoff]

    def sweep_orphans(self, digests=None, max_age_s: float = 0.0) -> int:
        """Free orphaned imports (refcount zero, ``imported_at`` set).

        A transfer that dies between :meth:`import_segments` and
        :meth:`adopt_extents` leaves payload bytes no client references;
        the aborting peer sweeps the digests it shipped, and the server's
        connection teardown (or a periodic pass with ``max_age_s``)
        catches peers that vanished without aborting.  Segments that were
        adopted, or that a local writer has since referenced, are never
        touched.  Returns on-disk bytes reclaimed."""
        cutoff = time.monotonic() - max_age_s
        freed = 0
        with self._lock:
            if digests is None:
                digests = [d for d, s in self._segments.items()
                           if s.imported_at is not None]
            for d in list(digests):
                seg = self._segments.get(d)
                if (seg is None or seg.refs > 0 or seg.pins > 0
                        or seg.imported_at is None
                        or seg.imported_at > cutoff):
                    continue
                del self._segments[d]
                self._release_extent(seg.offset, seg.stored_nbytes)
                freed += seg.stored_nbytes
        return freed

    # ------------------------------------------------------------- GC
    def _drop_meta(self, meta: Optional[UnitMeta]) -> None:
        if meta is None or meta.digest is None:
            return
        seg = self._segments.get(meta.digest)
        if seg is None:
            return
        seg.refs -= 1
        if seg.refs <= 0 and seg.pins <= 0:
            del self._segments[meta.digest]
            self._release_extent(seg.offset, seg.stored_nbytes)

    def release(self, client: "StoreClient") -> int:
        """Instance termination: decref every segment the owner references;
        segments at refcount zero are freed (their extents return to the
        allocator).  Returns on-disk bytes reclaimed."""
        with self._lock:
            before = self.live_bytes
            for meta in client.extents.values():
                self._drop_meta(meta)
            client.extents.clear()
            self._clients.pop(client.owner, None)
            return before - self.live_bytes

    # ------------------------------------------------------------- scrub
    def scrub(self, max_bytes: int = 64 << 20, repair: bool = True
              ) -> Dict[str, int]:
        """One bounded integrity pass: re-checksum up to ``max_bytes`` of
        stored payload, quarantine mismatches, and (optionally) repair
        them from the replica peer hook.  The cursor is resumable — the
        next call continues where this one stopped, wrapping at the end —
        so a background daemon covers the whole store in bounded slices
        without ever stalling the serve path for long."""
        scanned = segments = found = repaired = 0
        with self._lock:
            order = sorted(self._segments)
            start = 0
            for i, d in enumerate(order):
                if d > self._scrub_cursor:
                    start = i
                    break
            order = order[start:] + order[:start]
            for d in order:
                if scanned >= max_bytes:
                    break
                seg = self._segments.get(d)
                if seg is None:
                    continue
                blob = os.pread(self.fd, seg.stored_nbytes, seg.offset)
                self.reads += 1
                scanned += seg.stored_nbytes
                segments += 1
                self._scrub_cursor = d
                if zlib.crc32(blob) == seg.crc and not seg.corrupt:
                    continue
                self._mark_corrupt(d, seg)
                found += 1
                if repair and self._repair_locked(d, seg):
                    repaired += 1
        return {"scanned_bytes": scanned, "scanned_segments": segments,
                "corrupt_found": found, "repaired": repaired}

    def start_scrubber(self, interval_s: float = 30.0,
                       bytes_per_round: int = 64 << 20) -> "StoreScrubber":
        """Start (or return) the background scrub daemon."""
        with self._lock:
            if self._scrubber is None:
                self._scrubber = StoreScrubber(self, interval_s,
                                               bytes_per_round)
                self._scrubber.start()
            return self._scrubber

    def stop_scrubber(self) -> None:
        s = self._scrubber
        if s is not None:
            self._scrubber = None
            s.stop()

    def close(self) -> None:
        self.stop_scrubber()
        with self._lock:
            if self.fd is not None:
                os.close(self.fd)
                self.fd = None
            if os.path.exists(self.path):
                os.unlink(self.path)
            self._segments.clear()
            self._clients.clear()

    # ------------------------------------------------------------- stats
    @property
    def live_bytes(self) -> int:
        """On-disk bytes referenced by live segments."""
        return sum(s.stored_nbytes for s in self._segments.values())

    @property
    def file_bytes(self) -> int:
        return self._append_at

    def stats(self) -> Dict[str, float]:
        """Resident-vs-unique-vs-compressed accounting (density analysis)."""
        with self._lock:
            segs = list(self._segments.values())
            logical = elided = 0
            for c in self._clients.values():
                for m in c.extents.values():
                    logical += m.nbytes
                    if m.digest is None:
                        elided += m.nbytes
            unique = sum(s.raw_nbytes for s in segs)
            stored = sum(s.stored_nbytes for s in segs)
            return {
                "logical_bytes": logical,    # what verbatim files would hold
                "unique_bytes": unique,      # after dedup + elision
                "stored_bytes": stored,      # after compression (on disk)
                "elided_bytes": elided,
                "segments": len(segs),
                "puts": self.puts,
                "dedup_hits": self.dedup_hits,
                "elisions": self.elisions,
                "sink_events": self.sink_events,
                "free_bytes": sum(n for _, n in self._free),
                "corruptions": self.corruptions,
                "repairs": self.repairs,
                "import_rejects": self.import_rejects,
                "pinned_segments": sum(1 for s in segs if s.pins > 0),
                "pinned_bytes": sum(s.stored_nbytes for s in segs
                                    if s.pins > 0),
                "quarantined": sum(1 for s in segs if s.corrupt),
            }


class StoreScrubber:
    """Background integrity daemon: periodically runs one bounded
    :meth:`SwapStore.scrub` slice.  Stopped by :meth:`SwapStore.close`
    (or explicitly); ``wake()`` forces an immediate pass (tests)."""

    def __init__(self, store: SwapStore, interval_s: float,
                 bytes_per_round: int):
        self.store = store
        self.interval_s = interval_s
        self.bytes_per_round = bytes_per_round
        self.rounds = 0
        self.last: Dict[str, int] = {}
        self._stop = threading.Event()
        self._kick = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name=f"scrub:{store.path}", daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._kick.set()
        self._thread.join(timeout=5.0)

    def wake(self) -> None:
        self._kick.set()

    def _run(self) -> None:
        while not self._stop.is_set():
            self._kick.wait(self.interval_s)
            self._kick.clear()
            if self._stop.is_set():
                return
            with self.store._lock:
                if self.store.fd is None:
                    return
                self.last = self.store.scrub(self.bytes_per_round)
                self.rounds += 1


class StoreClient:
    """One tenant's view of the shared store — duck-typed to
    :class:`~repro.core.swap.SwapFile` so ``ModelInstance`` /
    ``HibernationManager`` / ``PagedKVCache`` work unchanged on either.

    ``hotness(key) -> int`` (wired to the instance's
    :meth:`~repro.core.reap.ReapRecorder.miss_count`) feeds the adaptive
    compression policy at write time.
    """

    def __init__(self, store: SwapStore, owner: str):
        self.store = store
        self.owner = owner
        self.path = store.path
        self.extents: Dict[Hashable, UnitMeta] = {}
        self.hotness: Optional[Callable[[Hashable], int]] = None
        self.bytes_written = 0               # logical (raw) bytes written
        self.bytes_read = 0
        self.reads = 0                       # read syscalls this owner caused
        self.writes = 0                      # unit writes (puts)
        self.last_receipt = WriteReceipt()

    def __contains__(self, key: Hashable) -> bool:
        return key in self.extents

    def _miss(self, key: Hashable) -> int:
        return self.hotness(key) if self.hotness is not None else 0

    # ------------------------------------------------------------- writes
    def write_unit(self, key: Hashable, arr: np.ndarray) -> None:
        r = self.store.put(self, key, arr, self._miss(key))
        self.bytes_written += r.logical_bytes
        self.writes += 1
        self.last_receipt += r

    def write_units(self, items: Sequence[Tuple[Hashable, np.ndarray]]
                    ) -> WriteReceipt:
        r = WriteReceipt()
        for k, a in items:
            r += self.store.put(self, k, a, self._miss(k))
            self.writes += 1
        self.bytes_written += r.logical_bytes
        self.last_receipt = r
        return r

    # ------------------------------------------------------------- reads
    def read_unit(self, key: Hashable) -> np.ndarray:
        return self.store.read(self, [key])[key]

    def read_units(self, keys: Sequence[Hashable]
                   ) -> Dict[Hashable, np.ndarray]:
        return self.store.read(self, keys)

    def read_units_iter(self, keys: Sequence[Hashable],
                        chunk_bytes: int = 1 << 20):
        """Chunk-granular streaming read (duck-types
        :meth:`~repro.core.swap._FileBase.read_units_iter`)."""
        return self.store.read_iter(self, keys, chunk_bytes)

    # ------------------------------------------------------------- admin
    def delete(self) -> None:
        """Sandbox termination (§3.4): release this owner's refs; shared
        segments survive for the tenants still referencing them."""
        self.store.release(self)

    @property
    def logical_bytes(self) -> int:
        return sum(m.nbytes for m in self.extents.values())

    @property
    def file_bytes(self) -> int:
        """Fair-share on-disk footprint (PSS analogue for disk): each
        segment's stored bytes split across its referencing units."""
        with self.store._lock:
            tot = 0.0
            for m in self.extents.values():
                if m.digest is None:
                    continue
                seg = self.store._segments.get(m.digest)
                if seg is not None and seg.refs:
                    tot += seg.stored_nbytes / seg.refs
            return int(tot)
