"""Deployment-wide resident KV prefix registry (cross-tenant COW adoption).

The CAS store dedups *swapped* pages; shared prompt prefixes (system
prompts, few-shot headers) still duplicate **resident** KV in every tenant
that serves them.  The registry closes that gap: a freshly prefilled
prompt is snapshotted under a salted token-hash, and any later session —
same tenant or another tenant on the node — whose prompt hashes to a
registered prefix *adopts* the existing pool pages by COW refcount instead
of recomputing prefill (HotSwap's live sharing of initialized state;
Pagurus's inter-container reuse).

Keys follow the store's keyed-BLAKE2b digest discipline: the hash is
salted with the deployment salt, so prefix digests never leak across
deployments and a tenant cannot probe another deployment's registry by
hash.  Within a deployment the trust stance is deliberate: adoption is
only sound because every instance of one ``arch_key`` is built by the
same deterministic factory (identical weights — the digest partitions on
the arch/base id precisely so tenants with different weights never share).

Lifecycle:

  * ``register`` — snapshot a prefilled session's pages under the digest;
    the registry takes its own pool references (owner ``"__prefix__"``)
    and immediately *write-throughs* the pages into the CAS store, so the
    prefix is content-addressed from birth;
  * ``adopt`` / ``reattach`` — COW-share the registry's pages into a
    session (never copied, never overwritten: the cache's write path
    breaks COW on refcount > 1, so adopted decode is bit-exact);
  * ``spill`` — last-sharer-down (every sharer deflated or gone): the
    registry frees its resident pages and drops its host units; both live
    on as CAS segments and ``revive`` rebuilds them by digest in one
    vectored read instead of prefill;
  * migration ships registry *records* (digests + token ids, no page
    payloads): the target rebuilds from its own registry or store.
"""
from __future__ import annotations

import hashlib
import os
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

#: pool/store owner the registry holds its references under
PREFIX_OWNER = "__prefix__"


@dataclass
class PrefixEntry:
    digest: bytes
    arch_key: str
    token_ids: Tuple[int, ...]
    num_tokens: int
    #: prefill's argmax token — adoption emits it without a forward pass
    first_token: int
    n_layers: int
    #: total page count (stable across spill/revive — sizes inventory math)
    n_pages: int
    #: pages[layer][i] = pool page id while resident; None while spilled
    pages: Optional[List[List[int]]]
    #: host units (SSM state, conv, cross-K/V) keyed (layer, kind) while
    #: resident; empty while spilled (the store holds them as ``pfxh`` keys)
    host_units: Dict[Tuple, np.ndarray] = field(default_factory=dict)
    #: bytes of the host units (stable across spill/revive, like n_pages)
    host_bytes: int = 0
    #: sessions currently mapping this prefix: (instance_id, session_id)
    sharers: Set[Tuple[str, str]] = field(default_factory=set)
    #: the subset of sharers whose prefix slots are currently resident
    resident_sharers: Set[Tuple[str, str]] = field(default_factory=set)
    adoptions: int = 0

    @property
    def resident(self) -> bool:
        return self.pages is not None

    def page_ids(self) -> List[int]:
        return [p for layer in (self.pages or []) for p in layer]


class PrefixRegistry:
    """Node-local half of the deployment-wide prefix registry.

    One per :class:`~repro.core.manager.InstanceManager`; the cluster
    router reads each node's :meth:`inventory` for the placement
    prefix-affinity term, and migration moves entries as records via
    :meth:`export_records` / :meth:`install_records`.
    """

    def __init__(self, pool, store=None, *, salt: Optional[bytes] = None,
                 min_tokens: int = 4):
        self.pool = pool
        self.store = store
        self.salt = (store.salt if store is not None
                     else (salt if salt is not None else os.urandom(16)))
        #: prompts shorter than this are not worth registry metadata
        self.min_tokens = min_tokens
        self._entries: Dict[bytes, PrefixEntry] = {}
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.registrations = 0
        self.spills = 0
        self.revives = 0

    # ------------------------------------------------------------- hashing
    def digest_of(self, arch_key: str, token_ids: Sequence[int]) -> bytes:
        """Salted token-hash: the store's keyed-BLAKE2b discipline applied
        to (arch, token ids) instead of page payloads."""
        buf = arch_key.encode() + b"\x00" + \
            np.asarray(list(token_ids), np.int64).tobytes()
        if self.store is not None:
            return self.store.keyed_digest(buf)
        return hashlib.blake2b(buf, digest_size=16, key=self.salt).digest()

    def get(self, digest: bytes) -> Optional[PrefixEntry]:
        return self._entries.get(digest)

    def lookup(self, arch_key: str,
               token_ids: Sequence[int]) -> Optional[PrefixEntry]:
        """Exact-match lookup (token ids are compared, not just the hash —
        a digest collision must never alias two prompts)."""
        with self._lock:
            e = self._entries.get(self.digest_of(arch_key, token_ids))
            if e is None or e.arch_key != arch_key or \
                    tuple(e.token_ids) != tuple(token_ids):
                self.misses += 1
                return None
            self.hits += 1
            return e

    # ------------------------------------------------------------- register
    def register(self, arch_key: str, kv, session_id: str,
                 first_token: int) -> Optional[PrefixEntry]:
        """Snapshot a freshly prefilled session as a shareable prefix.

        The registry COW-shares the session's pages under its own owner
        (so the prefix outlives the session) and write-throughs the page
        contents into the CAS store — the prefix is content-addressed from
        birth, which is what makes last-sharer-down spill and migration-
        by-digest free."""
        s = kv.sessions[session_id]
        if s.num_tokens < self.min_tokens:
            return None
        with self._lock:
            digest = self.digest_of(arch_key, s.token_ids)
            e = self._entries.get(digest)
            if e is not None:
                # concurrent private prefill of an already-known prompt:
                # attach as a sharer, don't re-snapshot
                self._attach(e, kv, s)
                return e
            pages: List[List[int]] = []
            host: Dict[Tuple, np.ndarray] = {}
            for layer in range(len(s.pages)):
                if any(p is None for p in s.pages[layer]):
                    return None            # partially deflated: not a donor
                pages.append(list(s.pages[layer]))
            for k, arr in s.host_units.items():
                if arr is None:
                    return None
                host[(k[2], k[3])] = arr.copy()
            self.pool.share([p for layer in pages for p in layer],
                            PREFIX_OWNER)
            e = PrefixEntry(
                digest=digest, arch_key=arch_key,
                token_ids=tuple(int(t) for t in s.token_ids),
                num_tokens=s.num_tokens,
                first_token=int(first_token), n_layers=len(pages),
                n_pages=sum(len(layer) for layer in pages),
                pages=pages, host_units=host,
                host_bytes=sum(a.nbytes for a in host.values()))
            self._entries[digest] = e
            self.registrations += 1
            self._write_through(e, kv)
            self._attach(e, kv, s)
            return e

    def _attach(self, e: PrefixEntry, kv, s) -> None:
        s.prefix_digest = e.digest
        s.prefix_tokens = e.num_tokens
        s.prefix_resident = True
        e.sharers.add((kv.instance_id, s.session_id))
        e.resident_sharers.add((kv.instance_id, s.session_id))

    def _write_through(self, e: PrefixEntry, kv) -> None:
        """Content-address the prefix into the CAS store now (not at
        deflate): spill/revive and cross-node rebuild work by digest."""
        if self.store is None or e.pages is None:
            return
        client = self.store.client(PREFIX_OWNER)
        items = []
        for layer, lpages in enumerate(e.pages):
            for pidx, pid in enumerate(lpages):
                key = ("pfx", e.digest, layer, pidx)
                if key in client:
                    continue
                items.append(
                    (key, kv.export_prefix_page(pid, pidx, e.num_tokens)))
        for (layer, kind), arr in e.host_units.items():
            key = ("pfxh", e.digest, layer, kind)
            if key not in client:
                items.append((key, arr))
        if items:
            client.write_units(items)

    # ------------------------------------------------------------- adopt
    def adopt(self, digest: bytes, kv, session_id: str):
        """Map a registered prefix into a brand-new session: COW page
        refs + host-unit copies.  Returns the new ``KVSession`` (the
        caller emits ``entry.first_token`` instead of running prefill)."""
        with self._lock:
            e = self._entries[digest]
            if e.pages is None:
                self._revive(e)
            s = kv.new_session(session_id)
            s.num_tokens = e.num_tokens
            s.token_ids = list(e.token_ids)
            s.pages = [list(layer) for layer in e.pages]
            self.pool.share(e.page_ids(), kv.instance_id)
            for (layer, kind), arr in e.host_units.items():
                key = ("kvh", session_id, layer, kind)
                s.host_units[key] = arr.copy()
                s.host_shapes[key] = arr.shape
            self._attach(e, kv, s)
            e.adoptions += 1
            return s

    def reattach(self, kv, session_id: str,
                 coords: Sequence[Tuple[int, int]]) -> int:
        """Re-map registry pages into a woken sharer's Not-Present prefix
        slots (the wake-side analogue of adopt).  ``coords`` is the
        (layer, page_idx) set the caller verified is prefix-backed — COW-
        broken slots live in the swap tier and must never come back from
        here.  Returns bytes made resident."""
        s = kv.sessions[session_id]
        if s.prefix_digest is None:
            return 0
        with self._lock:
            e = self._entries.get(s.prefix_digest)
            if e is None:
                raise KeyError(("prefix", s.prefix_digest))
            if e.pages is None:
                self._revive(e)
            shared: List[int] = []
            for layer, pidx in coords:
                if s.pages[layer][pidx] is not None:
                    continue
                pid = e.pages[layer][pidx]
                s.pages[layer][pidx] = pid
                shared.append(pid)
            # adopted host units ride the normal swap tier (private
            # copies), but a spilled copy may be missing there on a
            # migrated husk — restore from the registry template
            for k, arr in s.host_units.items():
                tk = (k[2], k[3])
                if arr is None and tk in e.host_units:
                    s.host_units[k] = e.host_units[tk].copy()
                    shared.append(-1)    # marker: something was restored
            if shared:
                self.pool.share([p for p in shared if p >= 0],
                                kv.instance_id)
                s.prefix_resident = True
                e.resident_sharers.add((kv.instance_id, session_id))
            return sum(self.pool.page_bytes for p in shared if p >= 0)

    # ------------------------------------------------------------- sharers
    def attach_session(self, digest: bytes, instance_id: str,
                       session_id: str) -> bool:
        """Re-register a sharer for an already-installed entry (migration
        target: the shipped session logically maps the prefix and will
        reattach its pages by digest on first wake)."""
        with self._lock:
            e = self._entries.get(digest)
            if e is None:
                return False
            e.sharers.add((instance_id, session_id))
            return True

    def note_detach(self, digest: bytes, instance_id: str,
                    session_id: str) -> None:
        """A sharer deflated: its prefix slots went Not-Present (the
        session still *logically* maps the prefix and will reattach on
        wake)."""
        with self._lock:
            e = self._entries.get(digest)
            if e is not None:
                e.resident_sharers.discard((instance_id, session_id))

    def release_sharer(self, digest: bytes, instance_id: str,
                       session_id: str) -> None:
        """A sharer is gone for good (session closed)."""
        with self._lock:
            e = self._entries.get(digest)
            if e is None:
                return
            e.sharers.discard((instance_id, session_id))
            e.resident_sharers.discard((instance_id, session_id))
            self._maybe_spill(e)

    def forget_owner(self, instance_id: str) -> None:
        """Instance evicted/migrated off: drop every sharer it held."""
        with self._lock:
            for e in list(self._entries.values()):
                e.sharers = {t for t in e.sharers if t[0] != instance_id}
                e.resident_sharers = {t for t in e.resident_sharers
                                      if t[0] != instance_id}
                self._maybe_spill(e)

    def _maybe_spill(self, e: PrefixEntry) -> None:
        """Last-sharer-down: with no live sharers the resident copy is
        pure overhead — drop to the CAS tier (or forget entirely when
        there is no store to revive from)."""
        if e.sharers:
            return
        if self.store is not None:
            self._spill(e)
        else:
            if e.pages is not None:
                self.pool.free(e.page_ids(), PREFIX_OWNER)
            self._entries.pop(e.digest, None)

    # ------------------------------------------------------------- spill
    def _spill(self, e: PrefixEntry) -> int:
        if e.pages is None or self.store is None:
            return 0
        pages = e.page_ids()
        self.pool.free(pages, PREFIX_OWNER)
        e.pages = None
        e.host_units = {}          # written through at register
        self.spills += 1
        return len(pages) * self.pool.page_bytes

    def spill(self, digest: bytes) -> int:
        """Governor reclaim: free the resident copy of a prefix no
        resident sharer maps (deflated sharers reattach-by-digest on
        wake).  Returns bytes freed."""
        with self._lock:
            e = self._entries.get(digest)
            if e is None or e.resident_sharers:
                return 0
            return self._spill(e)

    def spill_candidates(self) -> List[Tuple[int, bytes]]:
        """(freeable_bytes, digest) for resident entries with no resident
        sharer — what the governor may reclaim without touching any
        tenant's mapped memory."""
        with self._lock:
            return [(len(e.page_ids()) * self.pool.page_bytes, d)
                    for d, e in self._entries.items()
                    if e.pages is not None and not e.resident_sharers
                    and self.store is not None]

    def spilled_digests(self, arch_key: Optional[str] = None
                        ) -> List[bytes]:
        """Digests of spilled (non-resident, revivable) entries,
        optionally filtered to one deployment arch — what the forecast
        daemon revives ahead of a predicted burst."""
        with self._lock:
            return [d for d, e in self._entries.items()
                    if e.pages is None
                    and (arch_key is None or e.arch_key == arch_key)]

    def revive(self, digest: bytes) -> bool:
        """Rebuild a spilled entry's resident pages from the CAS store by
        digest (pre-inflate path: the next ``adopt``/``reattach`` finds
        the pages already resident instead of paying the revive on the
        serve path).  Returns True if a revive happened."""
        with self._lock:
            e = self._entries.get(digest)
            if e is None or e.pages is not None or self.store is None:
                return False
            self._revive(e)
            return True

    def _revive(self, e: PrefixEntry) -> None:
        """Rebuild the resident copy (pages and host units) from the CAS
        store by digest — the whole point of write-through: no prefill,
        one vectored read."""
        if self.store is None:
            raise KeyError(("prefix", e.digest, "spilled without a store"))
        client = self.store.client(PREFIX_OWNER)
        keys = sorted((k for k in client.extents
                       if k[0] == "pfx" and k[1] == e.digest),
                      key=lambda k: (k[2], k[3]))
        hkeys = [k for k in client.extents
                 if k[0] == "pfxh" and k[1] == e.digest]
        data = client.read_units(keys + hkeys)
        pages: List[List[int]] = [[] for _ in range(e.n_layers)]
        pids, rows = [], []
        for k in keys:
            pid = self.pool.alloc(1, PREFIX_OWNER)[0]
            pages[k[2]].append(pid)
            pids.append(pid)
            rows.append(np.asarray(data[k]).reshape(-1))
        if pids:
            self.pool.scatter(pids, np.stack(rows))
        e.pages = pages
        e.host_units = {(k[2], k[3]): data[k] for k in hkeys}
        self.revives += 1

    # ------------------------------------------------------------- cluster
    def entry_bytes(self, e: PrefixEntry) -> int:
        """Logical bytes sharing this prefix saves a would-be prefiller."""
        return e.n_pages * self.pool.page_bytes + e.host_bytes

    def inventory(self) -> Dict[bytes, int]:
        """digest -> shareable bytes: what this node advertises to the
        router's prefix-affinity placement term.  Spilled entries count —
        revive-by-digest still beats recomputing prefill."""
        with self._lock:
            return {d: self.entry_bytes(e) for d, e in self._entries.items()}

    def digests(self) -> frozenset:
        with self._lock:
            return frozenset(self._entries)

    def digests_for_instance(self, instance_id: str) -> List[bytes]:
        with self._lock:
            return [d for d, e in self._entries.items()
                    if any(t[0] == instance_id for t in e.sharers)]

    def resident_bytes(self) -> int:
        """Physical bytes the registry itself pins (PSS share of its
        refcounted pages) — charged once to the node, never per-sharer."""
        return int(self.pool.pss_bytes(PREFIX_OWNER))

    def held_bytes(self) -> int:
        """Bytes the registry keeps in memory: its pinned page share
        (:meth:`resident_bytes`) plus the host units of resident entries."""
        with self._lock:
            host = sum(e.host_bytes for e in self._entries.values()
                       if e.host_units)
        return self.resident_bytes() + host

    # ------------------------------------------------------------- wire
    def export_records(self, instance_id: str):
        """Migration source: (records, store_metas) for every prefix the
        instance shares.  Records are wire-safe dicts of pure metadata;
        the page payloads travel as CAS segments like everything else
        (dedup-aware: a digest the target already holds ships nothing)."""
        records, metas = [], {}
        with self._lock:
            for d in self.digests_for_instance(instance_id):
                e = self._entries[d]
                records.append({
                    "digest": e.digest, "arch": e.arch_key,
                    "token_ids": tuple(e.token_ids),
                    "num_tokens": e.num_tokens,
                    "first_token": e.first_token,
                    "n_layers": e.n_layers,
                    "n_pages": e.n_pages,
                })
        if self.store is not None and records:
            client = self.store.client(PREFIX_OWNER)
            wanted = {r["digest"] for r in records}
            metas = {k: m for k, m in self.store.export_meta(client).items()
                     if k[1] in wanted}
        return records, metas

    def install_records(self, records) -> int:
        """Migration target: install shipped prefix entries as spilled
        (pages and host units revive lazily by digest from the just-
        adopted CAS extents).  Entries already known locally are kept
        as-is — that is the cross-node win: nothing re-transfers, nothing
        re-prefills.  Returns entries newly installed."""
        n = 0
        with self._lock:
            for r in records:
                if r["digest"] in self._entries:
                    continue
                host_bytes = 0
                if self.store is not None:
                    client = self.store.client(PREFIX_OWNER)
                    host_bytes = sum(
                        m.nbytes for k, m in client.extents.items()
                        if k[0] == "pfxh" and k[1] == r["digest"])
                self._entries[r["digest"]] = PrefixEntry(
                    digest=r["digest"], arch_key=r["arch"],
                    token_ids=tuple(r["token_ids"]),
                    num_tokens=int(r["num_tokens"]),
                    first_token=int(r["first_token"]),
                    n_layers=int(r["n_layers"]),
                    n_pages=int(r["n_pages"]),
                    pages=None, host_bytes=host_bytes)
                n += 1
        return n

    # ------------------------------------------------------------- stats
    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "resident_entries": sum(1 for e in self._entries.values()
                                        if e.pages is not None),
                "hits": self.hits, "misses": self.misses,
                "registrations": self.registrations,
                "adoptions": sum(e.adoptions
                                 for e in self._entries.values()),
                "spills": self.spills, "revives": self.revives,
                "resident_bytes": self.resident_bytes(),
            }
