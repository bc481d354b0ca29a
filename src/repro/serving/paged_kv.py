"""Paged KV/SSM cache over the shared page pool (the paper's guest memory).

The cache is the *anonymous application memory* of a model instance: KV
entries live in fixed-size pool pages managed by the Bitmap Page Allocator;
SSM/conv/cross-attention states are host-cache units riding the same swap
machinery.  Logical *keys* are stable across hibernation cycles (physical
page ids are not — pages are freed on deflate and re-allocated on inflate,
exactly like madvise'd memory being recommitted by the host on fault):

  ``("kv",  session_id, layer, page_idx)``  one pool page of KV tokens
  ``("kvh", session_id, layer, kind)``      a host unit (ssm state, conv,
                                            cross_k/v, MLA latent uses "kv")

Sessions model multi-turn serverless invocations.  A session is freed as
soon as it is closed: its pages go back to the shared pool and its host
units are dropped, so a tenant kept warm holds only its open sessions.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Hashable, List, Optional, Sequence, Set, Tuple

import numpy as np


@dataclass
class KVSession:
    session_id: str
    num_tokens: int = 0
    token_ids: List[int] = field(default_factory=list)
    #: pages[layer][i] = physical page id, or None while swapped out
    pages: List[List[Optional[int]]] = field(default_factory=list)
    #: host units: key -> array (None while swapped out)
    host_units: Dict[Tuple, Optional[np.ndarray]] = field(default_factory=dict)
    host_shapes: Dict[Tuple, Tuple] = field(default_factory=dict)
    #: registry digest when the leading tokens map a shared prefix
    #: (:mod:`repro.core.prefix`); stable across hibernation cycles
    prefix_digest: Optional[bytes] = None
    #: tokens the shared prefix covers (<= num_tokens)
    prefix_tokens: int = 0
    #: True while the prefix slots map the registry's pages (cleared on
    #: deflate, restored by reattach)
    prefix_resident: bool = False


class PagedKVCache:
    """Per-instance paged cache.  ``token_elems`` is the per-layer flattened
    KV element count per token (2*Hkv*D for GQA, r+rd for MLA)."""

    def __init__(self, instance_id: str, cfg, pool, registry=None):
        self.instance_id = instance_id
        self.cfg = cfg
        self.pool = pool
        #: deployment prefix registry (``repro.core.prefix``) — None
        #: disables cross-tenant prefix adoption for this instance
        self.registry = registry
        if cfg.attention == "mla":
            self.token_elems = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
        elif cfg.attention == "none":
            self.token_elems = 0
        else:
            self.token_elems = 2 * cfg.num_kv_heads * cfg.head_dim
        # tokens per pool page (pool page size is global, shared by tenants)
        self.page_tokens = max(1, pool.page_elems // max(self.token_elems, 1)) \
            if self.token_elems else 0
        self.sessions: Dict[str, KVSession] = {}
        self.dropped = False                 # True while deflated

    # ------------------------------------------------------------- sessions
    def new_session(self, session_id: str) -> KVSession:
        if session_id in self.sessions:
            raise KeyError(f"session {session_id} exists")
        s = KVSession(session_id,
                      pages=[[] for _ in range(self.cfg.num_layers)])
        self.sessions[session_id] = s
        return s

    def close_session(self, session_id: str) -> None:
        """Free a session its last request is done with: its pages return
        to the pool, its host units are dropped, and the prefix registry
        loses a sharer (the last one down spills the prefix).  An install
        that arrives for it later finds no session and is skipped."""
        s = self.sessions.pop(session_id)
        self.pool.free([p for layer in s.pages for p in layer
                        if p is not None], self.instance_id)
        if self.registry is not None and s.prefix_digest is not None:
            self.registry.release_sharer(s.prefix_digest, self.instance_id,
                                         session_id)

    def fork_session(self, src_id: str, dst_id: str) -> KVSession:
        """COW prefix sharing: the new session references the same physical
        pages; the allocator refcounts them (paper's clone/COW analogue)."""
        src = self.sessions[src_id]
        dst = self.new_session(dst_id)
        dst.num_tokens = src.num_tokens
        dst.token_ids = list(src.token_ids)
        dst.pages = [list(layer) for layer in src.pages]
        shared = [p for layer in src.pages for p in layer if p is not None]
        self.pool.share(shared, self.instance_id)
        for k, v in src.host_units.items():
            nk = (k[0], dst_id) + k[2:]
            dst.host_units[nk] = None if v is None else v.copy()
            dst.host_shapes[nk] = src.host_shapes[k]
        if self.registry is not None and src.prefix_digest is not None:
            # the fork maps the same registry pages: it is a sharer too
            dst.prefix_digest = src.prefix_digest
            dst.prefix_tokens = src.prefix_tokens
            dst.prefix_resident = src.prefix_resident
            e = self.registry.get(src.prefix_digest)
            if e is not None:
                e.sharers.add((self.instance_id, dst_id))
                if dst.prefix_resident:
                    e.resident_sharers.add((self.instance_id, dst_id))
        return dst

    # ------------------------------------------------------------- writes
    def _n_pages(self, tokens: int) -> int:
        return -(-tokens // self.page_tokens) if self.token_elems else 0

    def write_tokens(self, session_id: str, layer: int,
                     data: np.ndarray, start_tok: int) -> List[Tuple]:
        """Write ``data`` ((T, token_elems)) at token offset ``start_tok``
        for one layer.  Allocates pages as needed.  Returns touched keys."""
        s = self.sessions[session_id]
        T = data.shape[0]
        data = np.asarray(data, self.pool.dtype).reshape(T, self.token_elems)
        touched = []
        t = 0
        while t < T:
            tok = start_tok + t
            pidx, off = divmod(tok, self.page_tokens)
            while len(s.pages[layer]) <= pidx:
                s.pages[layer].append(self.pool.alloc(1, self.instance_id)[0])
            pid = s.pages[layer][pidx]
            if pid is None:                      # swapped-out page: fault first
                raise KeyError(("kv", session_id, layer, pidx))
            if self.pool.refcount(pid) > 1:
                # COW write fault: the page is shared (prefix registry or
                # a forked sibling) — never overwrite, break to a private
                # copy first so every other sharer stays bit-exact
                pid = self.pool.break_cow(pid, self.instance_id)
                s.pages[layer][pidx] = pid
            n = min(self.page_tokens - off, T - t)
            phys = self.pool._phys([pid])[0]
            usable = self.page_tokens * self.token_elems
            page_view = self.pool.data[phys][:usable].reshape(
                self.page_tokens, self.token_elems)
            page_view[off:off + n] = data[t:t + n]
            touched.append(("kv", session_id, layer, pidx))
            t += n
        return touched

    def read_tokens(self, session_id: str, layer: int, n_tokens: int
                    ) -> np.ndarray:
        """Gather the first ``n_tokens`` of a layer into a dense array."""
        s = self.sessions[session_id]
        out = np.zeros((n_tokens, self.token_elems), self.pool.dtype)
        t = 0
        while t < n_tokens:
            pidx, off = divmod(t, self.page_tokens)
            pid = s.pages[layer][pidx]
            if pid is None:
                raise KeyError(("kv", session_id, layer, pidx))
            n = min(self.page_tokens - off, n_tokens - t)
            phys = self.pool._phys([pid])[0]
            usable = self.page_tokens * self.token_elems
            page = self.pool.data[phys][:usable].reshape(
                self.page_tokens, self.token_elems)
            out[t:t + n] = page[off:off + n]
            t += n
        return out

    def set_host_unit(self, session_id: str, layer, kind: str,
                      arr: np.ndarray) -> Tuple:
        s = self.sessions[session_id]
        key = ("kvh", session_id, layer, kind)
        s.host_units[key] = np.asarray(arr)
        s.host_shapes[key] = arr.shape
        return key

    def get_host_unit(self, session_id: str, layer, kind: str) -> np.ndarray:
        s = self.sessions[session_id]
        key = ("kvh", session_id, layer, kind)
        arr = s.host_units[key]
        if arr is None:
            raise KeyError(key)
        return arr

    def keys_for(self, session_id: str, window_tokens: Optional[int] = None
                 ) -> List[Tuple]:
        """All logical keys a request on this session will touch (pages in
        the attention window + every host unit) — the fault/record set."""
        s = self.sessions[session_id]
        keys: List[Tuple] = list(s.host_units)
        if self.token_elems:
            first_tok = 0
            if window_tokens is not None:
                first_tok = max(0, s.num_tokens - window_tokens)
            p0 = first_tok // self.page_tokens
            for layer in range(self.cfg.num_layers):
                for pidx in range(p0, len(s.pages[layer])):
                    keys.append(("kv", session_id, layer, pidx))
        return keys

    def nonresident_keys(self, keys: Sequence[Tuple]) -> List[Tuple]:
        out = []
        for k in keys:
            s = self.sessions.get(k[1])
            if s is None:
                continue
            if k[0] == "kv":
                if s.pages[k[2]][k[3]] is None:
                    out.append(k)
            elif s.host_units.get(k) is None:
                out.append(k)
        return out

    # ------------------------------------------------------------- prefix
    def _prefix_entry_pages(self, s: KVSession):
        """The registry entry's resident page table for this session's
        prefix, or None (no registry / no prefix / entry spilled)."""
        if self.registry is None or s.prefix_digest is None:
            return None
        e = self.registry.get(s.prefix_digest)
        return None if e is None else e.pages

    def is_prefix_slot(self, s: KVSession, layer: int, pidx: int) -> bool:
        """True when the slot still maps the registry's own page (COW-
        broken slots hold a private copy and are the tenant's to swap)."""
        ep = self._prefix_entry_pages(s)
        return (ep is not None and layer < len(ep)
                and pidx < len(ep[layer])
                and s.pages[layer][pidx] == ep[layer][pidx])

    def _prefix_page_count(self, s: KVSession) -> int:
        """Pages per layer the session's prefix spans."""
        return self._n_pages(s.prefix_tokens) if s.prefix_digest else 0

    def export_prefix_page(self, pid: int, pidx: int,
                           num_tokens: int) -> np.ndarray:
        """Registry write-through export: one page with the same zero-tail
        contract as :meth:`_export_page`, bounded by the *prefix* token
        count (not a session's) so identical prefixes hash identically."""
        phys = self.pool._phys([pid])[0]
        data = self.pool.data[phys].copy()
        used = min(max(num_tokens - pidx * self.page_tokens, 0),
                   self.page_tokens) * self.token_elems
        data[used:] = 0
        return data

    def prefix_missing_keys(self) -> List[Tuple]:
        """Not-Present page slots inside each session's prefix range —
        what a wake must either restore from swap (COW-broken copies) or
        reattach from the registry."""
        keys: List[Tuple] = []
        for sid, s in self.sessions.items():
            np_pages = self._prefix_page_count(s)
            if not np_pages:
                continue
            for layer in range(len(s.pages)):
                for pidx in range(min(np_pages, len(s.pages[layer]))):
                    if s.pages[layer][pidx] is None:
                        keys.append(("kv", sid, layer, pidx))
        return keys

    def ensure_prefix_slot(self, session_id: str, layer: int,
                           pidx: int) -> Optional[int]:
        """Last-chance remap for the compute path: re-share a Not-Present
        prefix slot from the registry, but ONLY when the slot provably
        never COW-broke (fully-covered page, or nothing was ever written
        past the prefix) — a broken slot's bytes live in the swap tier and
        must fault in from there.  Returns the page id or None."""
        s = self.sessions[session_id]
        if self.registry is None or s.prefix_digest is None or \
                pidx >= self._prefix_page_count(s):
            return None
        fully_covered = (pidx + 1) * self.page_tokens <= s.prefix_tokens
        if not (fully_covered or s.num_tokens == s.prefix_tokens):
            return None
        self.registry.reattach(self, session_id, [(layer, pidx)])
        return s.pages[layer][pidx]

    # ------------------------------------------------------------- hibernate
    def _export_page(self, s: KVSession, pid: int, pidx: int) -> np.ndarray:
        """One page's swap-out copy.  The region beyond its written
        tokens is allocator garbage; it is zeroed so identical-content
        pages hash identically across sessions and tenants — this is
        what lets KV pages dedup (and half-empty tail pages
        constant-elide) in the content-addressed SwapStore."""
        phys = self.pool._phys([pid])[0]
        data = self.pool.data[phys].copy()
        used = min(max(s.num_tokens - pidx * self.page_tokens, 0),
                   self.page_tokens) * self.token_elems
        data[used:] = 0
        return data

    def export_items(self, working_set: frozenset
                     ) -> Tuple[List[Tuple[Tuple, np.ndarray]],
                                List[Tuple[Tuple, np.ndarray]]]:
        """Partition resident cache units into (reap, swap) item lists
        (pages exported via :meth:`_export_page`'s zero-tail contract)."""
        reap, swap = [], []
        for sid, s in self.sessions.items():
            for layer in range(len(s.pages)):
                for pidx, pid in enumerate(s.pages[layer]):
                    if pid is None:
                        continue
                    if self.is_prefix_slot(s, layer, pidx):
                        # registry-backed page: already content-addressed
                        # at registration; the wake reattaches by digest —
                        # exporting it would double-swap another tenant's
                        # (and the registry's) live mapping
                        continue
                    key = ("kv", sid, layer, pidx)
                    data = self._export_page(s, pid, pidx)
                    (reap if key in working_set else swap).append((key, data))
            for key, arr in s.host_units.items():
                if arr is None:
                    continue
                (reap if key in working_set else swap).append((key, arr))
        return reap, swap

    def resident_keys(self) -> List[Tuple]:
        """Every logical key currently backed by memory (pool pages with a
        physical id + host units holding an array) — the partial-deflate
        victim candidate set."""
        keys: List[Tuple] = []
        for sid, s in self.sessions.items():
            for layer in range(len(s.pages)):
                for pidx, pid in enumerate(s.pages[layer]):
                    if pid is not None and \
                            not self.is_prefix_slot(s, layer, pidx):
                        keys.append(("kv", sid, layer, pidx))
            keys += [k for k, a in s.host_units.items() if a is not None]
        return keys

    def key_nbytes(self, key: Tuple) -> int:
        """Bytes one logical key pins in memory."""
        if key[0] == "kv":
            return self.pool.page_elems * np.dtype(self.pool.dtype).itemsize
        s = self.sessions.get(key[1])
        if s is None:
            return 0
        arr = s.host_units.get(key)
        if arr is not None:
            return arr.nbytes
        shape = s.host_shapes.get(key)
        return int(np.prod(shape)) * 4 if shape else 0

    def export_keys(self, keys: Sequence[Tuple]
                    ) -> List[Tuple[Tuple, np.ndarray]]:
        """Materialize specific resident keys as (key, data) items via
        :meth:`_export_page` (zero-tail dedup contract) — the
        partial-deflate victim export."""
        items: List[Tuple[Tuple, np.ndarray]] = []
        for key in keys:
            s = self.sessions.get(key[1])
            if s is None:
                continue
            if key[0] == "kv":
                _, sid, layer, pidx = key
                if layer >= len(s.pages) or pidx >= len(s.pages[layer]):
                    continue
                pid = s.pages[layer][pidx]
                if pid is None or self.is_prefix_slot(s, layer, pidx):
                    continue
                items.append((key, self._export_page(s, pid, pidx)))
            elif key[0] == "kvh":
                arr = s.host_units.get(key)
                if arr is not None:
                    items.append((key, arr))
        return items

    def nonresident_logical_keys(self) -> List[Tuple]:
        """Inverse of :meth:`resident_keys`: logical keys whose backing
        is swapped out (Not-Present page-table slots, host units holding
        None) — what a rung-aware wake must consider restoring."""
        keys: List[Tuple] = []
        for sid, s in self.sessions.items():
            for layer in range(len(s.pages)):
                for pidx, pid in enumerate(s.pages[layer]):
                    if pid is None:
                        keys.append(("kv", sid, layer, pidx))
            keys += [k for k, a in s.host_units.items() if a is None]
        return keys

    def drop_keys(self, keys: Sequence[Tuple]) -> int:
        """Free the physical backing of specific keys (partial deflate's
        madvise): pool pages return to the allocator, page-table slots go
        Not-Present, host units drop their arrays.  Returns pages freed."""
        n = 0
        for key in keys:
            s = self.sessions.get(key[1])
            if s is None:
                continue
            if key[0] == "kv":
                _, sid, layer, pidx = key
                if layer >= len(s.pages) or pidx >= len(s.pages[layer]):
                    continue
                pid = s.pages[layer][pidx]
                if pid is not None:
                    was_prefix = self.is_prefix_slot(s, layer, pidx)
                    self.pool.free([pid], self.instance_id)
                    s.pages[layer][pidx] = None
                    n += 1
                    if was_prefix and s.prefix_resident:
                        # partially detached counts as detached: the
                        # registry must not treat this sharer as pinning
                        # the resident copy anymore
                        s.prefix_resident = False
                        self.registry.note_detach(
                            s.prefix_digest, self.instance_id, sid)
            elif key[0] == "kvh" and s.host_units.get(key) is not None:
                s.host_units[key] = None
        return n

    def drop_pages(self) -> int:
        """Deflation step 3 tail: free every physical page (madvise) but keep
        the logical page tables — the 'Not-Present' page-table entries."""
        n = 0
        for sid, s in self.sessions.items():
            for layer in range(len(s.pages)):
                for pidx, pid in enumerate(s.pages[layer]):
                    if pid is not None:
                        self.pool.free([pid], self.instance_id)
                        s.pages[layer][pidx] = None
                        n += 1
            for key in s.host_units:
                s.host_units[key] = None
            if self.registry is not None and s.prefix_digest is not None \
                    and s.prefix_resident:
                # the session still *logically* maps the prefix (it will
                # reattach by digest on wake); only the resident pin drops
                s.prefix_resident = False
                self.registry.note_detach(s.prefix_digest,
                                          self.instance_id, sid)
        self.dropped = True
        return n

    def apply_prefetch(self, data: Dict[Hashable, np.ndarray]) -> int:
        """Install a batch of swapped-in units (REAP batch read)."""
        return self.install_batch(
            [(k, a) for k, a in data.items() if k[0] in ("kv", "kvh")],
            mark=True)

    def install_batch(self, items: Sequence[Tuple[Tuple, np.ndarray]],
                      mark: bool = True) -> int:
        """Install a batch of swapped-in units in ONE pool scatter.

        Pool pages are collected (allocating physical pages for keys whose
        slots are still Not-Present) and written with a single
        :meth:`PagePool.scatter` — the ``page_copy.scatter_pages`` path,
        one scatter per wake-pipeline chunk instead of a per-page
        ``_set`` copy.  Host units install individually.  Keys of closed
        sessions are skipped (a streamed wake may outlive them),
        and so are keys that are ALREADY resident: concurrent installers
        (streamer / demand / lookahead) are idempotent, and a stale
        background install must never clobber a page the engine has since
        faulted in and written fresh tokens to.  Returns bytes installed."""
        pages: List[int] = []
        rows: List[np.ndarray] = []
        n = 0
        for key, arr in items:
            s = self.sessions.get(key[1])
            if s is None:
                continue
            if key[0] == "kv":
                _, _sid, layer, pidx = key
                if layer >= len(s.pages) or pidx >= len(s.pages[layer]):
                    continue
                if s.pages[layer][pidx] is not None:
                    continue                   # resident: never overwrite
                s.pages[layer][pidx] = \
                    self.pool.alloc(1, self.instance_id)[0]
                pages.append(s.pages[layer][pidx])
                rows.append(np.asarray(arr).reshape(-1))
                n += arr.nbytes
            elif key[0] == "kvh" and key in s.host_shapes \
                    and s.host_units.get(key) is None:
                s.host_units[key] = np.asarray(arr).reshape(
                    s.host_shapes[key])
                n += arr.nbytes
        if pages:
            self.pool.scatter(pages, np.stack(rows))
        if mark and n:
            self.dropped = False
        return n

    def fault_in(self, keys: Sequence[Tuple], swap_file, reap_file) -> int:
        """Fault path: the key set is coalesced into one vectored batch
        read per file (extent-sorted, adjacent extents merged).

        Keys in no swap tier but inside a session's shared-prefix range
        remap from the registry instead (COW reattach — the prefix was
        never exported, its bytes live as registry pages or CAS segments).
        The swap tiers are consulted FIRST: a COW-broken prefix page's
        private copy is in the swap file, and restoring the pristine
        registry page there would clobber the session's divergent bytes.
        """
        swap_keys, reap_keys = [], []
        prefix_coords: Dict[str, List[Tuple[int, int]]] = {}
        for key in keys:
            if key in swap_file:
                swap_keys.append(key)
            elif key in reap_file.extents:
                reap_keys.append(key)
            elif key[0] == "kv" and self.registry is not None and \
                    (s := self.sessions.get(key[1])) is not None and \
                    s.prefix_digest is not None and \
                    key[3] < self._prefix_page_count(s):
                prefix_coords.setdefault(key[1], []).append(
                    (key[2], key[3]))
            else:
                raise KeyError(f"kv unit {key} not in any swap file")
        n = 0
        for f, ks in ((swap_file, swap_keys), (reap_file, reap_keys)):
            if not ks:
                continue
            # one vectored read + one pool scatter per file
            n += self.install_batch(list(f.read_units(ks).items()),
                                    mark=False)
        for sid, coords in prefix_coords.items():
            n += self.registry.reattach(self, sid, coords)
        return n

    # ------------------------------------------------------------- accounting
    def resident_page_count(self) -> int:
        return sum(1 for s in self.sessions.values()
                   for layer in s.pages for p in layer if p is not None)

    def host_bytes(self) -> int:
        return sum(a.nbytes for s in self.sessions.values()
                   for a in s.host_units.values() if a is not None)
