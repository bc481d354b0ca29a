"""Shared device page pool — the "host physical memory" of the TPU analogue.

One pool per device holds fixed-size pages (KV-cache pages for every tenant
on that device), managed by the paper's :class:`BitmapPageAllocator`.  Pages
are refcounted, so prefix-shared KV pages (the COW / process-clone analogue)
are held once and accounted proportionally (PSS semantics, matching the
paper's `pmap` methodology).

On this CPU container the backing store is host RAM (numpy); on a real TPU
deployment it is a single preallocated HBM buffer per device and the
``gather``/``scatter`` paths are the ``page_copy`` Pallas kernel.
"""
from __future__ import annotations

import threading
from typing import Dict, Iterable, List, Optional, Sequence, Set

import numpy as np

from repro.core.bitmap_alloc import (PAGES_PER_BLOCK, USABLE_PER_BLOCK,
                                     BitmapPageAllocator)


class PagePool:
    def __init__(self, page_elems: int, dtype=np.float32,
                 capacity_pages: int = 1 << 16):
        self.page_elems = page_elems
        self.dtype = np.dtype(dtype)
        self.capacity_blocks = max(1, capacity_pages // PAGES_PER_BLOCK)
        self.data = np.zeros((self.capacity_blocks * PAGES_PER_BLOCK,
                              page_elems), self.dtype)
        self._free_slots: List[int] = list(range(self.capacity_blocks))[::-1]
        self._slot_of_block: Dict[int, int] = {}
        self.allocator = BitmapPageAllocator(
            max_blocks=self.capacity_blocks,
            grow=self._on_grow, release=self._on_release)
        self._owner_pages: Dict[str, Set[int]] = {}
        # one pool serves every tenant; concurrent serves allocate/free
        # from worker threads, so allocator mutations are lock-guarded
        self._lock = threading.RLock()
        #: route batched scatters through the page_copy Pallas kernel
        #: (TPU deployments; CPU tests flip it to prove equivalence)
        self.use_kernel_scatter = False
        self.scatter_calls = 0

    # -- block <-> physical slot mapping ------------------------------------
    def _on_grow(self, block_id: int) -> None:
        if not self._free_slots:
            raise MemoryError("page pool: out of physical blocks")
        self._slot_of_block[block_id] = self._free_slots.pop()

    def _on_release(self, block_id: int) -> None:
        # "madvise(MADV_DONTNEED)": the physical block returns to the host
        self._free_slots.append(self._slot_of_block.pop(block_id))

    def _phys(self, pages: Sequence[int]) -> np.ndarray:
        return np.array(
            [self._slot_of_block[p >> 10] * PAGES_PER_BLOCK +
             (p & (PAGES_PER_BLOCK - 1)) for p in pages], np.int64)

    # -- allocation -----------------------------------------------------------
    def alloc(self, n: int, owner: str) -> List[int]:
        with self._lock:
            ids = self.allocator.alloc_many(n)
            self._owner_pages.setdefault(owner, set()).update(ids)
            return ids

    def share(self, pages: Iterable[int], new_owner: str) -> None:
        """COW-share existing pages with another owner (prefix sharing)."""
        pages = list(pages)
        with self._lock:
            for p in pages:
                self.allocator.incref(p)
            self._owner_pages.setdefault(new_owner, set()).update(pages)

    def refcount(self, page: int) -> int:
        """How many owners/sessions currently map this page."""
        with self._lock:
            return self.allocator.refcount(page)

    def break_cow(self, page: int, owner: str) -> int:
        """Copy-on-write break: give ``owner`` a private copy of ``page``.

        Allocates a fresh page, copies the physical contents, and drops
        this owner's reference on the shared original (which stays alive
        for its other sharers).  Returns the new page id.  The write-fault
        analogue of a COW-mapped guest page being touched."""
        with self._lock:
            new = self.alloc(1, owner)[0]
            src, dst = self._phys([page, new])
            self.data[dst] = self.data[src]
            self.free([page], owner)
            return new

    def free(self, pages: Iterable[int], owner: str) -> int:
        """Decref pages for this owner; returns how many were truly freed."""
        freed = 0
        with self._lock:
            own = self._owner_pages.get(owner, set())
            for p in list(pages):
                own.discard(p)
                if self.allocator.decref(p):
                    freed += 1
        return freed

    def free_owner(self, owner: str) -> int:
        with self._lock:
            pages = list(self._owner_pages.get(owner, ()))
            n = self.free(pages, owner)
            self._owner_pages.pop(owner, None)
            return n

    # -- data movement ----------------------------------------------------------
    def write(self, pages: Sequence[int], data: np.ndarray) -> None:
        d = np.asarray(data, self.dtype).reshape(len(pages), self.page_elems)
        self.data[self._phys(pages)] = d

    def read(self, pages: Sequence[int]) -> np.ndarray:
        return self.data[self._phys(pages)].copy()

    def gather(self, pages: Sequence[int]) -> np.ndarray:
        """Zero-copy-ish view for compute (CPU sim of the paged gather)."""
        return self.data[self._phys(pages)]

    def scatter(self, pages: Sequence[int], rows: np.ndarray, *,
                use_kernel: Optional[bool] = None) -> None:
        """Batched page scatter: install a contiguous buffer of restored
        pages in ONE store — the inflate-side half of the ``page_copy``
        kernel's contract (scattered pool pages <-> one sequential
        stream).  The wake pipeline issues one scatter per chunk instead
        of a per-page ``_set`` copy.

        ``use_kernel`` routes the copy through the ``page_copy.
        scatter_pages`` Pallas kernel, compiled for the TPU (CPU callers
        run it under ``pltpu.force_tpu_interpret_mode()``).  The kernel
        path rebinds ``self.data`` to the kernel's output buffer, so it
        must only be enabled when no other thread holds page views into
        the pool — the default numpy path is
        an in-place vectorized store and is always safe."""
        rows = np.asarray(rows, self.dtype).reshape(len(pages),
                                                    self.page_elems)
        with self._lock:
            phys = self._phys(pages)
        if use_kernel is None:
            use_kernel = self.use_kernel_scatter
        if use_kernel and self.page_elems % 128 == 0:
            import jax.numpy as jnp
            from repro.kernels.page_copy import ops as pc_ops
            self.data = np.asarray(pc_ops.scatter_pages(
                jnp.asarray(self.data), jnp.asarray(phys, jnp.int32),
                jnp.asarray(rows)))
        else:
            self.data[phys] = rows
        self.scatter_calls += 1

    # -- accounting (PSS analogue) ------------------------------------------------
    @property
    def page_bytes(self) -> int:
        return self.page_elems * self.dtype.itemsize

    def pages_of(self, owner: str) -> Set[int]:
        return set(self._owner_pages.get(owner, ()))

    def rss_bytes(self, owner: str) -> int:
        return len(self._owner_pages.get(owner, ())) * self.page_bytes

    def pss_bytes(self, owner: str) -> float:
        """Proportional set size: shared pages divided by refcount."""
        tot = 0.0
        for p in self._owner_pages.get(owner, ()):
            tot += self.page_bytes / self.allocator.refcount(p)
        return tot

    @property
    def committed_bytes(self) -> int:
        """Bytes of blocks currently committed (not yet madvise'd away)."""
        return self.allocator.committed_blocks * PAGES_PER_BLOCK * \
            self.page_bytes

    @property
    def used_bytes(self) -> int:
        return self.allocator.allocated_pages * self.page_bytes
