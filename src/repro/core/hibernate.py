"""HibernationManager — the 4-step deflation of §3.2, the deflation-ladder
rungs, and all inflate paths.

Full deflate (Warm/Woken/MmapClean/Partial -> Hibernate):
  1. *Pause*: SIGSTOP transition; the engine stops scheduling the instance
     (its compiled executables — the "blocked runtime threads" — stay alive).
     An in-flight wake stream is cancelled and drained first, and any
     working-set unit the cancelled stream never delivered is restored from
     the (unmodified) REAP file before it is rewritten — a deflate racing a
     wake can never lose bytes.
  2. *Reclaim freed memory*: trim KV-cache slack pages back to the shared
     pool (the Bitmap allocator returns fully-free blocks to the host).
  3. *Swap out committed memory*: weight units + live KV pages.  Working-set
     units (from the REAP recorder) go to the REAP file with one batched
     sequential write **in first-touch order**; the rest go to the
     page-fault swap file.
  4. *Clean file-backed mmap*: shared base-weight leaves are decref'd in the
     registry (dropped at zero; re-read from the checkpoint on demand).

Wake — three inflate paths:
  * ``mode="reap"``, pipelined (default deployment config) — the streamed
    wake pipeline (:mod:`repro.core.inflate`): the REAP extent list is
    split into chunks and ``preadv`` double-buffers against decode/install
    workers; ``wake()`` returns as soon as the prefill-critical prefix
    (embedding blocks + layer-0 units) is resident while the tail streams
    in the background.  Faults arriving mid-stream demand-pull their
    chunks; serviced faults trigger lookahead prefetch of the next
    layer's units.
  * ``mode="reap"``, synchronous — one batched sequential read restores
    the whole working set before ``wake()`` returns.
  * ``mode="pagefault"`` — nothing restored upfront; each unit is a random
    read on first access.

Ladder rungs (the governor's incremental deflate, between Warm and the
full Hibernate above):

  * :meth:`HibernationManager.deflate_mmap` — step 4 alone: the §3.5
    file-backed mmap cleanup.  Shared base-weight units are decref'd
    (dropped at refcount zero, re-read from the checkpoint on wake);
    anonymous memory stays resident, so wake is a re-map.
  * :meth:`HibernationManager.deflate_partial` — steps 1+3 on a *victim
    subset*: the given cold unit keys (REAP-miss-ranked experts /
    deep-layer KV pages) are written to the page-fault tier and dropped,
    while the prefill-critical prefix stays resident.  Reuses the wake-
    stream drain logic, so a partial deflate racing a streamed wake never
    loses bytes.  Callable repeatedly for proportional reclaim.

Wakes are rung-aware: a PARTIAL wake has no REAP batch to stream — it
re-maps and restores the swapped units in the background
(:func:`repro.core.inflate.partial_restore_keys`); an MMAP_CLEAN wake is
a pure re-map.  ``WakeStats.rung`` records which rung a wake climbed
from, which is how the governor learns measured per-rung wake costs.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional, Tuple

from repro.core.inflate import (InflatePipeline, InflatorPool,
                                partial_restore_keys)
from repro.core.instance import ModelInstance
from repro.core.metrics import span
from repro.core.state import ContainerState, Event


@dataclass
class DeflateStats:
    reap_bytes: int = 0
    swap_bytes: int = 0              # logical (raw) bytes sent to swap tier
    kv_pages_swapped: int = 0
    kv_pages_reclaimed: int = 0
    shared_bytes_released: int = 0
    # content-addressed tier breakdown for a SwapStore-backed instance
    # (a verbatim per-sandbox SwapFile reports stored == swap_bytes)
    swap_stored_bytes: int = 0       # new on-disk bytes (post compression)
    swap_dedup_bytes: int = 0        # satisfied by existing shared segments
    swap_elided_bytes: int = 0       # constant-fill units, metadata only
    seconds: float = 0.0
    #: ladder rung this deflate landed on ("mmap_clean"/"partial"/"hibernated")
    rung: str = "hibernated"


@dataclass
class WakeStats:
    mode: str = "reap"
    prefetched_bytes: int = 0
    faulted_bytes: int = 0
    faults: int = 0
    #: wall time ``wake()``/``fault()`` blocked the caller.  For a
    #: pipelined wake this is the *critical path* only — the tail keeps
    #: streaming after the call returns.
    seconds: float = 0.0
    #: time spent in vectored reads (pipelined: summed across concurrent
    #: chunk reads, may exceed wall time)
    io_seconds: float = 0.0
    #: time spent decoding + installing units (zlib inflate for store-tier
    #: payloads, array materialization + pool scatter for REAP chunks)
    inflate_seconds: float = 0.0
    #: time-to-first-schedulable: from wake start until the prefill-
    #: critical prefix was resident (== ``seconds`` for synchronous wakes)
    critical_path_seconds: float = 0.0
    #: stream was pipelined (the tail may still be inflating)
    pipelined: bool = False
    #: ladder rung this wake climbed from ("mmap_clean"/"partial"/
    #: "hibernated") — the governor's measured per-rung cost signal
    rung: str = "hibernated"


class HibernationManager:
    def __init__(self, shared_registry=None, *,
                 inflator: Optional[InflatorPool] = None,
                 wake_chunk_bytes: int = 256 << 10):
        self.shared_registry = shared_registry      # manager's weight registry
        self.inflator = inflator
        self.wake_chunk_bytes = wake_chunk_bytes
        self.log: List[Tuple[str, str, object]] = []
        #: lookahead-prefetch accounting
        self.lookahead_keys = 0

    # ------------------------------------------------------------- deflate
    def quiesce(self, inst: ModelInstance) -> None:
        """Step 0 of every whole-instance transition (full deflate,
        migration): an in-flight wake stream drains first (no new chunks
        are claimed; in-flight chunks finish installing), and background
        lookahead fetches quiesce — the caller must own the instance."""
        pipe = inst.wake_pipeline
        if pipe is not None:
            pipe.cancel(drain=True)
            inst.wake_pipeline = None
        inst.quiesce_bg()

    def deflate(self, inst: ModelInstance, *,
                event: Event = Event.SIGSTOP) -> DeflateStats:
        """Full deflate.  ``event`` is normally SIGSTOP (④); a cluster
        migration of a not-yet-hibernated tenant passes ``MIGRATE`` — the
        same swap-out body runs, but the state lands on MIGRATING so the
        governor cannot touch the tenant while its snapshot ships."""
        t0 = time.monotonic()
        st = DeflateStats()

        self.quiesce(inst)

        # step 1: pause (SIGSTOP / MIGRATE).  Raises if a request is in
        # flight.
        inst.sm.fire(event)

        # a cancelled stream may have left working-set units undelivered;
        # the REAP file is rewritten below from *resident* state, so
        # restore them now or their bytes would be lost
        self._restore_reap_leftovers(inst)

        # step 2: reclaim freed memory — trim KV slack back to the pool
        if inst.kv is not None:
            st.kv_pages_reclaimed = inst.kv.trim()

        # step 3: swap out committed memory (weights + live KV pages)
        ws = inst.recorder.working_set
        w_reap, w_swap = inst.collect_weight_items(ws)
        kv_reap, kv_swap, n_pages = ([], [], 0)
        if inst.kv is not None:
            kv_reap, kv_swap = inst.kv.export_items(ws)
            n_pages = len(kv_reap) + len(kv_swap)
        # unconditional: an empty working set must CLEAR the REAP file,
        # or a later wake would prefetch a previous cycle's stale extents.
        # The batch is laid out in FIRST-TOUCH order (the recorder's
        # insertion order) so the wake pipeline streams units in the order
        # the sample request needed them.
        order = {k: i for i, k in enumerate(inst.recorder.ordered_working_set)}
        items = sorted(w_reap + kv_reap,
                       key=lambda it: order.get(it[0], len(order)))
        inst.reap_file.write_batch(items)
        # content-address the working set too (cluster inventory): the
        # REAP file keeps the wake path private + sequential, while the
        # CAS copy dedups against every same-deployment tenant on the
        # node — digest-overlap placement affinity and dedup-aware
        # migration transfers (repro.cluster) read it.  For shared base
        # weights this is metadata-only after the first tenant.
        if items and getattr(inst.swap_file, "store", None) is not None:
            inst.swap_file.write_units(items)
        # coldness signal for the store's compression tiers: these units
        # missed the working set this cycle.  Only meaningful when a REAP
        # working set exists — with no recorded set (pagefault-mode
        # deployments) nothing can "miss" it, and hot units must not sink
        # to zlib tiers.  Prune counters for keys that no longer exist
        # (trimmed sessions) so session churn cannot grow the dict
        if ws:
            inst.recorder.note_misses(k for k, _ in w_swap + kv_swap)
            live = set(inst.units)
            live.update(k for k, _ in kv_reap + kv_swap)
            inst.recorder.prune_misses(live)
        receipt = inst.swap_file.write_units(w_swap + kv_swap)
        if receipt is not None:
            st.swap_stored_bytes = receipt.stored_bytes
            st.swap_dedup_bytes = receipt.dedup_bytes
            st.swap_elided_bytes = receipt.elided_bytes
        inst.drop_weights()
        if inst.kv is not None:
            inst.kv.drop_pages()
        st.reap_bytes = sum(a.nbytes for _, a in w_reap + kv_reap)
        st.swap_bytes = sum(a.nbytes for _, a in w_swap + kv_swap)
        st.kv_pages_swapped = n_pages

        # step 4: clean up file-backed (shared) memory.  Guarded by the
        # mmap_dropped flag so a ladder path through MMAP_CLEAN/PARTIAL
        # (which already released) stays refcount-balanced.
        st.shared_bytes_released = self._release_mmap(inst)

        inst.inflated = False
        st.seconds = time.monotonic() - t0
        self.log.append(("deflate", inst.instance_id, st))
        return st

    def _has_mmap(self, inst: ModelInstance) -> bool:
        return (self.shared_registry is not None and bool(inst.base_id)
                and bool(inst.shared_paths))

    def _release_mmap(self, inst: ModelInstance) -> int:
        """Mark the mmap rung descended and release the registry ref if
        one is actually held.  The flag is set even for instances with no
        shared mmap: it also tells ``ensure_awake`` that an MMAP_CLEAN
        instance still needs its (no-op) re-map wake."""
        held = self._has_mmap(inst) and not inst.mmap_dropped
        inst.mmap_dropped = True
        return self.shared_registry.release(inst.base_id) if held else 0

    def remap(self, inst: ModelInstance) -> None:
        """Re-acquire the shared base-weight mmap dropped by a ladder
        descent (clean file-backed pages: re-read from the checkpoint at
        refcount 0->1, free otherwise)."""
        if self._has_mmap(inst) and inst.mmap_dropped:
            self.shared_registry.acquire(inst.base_id, inst)
        inst.mmap_dropped = False

    # --------------------------------------------------------- ladder rungs
    def deflate_mmap(self, inst: ModelInstance) -> DeflateStats:
        """Rung 1 (MMAP_CLEAN): the §3.5 file-backed mmap cleanup alone.

        Shared base-weight units are decref'd in the registry; anonymous
        memory stays resident and the instance remains schedulable, so
        the wake cost is a re-map (plus one checkpoint re-read when this
        tenant was the last sharer).  An in-flight wake stream is left
        alone — it only installs anonymous units."""
        t0 = time.monotonic()
        st = DeflateStats(rung="mmap_clean")
        inst.sm.fire(Event.MMAP_DROP)
        st.shared_bytes_released = self._release_mmap(inst)
        if inst.state == ContainerState.PARTIAL:
            # a WOKEN instance lands in PARTIAL (4a'): its next request
            # must run the re-map wake, so clear the wake-storm guard's
            # "already inflated this cycle" flag
            inst.inflated = False
            st.rung = "partial"
        st.seconds = time.monotonic() - t0
        self.log.append(("deflate", inst.instance_id, st))
        return st

    def deflate_partial(self, inst: ModelInstance, keys) -> DeflateStats:
        """Rung 2 (PARTIAL): swap out only the given *cold* unit keys.

        The prefill-critical prefix stays resident, so a later wake is
        near-warm; the victims (REAP-miss-ranked MoE experts, deep-layer
        KV pages — chosen by the governor) go to the page-fault tier and
        demand-fault back on first touch.  Reuses the full-deflate drain
        logic: an in-flight wake stream is cancelled and drained first so
        a stale background install cannot resurrect a dropped unit.
        Callable repeatedly on an already-PARTIAL instance — proportional
        reclaim takes several small bites instead of one full deflate."""
        t0 = time.monotonic()
        st = DeflateStats(rung="partial")

        self.quiesce(inst)

        inst.sm.fire(Event.PARTIAL_STOP)
        # mmap cleanup rides along: PARTIAL is below MMAP_CLEAN on the
        # ladder, and the flag keeps the refcount balanced if it already ran
        st.shared_bytes_released = self._release_mmap(inst)

        keys = list(dict.fromkeys(keys))
        w_items = inst.collect_weight_items_for(
            [k for k in keys if k and k[0] == "w"])
        kv_items = (inst.kv.export_keys(
            [k for k in keys if k and k[0] in ("kv", "kvh")])
            if inst.kv is not None else [])
        items = w_items + kv_items
        # victims are cold by construction: bump their coldness counters
        # so the store's compression tiers can sink them
        inst.recorder.note_misses(k for k, _ in items)
        receipt = inst.swap_file.write_units(items)
        if receipt is not None:
            st.swap_stored_bytes = receipt.stored_bytes
            st.swap_dedup_bytes = receipt.dedup_bytes
            st.swap_elided_bytes = receipt.elided_bytes
        inst.drop_units([k for k, _ in w_items])
        if kv_items and inst.kv is not None:
            st.kv_pages_swapped = inst.kv.drop_keys([k for k, _ in kv_items])
        st.swap_bytes = sum(a.nbytes for _, a in items)

        inst.inflated = False
        st.seconds = time.monotonic() - t0
        self.log.append(("deflate", inst.instance_id, st))
        return st

    def _restore_reap_leftovers(self, inst: ModelInstance) -> None:
        """Fault in working-set units still sitting only in the REAP file
        (a cancelled mid-stream wake, or pagefault-mode access that never
        touched them) before the file is rewritten."""
        if not inst.reap_file.extents:
            return
        wkeys = [k for k in inst.reap_file.extents
                 if k[0] == "w" and k not in inst.resident]
        if wkeys:
            inst.fault_in(wkeys)
        if inst.kv is not None:
            kvkeys = inst.kv.nonresident_keys(
                [k for k in inst.reap_file.extents
                 if k[0] in ("kv", "kvh")])
            if kvkeys:
                with inst.install_lock:
                    inst.kv.fault_in(kvkeys, inst.swap_file, inst.reap_file)

    # ------------------------------------------------------------- wake
    def wake(self, inst: ModelInstance, mode: str = "reap",
             trigger: str = "request", pipelined: bool = False,
             priority: str = "high") -> WakeStats:
        """Inflate.  ``trigger="sigcont"`` is the predictive control-plane
        wake (⑤); ``trigger="request"`` is the request-driven wake (⑦) —
        the state transition to HIBERNATE_RUNNING is fired by the engine.

        With ``pipelined=True`` the REAP restore streams through an
        :class:`InflatePipeline`: this call returns once the prefill-
        critical prefix is resident (``critical_path_seconds``); the tail
        keeps inflating on ``inst.wake_pipeline``.  Anticipatory wakes
        (``priority="low"``) run the same pipeline without read
        double-buffering and yield between chunks.

        The wake is *rung-aware*: MMAP_CLEAN and PARTIAL instances take
        their cheap paths (:meth:`_wake_mmap` / :meth:`_wake_partial`)
        instead of the full REAP restore."""
        if inst.state == ContainerState.MMAP_CLEAN:
            return self._wake_mmap(inst, trigger)
        if inst.state == ContainerState.PARTIAL:
            return self._wake_partial(inst, trigger, pipelined)
        t0 = time.monotonic()
        st = WakeStats(mode=mode)

        # re-acquire shared base weights (file-backed: from checkpoint)
        self.remap(inst)

        if mode == "reap" and inst.reap_file.extents:
            if pipelined:
                st.pipelined = True
                pipe = InflatePipeline(
                    inst, self.inflator, st,
                    chunk_bytes=self.wake_chunk_bytes, priority=priority)
                inst.wake_pipeline = pipe
                pipe.start()
                pipe.wait_critical()
            else:
                # ONE batched sequential read (preadv), -> weights + KV
                tenant = inst.instance_id
                t_io = time.monotonic()
                with span("wake.read", tenant=tenant):
                    data = inst.reap_file.read_batch()
                st.io_seconds = time.monotonic() - t_io
                t_inf = time.monotonic()
                with span("wake.install", tenant=tenant):
                    st.prefetched_bytes += inst.apply_prefetch(data)
                    if inst.kv is not None:
                        st.prefetched_bytes += inst.kv.apply_prefetch(data)
                st.inflate_seconds = time.monotonic() - t_inf
        # pagefault mode restores nothing here; units fault in on access

        # shared-prefix slots are never swapped (the registry pins the
        # pages); re-mapping them is a COW share, not IO — do it eagerly
        # so the woken tenant decodes without compute-path remap faults
        st.prefetched_bytes += self._reattach_prefixes(inst)

        inst.inflated = True
        if trigger == "sigcont":
            inst.sm.fire(Event.SIGCONT)
        st.seconds = time.monotonic() - t0
        if not st.pipelined:
            st.critical_path_seconds = st.seconds
        self.log.append(("wake", inst.instance_id, st))
        return st

    def _wake_mmap(self, inst: ModelInstance, trigger: str) -> WakeStats:
        """MMAP_CLEAN wake: pure re-map — anonymous memory never left."""
        t0 = time.monotonic()
        st = WakeStats(mode="remap", rung="mmap_clean")
        self.remap(inst)
        inst.inflated = True
        if trigger == "sigcont":
            inst.sm.fire(Event.SIGCONT)          # -> WARM
        st.seconds = st.critical_path_seconds = time.monotonic() - t0
        self.log.append(("wake", inst.instance_id, st))
        return st

    def _wake_partial(self, inst: ModelInstance, trigger: str,
                      pipelined: bool) -> WakeStats:
        """PARTIAL wake: the critical prefix is already resident, so the
        caller is schedulable immediately — the swapped cold tail restores
        in the background (demand faults cover anything touched sooner).
        Without an inflator pool (or with ``pipelined=False``) the restore
        runs synchronously instead."""
        t0 = time.monotonic()
        st = WakeStats(mode="partial", rung="partial",
                       pipelined=pipelined and self.inflator is not None)
        self.remap(inst)
        inst.inflated = True
        st.prefetched_bytes += self._reattach_prefixes(inst)
        keys = partial_restore_keys(inst)
        if trigger == "sigcont":
            inst.sm.fire(Event.SIGCONT)          # -> WOKEN
        if st.pipelined:
            st.critical_path_seconds = time.monotonic() - t0
            self.prefetch_async(inst, keys)
        elif keys:
            t_io = time.monotonic()
            wkeys = [k for k in keys if k[0] == "w"]
            st.prefetched_bytes += inst.fault_in(wkeys)
            kvkeys = [k for k in keys if k[0] in ("kv", "kvh")]
            if kvkeys and inst.kv is not None:
                with inst.install_lock:
                    st.prefetched_bytes += inst.kv.fault_in(
                        kvkeys, inst.swap_file, inst.reap_file)
            st.io_seconds = time.monotonic() - t_io
        st.seconds = time.monotonic() - t0
        if not st.pipelined:
            st.critical_path_seconds = st.seconds
        self.log.append(("wake", inst.instance_id, st))
        return st

    def _reattach_prefixes(self, inst: ModelInstance) -> int:
        """Re-map a woken tenant's shared-prefix slots from the registry
        (a COW re-share of resident pages; a spilled prefix revives from
        the CAS store by digest first).  Returns bytes made resident."""
        kv = inst.kv
        if kv is None or kv.registry is None:
            return 0
        missing = kv.prefix_missing_keys()
        if not missing:
            return 0
        with inst.install_lock:
            return kv.fault_in(missing, inst.swap_file, inst.reap_file)

    # ------------------------------------------------------------- faults
    def fault(self, inst: ModelInstance, keys) -> WakeStats:
        """Fault path for weight and KV unit keys.

        Keys covered by an in-flight wake stream are *demand-pulled*: their
        chunks are claimed and processed inline (or waited on if the
        streamer already has them) — a fault never re-reads bytes the
        pipeline is about to deliver.  The remainder batches through the
        vectored swap-file read (`read_units`): extent-sorted, adjacent
        extents merged, one ``preadv`` per run."""
        t0 = time.monotonic()
        st = WakeStats(mode="pagefault")
        pipe = inst.wake_pipeline
        if pipe is not None and pipe.active:
            covered = [k for k in keys if pipe.covers(k)]
            if covered:
                st.faulted_bytes += pipe.demand(covered)
        # the residual path re-checks residency, so anything the pipeline
        # just delivered (or a cancelled stream failed to) is handled
        # exactly once
        wkeys = [k for k in keys if k and k[0] == "w"]
        kvkeys = [k for k in keys if k and k[0] in ("kv", "kvh")]
        st.faulted_bytes += inst.fault_in(wkeys)
        if kvkeys and inst.kv is not None:
            kvkeys_nr = inst.kv.nonresident_keys(kvkeys)
            if kvkeys_nr:
                with inst.install_lock:
                    st.faulted_bytes += inst.kv.fault_in(
                        kvkeys_nr, inst.swap_file, inst.reap_file)
        st.faults += len(wkeys) + len(kvkeys)
        st.seconds = time.monotonic() - t0
        return st

    # ------------------------------------------------------------- lookahead
    def prefetch_async(self, inst: ModelInstance, keys) -> int:
        """Lookahead prefetch: asynchronously make ``keys`` resident on an
        inflator-pool thread so the units the next layer (or the session's
        next KV pages) will touch hit residency instead of faulting.

        Best-effort: errors are swallowed, residency is re-checked under
        the install lock, and deflate quiesces outstanding fetches via the
        instance's background-task counter."""
        keys = [k for k in dict.fromkeys(keys)]
        if not keys or self.inflator is None:
            return 0
        inst.bg_begin()
        self.inflator.submit(self._prefetch_task, inst, keys)
        self.lookahead_keys += len(keys)
        return len(keys)

    def _prefetch_task(self, inst: ModelInstance, keys) -> None:
        try:
            if not inst.inflated:
                return                          # deflated since scheduling
            pipe = inst.wake_pipeline
            if pipe is not None and pipe.active:
                # opportunistic (wait=False): a pool worker must never
                # park waiting on an in-flight chunk — the read that
                # would complete it may be queued behind this very task
                # on the same pool (priority inversion).  In-flight
                # chunks are coming anyway; pending ones process inline.
                covered = [k for k in keys if pipe.covers(k)]
                if covered:
                    pipe.demand(covered, timeout=30.0, wait=False)
                    keys = [k for k in keys if k not in set(covered)]
            swap_ks = [k for k in keys if k in inst.swap_file]
            reap_ks = [k for k in keys if k not in inst.swap_file
                       and k in inst.reap_file.extents]
            for f, ks in ((inst.swap_file, swap_ks),
                          (inst.reap_file, reap_ks)):
                if not ks:
                    continue
                # chunked streaming read: bounded memory, and the install
                # lock is only held per-chunk
                for batch in f.read_units_iter(ks, self.wake_chunk_bytes):
                    inst.install_units(batch)
        except Exception:                      # pragma: no cover - best effort
            pass
        finally:
            inst.bg_end()
