"""Streamed wake pipeline: overlap swap-in, decompression, and compute.

The paper's latency claim for a Woken container is that it answers "with
similar response latency to Warm" because only *part* of the deflated
memory must be inflated before the request runs.  The synchronous wake
path (`HibernationManager.wake`) restores the whole REAP batch before the
engine schedules anything; this module converts that serial region into a
three-stage pipeline:

  stage 1  IO        chunked vectored ``preadv`` over the REAP extent
                     list (written in first-touch order), double-buffered:
                     the read for chunk N+1 is issued while chunk N is
                     still being decoded/installed (``preadv`` releases
                     the GIL, as does zlib for store-tier lookahead).
  stage 2  decode    raw extents are materialized into arrays (zlib
                     inflate for SwapStore-tier lookahead fetches).
  stage 3  install   units land in the instance: weight units via
                     ``_set_unit``, KV pages batched through one pool
                     scatter per chunk (`PagedKVCache.install_batch` /
                     the ``page_copy.scatter_pages`` Pallas kernel).

``wake()`` returns as soon as the **prefill-critical prefix** is resident
— embedding blocks + non-expert ("layer-0"-bearing) weight leaves +
layer-0 KV pages + host cache units — while the tail (MoE experts,
deeper-layer KV pages) streams in the background.  Requests arriving
mid-stream *demand-pull* the exact chunks they fault on
(`InflatePipeline.demand`), and the engine turns serviced faults into
lookahead prefetch of the next layer's units.

Cancellation: deflate (or eviction) during an in-flight stream calls
``cancel(drain=True)`` — the streamer stops claiming new chunks, in-flight
chunks finish installing, and the caller can then restore any still-
missing working-set units from the (unmodified) REAP file.
"""
from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, Hashable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.metrics import span
from repro.core.swap import read_extents

#: chunk states
_PENDING, _INFLIGHT, _DONE = 0, 1, 2


def is_critical_key(key: Hashable) -> bool:
    """Prefill-critical units: the wake pipeline must deliver these before
    the instance is first-schedulable.

      * weight units: embedding blocks and every non-expert leaf (layers
        are stacked, so each dense leaf carries layer 0); MoE expert
        slices are tail — the router reveals them per request;
      * KV pages: layer 0 only — deeper layers stream behind compute;
      * host cache units (SSM state, conv, cross-K/V): always critical,
        prefill reads them at step 0.
    """
    kind = key[0]
    if kind == "w":
        return key[1] == "embed" or key[2] < 0 or "/moe/" not in key[1]
    if kind == "kv":
        return key[2] == 0
    return True


def critical_wake_keys(inst) -> List[Hashable]:
    """The critical prefix of this instance's REAP file, in file order."""
    return [k for k in inst.reap_file.extents if is_critical_key(k)]


def partial_restore_keys(inst) -> List[Hashable]:
    """Rung-aware wake plan for a PARTIAL-rung instance.

    A partial deflate swaps *cold* units into the page-fault tier while
    the prefill-critical prefix stays resident, so a PARTIAL wake has no
    REAP batch to stream — it restores exactly the swapped-out units.
    Ordered for the background restorer: any critical key first (the
    governor never swaps them, but a wake must not starve prefill if one
    slipped through), then hottest-first (lowest REAP-miss count) so the
    units most likely to be touched next arrive before the truly cold
    tail."""
    def swapped(k):
        # a unit may live in the REAP file instead of the page-fault
        # tier: a cancelled mid-stream wake leaves undelivered working-
        # set units there, and a partial deflate does not rewrite it —
        # those are hot, so the restore must cover them too
        return k in inst.swap_file or k in inst.reap_file.extents

    keys: List[Hashable] = [k for k in inst.nonresident_keys()
                            if swapped(k)]
    if inst.kv is not None:
        keys += [k for k in inst.kv.nonresident_logical_keys()
                 if swapped(k)]
    miss = inst.recorder.miss_count
    return sorted(keys, key=lambda k: (not is_critical_key(k), miss(k)))


class InflatorPool:
    """Per-deployment pool of inflator worker threads.

    A lazy thread pool whose daemon workers exit after ``idle_s`` without
    work, so deployments (and tests) that never wake pay zero threads and
    idle deployments shed them.  Used for the pipeline's read prefetch
    (stage-1 double buffering) and for background lookahead fetches."""

    def __init__(self, max_workers: int = 3, idle_s: float = 2.0,
                 name: str = "inflate"):
        self.max_workers = max(1, max_workers)
        self.idle_s = idle_s
        self.name = name
        self._q: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._workers = 0
        self._idle = 0
        self._seq = 0

    def submit(self, fn, *args) -> Future:
        fut: Future = Future()
        with self._lock:
            self._q.put((fut, fn, args))
            if self._idle == 0 and self._workers < self.max_workers:
                self._workers += 1
                self._seq += 1
                threading.Thread(target=self._worker, daemon=True,
                                 name=f"{self.name}-{self._seq}").start()
        return fut

    def _worker(self) -> None:
        while True:
            with self._lock:
                self._idle += 1
            try:
                item = self._q.get(timeout=self.idle_s)
            except queue.Empty:
                with self._lock:
                    self._idle -= 1
                    if self._q.empty():
                        self._workers -= 1
                        return
                continue
            with self._lock:
                self._idle -= 1
            fut, fn, args = item
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn(*args))
            except BaseException as e:          # worker must survive anything
                fut.set_exception(e)


class _Chunk:
    __slots__ = ("idx", "keys", "extents", "nbytes", "state")

    def __init__(self, idx: int, keys, extents, nbytes: int):
        self.idx = idx
        self.keys: List[Hashable] = keys
        self.extents: List[Tuple[int, int, str, Tuple]] = extents
        self.nbytes = nbytes
        self.state = _PENDING


class InflatePipeline:
    """One in-flight streamed wake of one instance.

    The handle lives on ``inst.wake_pipeline`` for the duration of the
    stream; the wake-storm guard hands it to late arrivals, the fault path
    demand-pulls from it, and deflate cancels it.
    """

    def __init__(self, inst, pool: Optional[InflatorPool], stats, *,
                 chunk_bytes: int = 256 << 10, priority: str = "high"):
        self.inst = inst
        self.pool = pool
        self.stats = stats                     # WakeStats (duck-typed)
        self.priority = priority
        self.chunk_bytes = max(1, chunk_bytes)
        self._cv = threading.Condition()
        self._critical_evt = threading.Event()
        self._done_evt = threading.Event()
        self._cancelled = False
        #: >0 while a request is actively computing on the instance: the
        #: streamer pauses between chunks (the request's own thread
        #: demand-pulls anything it needs), so background installs never
        #: steal the serve path's cycles.  Cheap-to-miss: the tail simply
        #: finishes a little later.
        self._backpressure = 0
        self.failed: Optional[BaseException] = None
        self._t0 = time.monotonic()

        # Plan chunks over the REAP file in first-touch (= file) order,
        # critical keys and tail keys into SEPARATE chunk streams:
        #   * critical chunks are large (8x) — they gate time-to-first-
        #     schedulable, so per-chunk overhead matters more than
        #     demand-pull granularity (each is still a few vectored runs);
        #   * tail chunks stay fine-grained so a mid-stream fault
        #     demand-pulls little more than what it asked for.
        # Within each class the subsequence keeps ascending file offsets,
        # which is what read_extents needs to merge runs.
        self._chunk_of: Dict[Hashable, _Chunk] = {}
        self.chunks: List[_Chunk] = []
        crit_items, tail_items = [], []
        for key, e in inst.reap_file.extents.items():
            dst = crit_items if is_critical_key(key) else tail_items
            dst.append((key, (e.offset, e.nbytes, e.dtype, e.shape)))
        self._remaining_critical = {k for k, _ in crit_items}
        for items, cbytes in ((crit_items, 8 * self.chunk_bytes),
                              (tail_items, self.chunk_bytes)):
            keys, exts, size = [], [], 0
            for key, ext in items:
                keys.append(key)
                exts.append(ext)
                size += ext[1]
                if size >= cbytes:
                    self._push_chunk(keys, exts, size)
                    keys, exts, size = [], [], 0
            if keys:
                self._push_chunk(keys, exts, size)
        # chunk idx order == critical chunks first, then the tail
        self._order = list(self.chunks)
        self._thread: Optional[threading.Thread] = None

    def _push_chunk(self, keys, exts, size) -> None:
        ch = _Chunk(len(self.chunks), keys, exts, size)
        self.chunks.append(ch)
        for k in keys:
            self._chunk_of[k] = ch

    # ---------------------------------------------------------------- state
    @property
    def active(self) -> bool:
        return not self._done_evt.is_set()

    def covers(self, key: Hashable) -> bool:
        return key in self._chunk_of

    def backpressure(self, delta: int) -> None:
        """Engine hook: +1 while a request computes on this instance,
        -1 when it finishes.  While positive, the streamer parks between
        chunks instead of competing with compute for the interpreter."""
        with self._cv:
            self._backpressure += delta
            self._cv.notify_all()

    def installed(self, key: Hashable) -> bool:
        ch = self._chunk_of.get(key)
        return ch is not None and ch.state == _DONE

    # ---------------------------------------------------------------- start
    def start(self) -> "InflatePipeline":
        if not self.chunks:
            self._finish_critical()
            self._done_evt.set()
            return self
        self._thread = threading.Thread(
            target=self._streamer, daemon=True,
            name=f"wake-stream-{self.inst.instance_id}")
        self._thread.start()
        return self

    # ---------------------------------------------------------------- stages
    def _read(self, chunk: _Chunk):
        """Stage 1: one vectored read of the chunk's extents (ascending
        offsets — the REAP file is laid out in stream order, so a chunk is
        a handful of merged sequential runs)."""
        t0 = time.monotonic()
        with span("wake.read", tenant=self.inst.instance_id):
            bufs, calls = read_extents(
                self.inst.reap_file.fd,
                [(off, n) for off, n, _, _ in chunk.extents])
        dt = time.monotonic() - t0
        with self._cv:
            self.stats.io_seconds += dt
            f = self.inst.reap_file
            f.reads += calls
            f.bytes_read += chunk.nbytes
        return bufs

    def _decode_install(self, chunk: _Chunk, bufs) -> None:
        """Stages 2+3: materialize arrays and install them (weights via
        ``_set_unit``, KV pages batched through one pool scatter)."""
        t0 = time.monotonic()
        with span("wake.install", tenant=self.inst.instance_id):
            data: Dict[Hashable, np.ndarray] = {}
            for key, (_, _, dtype, shape), buf in zip(
                    chunk.keys, chunk.extents, bufs):
                data[key] = np.frombuffer(buf, dtype).reshape(shape)
            installed = self.inst.install_units(data)
        with self._cv:
            self.stats.inflate_seconds += time.monotonic() - t0
            self.stats.prefetched_bytes += installed
            chunk.state = _DONE
            self._remaining_critical.difference_update(chunk.keys)
            if not self._remaining_critical:
                self._finish_critical()
            if all(c.state == _DONE for c in self.chunks):
                self._done_evt.set()
            self._cv.notify_all()

    def _process(self, chunk: _Chunk) -> None:
        self._decode_install(chunk, self._read(chunk))

    def _finish_critical(self) -> None:
        if not self._critical_evt.is_set():
            self.stats.critical_path_seconds = time.monotonic() - self._t0
            self._critical_evt.set()

    # ---------------------------------------------------------------- stream
    def _claim_next(self) -> Optional[_Chunk]:
        """With ``_cv`` held: claim the first pending chunk in priority
        order (critical-bearing chunks first)."""
        for ch in self._order:
            if ch.state == _PENDING:
                ch.state = _INFLIGHT
                return ch
        return None

    def _streamer(self) -> None:
        """Background stream: double-buffered when priority is high — the
        read of chunk N+1 runs on an inflator-pool thread while chunk N
        decodes/installs here.  Low priority (anticipatory wakes) streams
        one chunk at a time and yields between chunks."""
        try:
            prefetch = self.priority == "high" and self.pool is not None
            pending = None                     # (chunk, read future) in flight
            while True:
                if pending is None:
                    # holding no claimed chunk: safe to park here — a
                    # parked streamer must never own a chunk a demand
                    # (from the very thread applying backpressure) waits on
                    self._park_if_backpressured()
                    with self._cv:
                        cur = None if self._cancelled else self._claim_next()
                    if cur is None:
                        break
                    bufs = self._read(cur)
                else:
                    cur, fut = pending
                    pending = None
                    bufs = fut.result()
                # double-buffer: issue the NEXT chunk's read on a pool
                # thread before installing this one (skip while
                # backpressured — claimed work must drain, not grow)
                if prefetch and not self._backpressured():
                    with self._cv:
                        nxt = None if self._cancelled else self._claim_next()
                    if nxt is not None:
                        pending = (nxt, self.pool.submit(self._read, nxt))
                self._decode_install(cur, bufs)
                if self.priority != "high":
                    time.sleep(0)              # yield to request threads
        except BaseException as e:             # fd closed mid-evict etc.
            self.failed = e
        finally:
            with self._cv:
                self._finish_critical()
                self._done_evt.set()
                self._cv.notify_all()

    def _backpressured(self) -> bool:
        with self._cv:
            return self._backpressure > 0

    def _park_if_backpressured(self) -> None:
        """Wait out active compute on the instance (bounded so cancel and
        serve-finish are both picked up promptly)."""
        with self._cv:
            while self._backpressure > 0 and not self._cancelled:
                self._cv.wait(0.05)

    # ---------------------------------------------------------------- pull
    def demand(self, keys: Sequence[Hashable], timeout: float = 120.0,
               wait: bool = True) -> int:
        """Demand-pull: make ``keys`` resident *now*.

        Pending chunks holding them are claimed and processed inline on
        the calling thread (out of stream order); chunks already in flight
        on the streamer are waited on.  Returns the bytes of demanded keys
        this call actually saw through to installation (chunks already
        done at entry, or never delivered because the stream was
        cancelled, are not billed — the caller's residual fault path
        accounts for those).

        ``wait=False`` is the opportunistic mode for lookahead running on
        inflator-pool workers: claim-and-process what is pending, but
        NEVER block on an in-flight chunk — a pool worker parked in a
        wait can starve the very read (queued on the same pool) that
        would satisfy it (priority inversion).
        """
        need: Dict[_Chunk, None] = {}
        mine: List[_Chunk] = []
        with self._cv:
            billable = [k for k in keys
                        if (ch := self._chunk_of.get(k)) is not None
                        and ch.state != _DONE]
            for k in billable:
                need.setdefault(self._chunk_of[k])
            for ch in need:
                if ch.state == _PENDING:
                    ch.state = _INFLIGHT
                    mine.append(ch)
        for ch in mine:
            try:
                self._process(ch)
            except BaseException as e:         # fd closed mid-evict etc.
                with self._cv:
                    self.failed = e
                    self._done_evt.set()
                    self._cv.notify_all()
                raise
        deadline = time.monotonic() + timeout
        with self._cv:
            while wait and any(ch.state != _DONE for ch in need):
                if self.failed is not None or self._done_evt.is_set():
                    break
                if not self._cv.wait(max(0.0, min(1.0, deadline - time.monotonic()))):
                    if time.monotonic() >= deadline:
                        raise TimeoutError(
                            f"wake pipeline demand timed out on "
                            f"{self.inst.instance_id}")
            return sum(self.inst.reap_file.extents[k].nbytes
                       for k in billable
                       if self._chunk_of[k].state == _DONE)

    # ---------------------------------------------------------------- waits
    def wait_critical(self, timeout: Optional[float] = None) -> bool:
        """Block until the prefill-critical prefix is resident (time-to-
        first-schedulable)."""
        ok = self._critical_evt.wait(timeout)
        if self.failed is not None:
            raise self.failed
        return ok

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the whole stream has drained (or was cancelled)."""
        return self._done_evt.wait(timeout)

    # ---------------------------------------------------------------- cancel
    def cancel(self, drain: bool = True,
               timeout: Optional[float] = 120.0) -> None:
        """Stop the stream: no new chunks are claimed; with ``drain`` the
        in-flight chunks finish installing before this returns, so the
        instance is never left with a half-installed chunk."""
        with self._cv:
            self._cancelled = True
            # pending chunks will never be claimed now; if nothing is in
            # flight the stream is already as drained as it will get
            if all(c.state != _INFLIGHT for c in self.chunks):
                self._finish_critical()
                self._done_evt.set()
                self._cv.notify_all()
        if drain:
            if self._thread is not None:
                self._thread.join(timeout)
            deadline = time.monotonic() + (timeout or 120.0)
            with self._cv:
                while any(c.state == _INFLIGHT for c in self.chunks):
                    if not self._cv.wait(max(0.0, min(
                            1.0, deadline - time.monotonic()))):
                        if time.monotonic() >= deadline:
                            break
                self._finish_critical()
                self._done_evt.set()
                self._cv.notify_all()
