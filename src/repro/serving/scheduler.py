"""Serverless platform control plane: event-driven, multi-tenant.

This is the control plane of Fig. 3, rebuilt around concurrency:

  * :class:`AsyncPlatform` — per-tenant request queues with admission
    control, a worker pool that serves *different* instances in parallel
    (per-instance locks keep each state machine race-free), and a
    background policy daemon that owns keep-alive deflation (④ SIGSTOP),
    memory-pressure handling, and predictive/anticipatory wakes (⑤
    SIGCONT).  ``submit`` returns a future; workers batch whatever is
    queued per tenant when they claim it (continuous batching).
  * :class:`Platform` — the original synchronous facade, kept as a thin
    compatibility shim: ``step()`` drains the queues inline and
    ``tick()`` runs one policy pass, with no threads involved.

Wake storms are deduplicated below the platform: every inflate routes
through ``InstanceManager.ensure_awake``, so N concurrent requests to
one hibernating tenant share a single batched (vectored) inflate.

The policy is intentionally simple (LRU deflate / TTL), matching the
paper's platform assumptions; FaasCache-style smarter keep-alive is noted
as related work, not reproduced.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Deque, Dict, List, Optional, Set, Tuple

from repro.core.forecast import ForecastDaemon
from repro.core.state import RUNG_OF, ContainerState, Rung
from repro.serving.engine import (SLO_BATCH, Request, Response,
                                  ServingEngine, TenantMigrated)

S = ContainerState


class AdmissionError(RuntimeError):
    """A tenant's queue is full: the request was rejected at admission.

    ``retry_after_s`` is the platform's backoff hint — predicted wake
    cost of the tenant's current rung plus the queued work ahead of the
    rejected request (what a gateway surfaces as ``Retry-After``)."""

    def __init__(self, msg: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


@dataclass
class PlatformPolicy:
    keep_warm_s: float = 5.0            # idle time before deflation (④)
    memory_target_bytes: Optional[int] = None
    deflate_instead_of_evict: bool = True   # the paper's knob: off = classic
    predictive_wake: bool = False           # ⑤ wake on queue arrival
    #: anticipatory wake (⑤, "platform predicts a request"): wake a
    #: hibernated tenant when the EWMA of its inter-arrival gap says the
    #: next request is due within this margin (seconds); None disables
    anticipate_margin_s: Optional[float] = None
    ewma_alpha: float = 0.3
    #: admission control: max queued requests per tenant before rejection
    max_queue_depth: int = 64
    #: admission for batch-SLO requests; None inherits max_queue_depth.
    #: Under pressure the gateway sheds batch first, so a lower batch
    #: depth keeps background work from starving interactive admission
    max_queue_depth_batch: Optional[int] = None
    #: cadence of the background policy daemon (AsyncPlatform only)
    tick_interval_s: float = 0.05


class AsyncPlatform:
    """Event-driven single-node serverless platform over a
    :class:`ServingEngine`.

    ``arch_of``: instance id -> arch key for the engine factory (requests
    are keyed by instance id; cold starts look the arch up here).

    Use as a context manager (or call ``start()``/``stop()``)::

        with AsyncPlatform(engine, policy, arch_of, workers=4) as plat:
            futs = [plat.submit(req) for req in reqs]
            resps = [f.result() for f in futs]
    """

    def __init__(self, engine: ServingEngine, policy: PlatformPolicy,
                 arch_of: Dict[str, str], workers: int = 4):
        self.engine = engine
        self.policy = policy
        self.arch_of = arch_of
        self.workers = workers
        #: per-tenant FIFO of (request, future, monotonic time queued);
        #: insertion-ordered dict
        self.queues: Dict[str, Deque[Tuple[Request, Future, float]]] = {}
        #: claimed future -> seconds its request waited in the queue,
        #: until ``_serve`` puts it on the response as ``serve.queue``
        self._queued_s: Dict[Future, float] = {}
        self._cv = threading.Condition()
        self._busy: Set[str] = set()          # tenants claimed by a worker
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self.log: List[tuple] = []
        # ONE arrival model for the whole node: the governor owns the
        # per-tenant EWMA; anticipatory wakes and victim selection read
        # the same prediction.  The platform policy's alpha applies only
        # when the user did not configure the governor explicitly — an
        # explicit GovernorConfig wins.
        if engine.manager.cfg.governor_policy is None:
            engine.manager.governor.cfg.ewma_alpha = policy.ewma_alpha
        # every eviction (keep-alive OR governor TERMINATED) must drop
        # this platform's per-tenant queue entry and serve lock
        engine.manager.on_evict = self._forget_tenant
        self.rejected = 0
        #: EWMA of per-request service seconds (feeds retry-after hints)
        self._service_ewma = 0.05
        #: cluster hook: ``reroute(iid, reqs, futs) -> bool`` takes over a
        #: batch whose tenant migrated off this node (the router resolves
        #: the futures against the target node).  Without it, stragglers
        #: fail with :class:`TenantMigrated` on their futures.
        self.reroute = None
        #: forecast control plane: created lazily on the first policy
        #: pass that sees the governor running a TrafficForecaster
        #: (``GovernorConfig.forecast``); None in the reactive world
        self._forecast_daemon: Optional[ForecastDaemon] = None

    @property
    def arrivals(self) -> Dict[str, tuple]:
        """Per-tenant arrival model (last_arrival_ts, ewma_gap_s) —
        owned by the manager's MemoryGovernor."""
        return self.engine.manager.governor.arrivals

    # ------------------------------------------------------------- lifecycle
    def start(self) -> "AsyncPlatform":
        if self._threads:
            return self
        self._stop.clear()
        for i in range(self.workers):
            t = threading.Thread(target=self._worker_loop,
                                 name=f"platform-worker-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        t = threading.Thread(target=self._daemon_loop,
                             name="platform-daemon", daemon=True)
        t.start()
        self._threads.append(t)
        return self

    def stop(self, drain: bool = True, timeout: float = 60.0) -> None:
        if drain:
            self.drain(timeout)
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout)
        self._threads = []

    def drain(self, timeout: float = 60.0) -> bool:
        """Block until every queued request has been served (or timeout).
        Returns True if fully drained."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while any(self.queues.values()) or self._busy:
                if not self._cv.wait(min(0.1, max(0.0, deadline -
                                                  time.monotonic()))):
                    if time.monotonic() >= deadline:
                        return False
        return True

    def __enter__(self) -> "AsyncPlatform":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------- requests
    def submit(self, req: Request, now: Optional[float] = None) -> Future:
        """Enqueue a request; returns a future resolving to its
        :class:`Response` (or raising :class:`AdmissionError` if the
        tenant's queue is full)."""
        fut: Future = Future()
        now = now if now is not None else time.monotonic()
        depth = self.policy.max_queue_depth
        if req.slo == SLO_BATCH and \
                self.policy.max_queue_depth_batch is not None:
            depth = self.policy.max_queue_depth_batch
        with self._cv:
            q = self.queues.setdefault(req.instance_id, deque())
            if len(q) >= depth:
                self.rejected += 1
                self.log.append((now, "rejected", req.instance_id))
                fut.set_exception(AdmissionError(
                    f"tenant {req.instance_id}: {req.slo} queue depth "
                    f">= {depth}",
                    retry_after_s=self.retry_after_s(req.instance_id)))
                return fut
            q.append((req, fut, time.monotonic()))
            self._note_arrival(req.instance_id, now)
            self._cv.notify()
        if self.policy.predictive_wake:
            # ⑤ request arrival wakes a hibernated tenant off the serve
            # path — the streamed pipeline at low priority; the request
            # that triggered it is absorbed mid-stream via demand-pull
            if self.engine.manager.ensure_awake(
                    req.instance_id, trigger="sigcont",
                    priority="low") is not None:
                self.log.append((now, "predictive_wake", req.instance_id))
        return fut

    def fail_pending(self, exc: BaseException) -> int:
        """Crash path (``Node.kill``): resolve every queued future with
        ``exc`` and empty the queues.  Requests already claimed by a
        worker fail on their own when the engine call errors; the point
        here is that nothing stays parked waiting for a node that will
        never serve again.  Returns the number of requests failed."""
        failed = 0
        with self._cv:
            for q in self.queues.values():
                while q:
                    _, fut, _ = q.popleft()
                    if not fut.done():
                        fut.set_exception(exc)
                    failed += 1
            self._cv.notify_all()
        return failed

    def _forget_tenant(self, iid: str) -> None:
        """Drop an evicted tenant's empty queue and serve lock; both are
        recreated on the next submit/cold-start."""
        with self._cv:
            q = self.queues.get(iid)
            if q is not None and not q:
                del self.queues[iid]
        self.engine.drop_instance_lock(iid)

    def _note_arrival(self, iid: str, now: float) -> None:
        self.engine.manager.governor.observe_arrival(iid, now)

    def retry_after_s(self, iid: str) -> float:
        """Backoff hint for a rejected request: the tenant's predicted
        wake cost at its current rung (per-rung EWMA the governor
        learned) plus the queue ahead at the measured per-request
        service rate.  This is what makes a gateway 429 honest — the
        client comes back when the node can plausibly serve it."""
        mgr = self.engine.manager
        wake = 0.0
        inst = mgr.instances.get(iid)
        if inst is not None:
            rung = RUNG_OF.get(inst.state, Rung.WARM)
            if rung != Rung.WARM:
                wake = mgr.governor.wake_cost(rung)
        with self._cv:
            depth = len(self.queues.get(iid, ()))
        return max(0.05, wake + depth * self._service_ewma)

    # ------------------------------------------------------------- serving
    def _claim(self):
        """With ``_cv`` held: pop the whole queue of the first unclaimed
        tenant with work (one claim = one continuous batch).  Tenants
        whose queue head is interactive-SLO are claimed before tenants
        with only batch work — the gateway's SLO classes reach the
        worker pool here."""
        batch_pick = None
        for iid, q in self.queues.items():
            if not q or iid in self._busy:
                continue
            if q[0][0].slo == SLO_BATCH:
                if batch_pick is None:
                    batch_pick = iid
                continue
            return self._claim_tenant(iid)
        if batch_pick is not None:
            return self._claim_tenant(batch_pick)
        return None

    def _claim_tenant(self, iid: str):
        q = self.queues[iid]
        reqs, futs = [], []
        now = time.monotonic()
        while q:
            r, f, queued = q.popleft()
            reqs.append(r)
            futs.append(f)
            self._queued_s[f] = now - queued
        self._busy.add(iid)
        return iid, reqs, futs

    def _worker_loop(self) -> None:
        while True:
            with self._cv:
                claim = self._claim()
                while claim is None:
                    if self._stop.is_set():
                        return
                    self._cv.wait(0.1)
                    claim = self._claim()
            iid, reqs, futs = claim
            try:
                self._serve(iid, reqs, futs)
            finally:
                with self._cv:
                    self._busy.discard(iid)
                    self._cv.notify_all()

    def _serve(self, iid: str, reqs: List[Request],
               futs: List[Future]) -> None:
        with self._cv:
            queued = [self._queued_s.pop(f, None) for f in futs]
        try:
            mgr = self.engine.manager
            if iid not in mgr.instances and iid not in mgr.migrated:
                # first request of an unknown tenant: specialize a zygote
                # (warm fork) when the pool holds one for this family;
                # fall back to the classic cold init otherwise
                arch = self.arch_of[iid]
                if self.engine.fork_instance(iid, arch) is not None:
                    self.log.append((time.monotonic(), "fork_start", iid))
                else:
                    self.engine.start_instance(iid, arch)
                    self.log.append((time.monotonic(), "cold_start", iid))
            t0 = time.monotonic()
            resps = self.engine.serve_batch(iid, reqs)
            per_req = (time.monotonic() - t0) / max(len(reqs), 1)
            self._service_ewma += 0.3 * (per_req - self._service_ewma)
            for f, r, q in zip(futs, resps, queued):
                if q is not None:
                    r.spans["serve.queue"] = q
                f.set_result(r)
        except TenantMigrated as e:
            # the tenant lives on another node now: hand the batch to the
            # cluster router (it resolves the futures against the target)
            if self.reroute is not None and self.reroute(iid, reqs, futs):
                self.log.append((time.monotonic(), "rerouted", iid))
                return
            for f in futs:
                if not f.done():
                    f.set_exception(e)
        except BaseException as e:
            for f in futs:
                if not f.done():
                    f.set_exception(e)

    # ------------------------------------------------------------- policy
    def _daemon_loop(self) -> None:
        while not self._stop.wait(self.policy.tick_interval_s):
            try:
                self.policy_pass()
            except Exception as e:       # policy must never kill the daemon
                self.log.append((time.monotonic(), "policy_error", repr(e)))

    def policy_pass(self, now: Optional[float] = None) -> List[str]:
        """One policy sweep: keep-alive deflation (or eviction), memory
        pressure, anticipatory wakes.  Instances currently serving are
        skipped via non-blocking per-instance locks."""
        now = now if now is not None else time.monotonic()
        mgr = self.engine.manager
        acted = []
        # every rung above HIBERNATE ages out: a tenant the governor
        # parked at MMAP_CLEAN/PARTIAL during a transient breach must not
        # pin its resident prefix forever once pressure clears
        idle_states = (S.WARM, S.WOKEN, S.MMAP_CLEAN, S.PARTIAL)
        for iid, inst in list(mgr.instances.items()):
            idle = now - inst.last_used
            if inst.state not in idle_states or \
                    idle <= self.policy.keep_warm_s:
                continue
            lock = self.engine.instance_lock(iid)
            if not lock.acquire(blocking=False):
                continue                       # in-flight request: not idle
            try:
                if inst.state not in idle_states:
                    continue
                if self.policy.deflate_instead_of_evict:
                    mgr.descend(iid, Rung.HIBERNATED)
                    self.log.append((now, "deflate", iid))
                else:
                    mgr.evict(iid)         # on_evict hook forgets the tenant
                    self.log.append((now, "evict", iid))
                acted.append(iid)
            finally:
                lock.release()
        # memory pressure: the governor walks victims down the deflation
        # ladder (cost/benefit, proportional reclaim).  The platform-level
        # target (if set) overrides the manager's configured node budget.
        if self.policy.memory_target_bytes is not None or \
                mgr.cfg.memory_budget_bytes is not None:
            acted += mgr.handle_memory_pressure(
                self.policy.memory_target_bytes,
                try_lock=self.engine.instance_lock, now=now)
        # ⑤ anticipatory SIGCONT: wake tenants whose EWMA inter-arrival
        # model predicts a request within the margin.  These run the SAME
        # streamed wake pipeline as request-driven wakes, at low priority
        # (no read double-buffering, yields between chunks) — a request
        # landing mid-stream is absorbed by demand-pulling its chunks
        if self.policy.anticipate_margin_s is not None:
            for iid, inst in list(mgr.instances.items()):
                if inst.state not in (S.HIBERNATE, S.PARTIAL, S.MMAP_CLEAN):
                    continue
                last, gap = self.arrivals.get(iid, (None, None))
                if last is None or gap is None:
                    continue
                due_in = (last + gap) - now
                if due_in <= self.policy.anticipate_margin_s:
                    if mgr.ensure_awake(iid, trigger="sigcont",
                                        priority="low") is not None:
                        self.log.append((now, "anticipated_wake", iid))
                        acted.append(iid)
        # forecast-driven pre-inflate: with a TrafficForecaster on the
        # governor, seasonal/flash-crowd predictions wake tenants (and
        # revive their spilled prefixes) *ahead* of the memoryless EWMA
        # above — the daemon rides the same policy cadence and the same
        # low-priority streamed wake pipeline
        if mgr.governor.forecaster is not None:
            if self._forecast_daemon is None:
                self._forecast_daemon = ForecastDaemon(mgr, self.arch_of)
            for iid in self._forecast_daemon.step(now):
                self.log.append((now, "forecast_wake", iid))
                acted.append(iid)
        # zygote TTL: retire donors idle past retire_idle_s even without
        # memory pressure (the governor handles the pressure-driven case)
        if mgr.zygotes is not None:
            for zid in mgr.zygotes.reap_idle(now):
                self.log.append((now, "zygote_retire", zid))
                acted.append(zid)
        return acted


class Platform(AsyncPlatform):
    """Synchronous compatibility shim over :class:`AsyncPlatform`.

    No threads: ``step()`` drains the per-tenant queues inline (grouped
    per instance for batching, as before) and ``tick()`` runs one policy
    pass.  ``submit`` still returns a future, already resolved by the
    time ``step()`` returns.
    """

    def __init__(self, engine: ServingEngine, policy: PlatformPolicy,
                 arch_of: Dict[str, str]):
        super().__init__(engine, policy, arch_of, workers=0)

    def submit(self, req: Request, now: Optional[float] = None) -> Future:
        """Like the async submit, but admission rejection raises
        immediately: legacy callers ignore the returned future, and a
        rejection parked on it would silently drop the request."""
        fut = super().submit(req, now)
        if fut.done() and fut.exception() is not None:
            raise fut.exception()
        return fut

    def step(self) -> List[Response]:
        """Drain the queues once (grouped per instance for batching)."""
        out: List[Response] = []
        while True:
            with self._cv:
                claim = self._claim()
            if claim is None:
                return out
            iid, reqs, futs = claim
            try:
                self._serve(iid, reqs, futs)
            finally:
                with self._cv:
                    self._busy.discard(iid)
            out.extend(f.result() for f in futs)

    def tick(self, now: Optional[float] = None) -> List[str]:
        """Apply keep-alive/pressure/anticipation policy once."""
        return self.policy_pass(now)
