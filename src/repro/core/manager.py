"""Multi-tenant InstanceManager: the "Serverless Platform" control plane.

Implements the platform-side behaviours of the paper:
  * cold start (①): init/load weights + compile — the expensive path;
  * keep-alive with *deflate-instead-of-evict* under memory pressure;
  * predictive wake (⑤) and request-driven wake (⑦), with a wake-storm
    guard: concurrent requests racing to inflate the same hibernating
    tenant share a single batched inflate (`ensure_awake`);
  * shared base-weight registry (§3.5): refcounted "file-backed" leaves,
    re-read from the checkpoint at refcount 0->1.

The manager is thread-safe for the AsyncPlatform's worker pool: the
instance table is lock-guarded and each instance has a wake lock.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core.governor import GovernorConfig, MemoryGovernor
from repro.core.hibernate import HibernationManager
from repro.core.inflate import InflatorPool
from repro.core.instance import ModelInstance
from repro.core.metrics import span
from repro.core.pool import PagePool
from repro.core.state import (DEFLATE_EVENT_FOR, ContainerState, Event,
                              Rung)
from repro.core.store import StorePolicy, SwapStore
from repro.core.prefix import PREFIX_OWNER, PrefixRegistry
from repro.core.zygote import ZygoteConfig, ZygotePool, is_zygote_id

#: ladder states a wake (request-driven or predictive) climbs out of
WAKEABLE_STATES = (ContainerState.HIBERNATE, ContainerState.PARTIAL,
                   ContainerState.MMAP_CLEAN)


class SharedWeightsRegistry:
    """Refcounted shared base weights (the runtime-binary mmap analogue).

    ``loader(base_id) -> {path: np.ndarray}`` plays the role of the backing
    file: dropping the weights at refcount zero costs nothing to write
    (file-backed pages are clean) but re-acquiring re-reads the checkpoint.
    """

    def __init__(self, loader: Callable[[str], Dict[str, np.ndarray]]):
        self.loader = loader
        self._weights: Dict[str, Dict[str, np.ndarray]] = {}
        self._refs: Dict[str, int] = {}
        self.reload_count = 0

    def acquire(self, base_id: str, inst: Optional[ModelInstance] = None
                ) -> Dict[str, np.ndarray]:
        """Incref ``base_id`` (loading at 0->1) and, when ``inst`` is
        given, map the shared buffers into its weight table — every
        sharer sees the *same* ndarrays, the mmap analogue."""
        if base_id not in self._weights:
            self._weights[base_id] = self.loader(base_id)
            self.reload_count += 1
        self._refs[base_id] = self._refs.get(base_id, 0) + 1
        w = self._weights[base_id]
        if inst is not None:
            for path, arr in w.items():
                inst.weights[path] = arr        # share the same buffers
        return w

    def release(self, base_id: str) -> int:
        """Decref; drop at zero.  Returns bytes released (0 if still shared)."""
        self._refs[base_id] -= 1
        if self._refs[base_id] > 0:
            return 0
        w = self._weights.pop(base_id, {})
        return sum(a.nbytes for a in w.values())

    def refcount(self, base_id: str) -> int:
        """Current sharer count for ``base_id`` (0 if never acquired)."""
        return self._refs.get(base_id, 0)

    def is_loaded(self, base_id: str) -> bool:
        """True while the shared buffers are resident (refcount > 0)."""
        return base_id in self._weights


@dataclass
class ManagerConfig:
    """Per-node sizing and policy for one :class:`InstanceManager`."""

    spool_dir: str = "/tmp/repro_spool"
    pool_capacity_pages: int = 1 << 15
    pool_page_elems: int = 16384
    keep_alive_s: float = 600.0          # warm keep-alive window
    memory_limit_bytes: Optional[int] = None
    share_base_weights: bool = True      # §3.5 policy knob
    wake_mode: str = "reap"              # "reap" | "pagefault"
    #: content-addressed swap tier (§3.4 de-dup table, cross-tenant).
    #: False falls back to PR-1 private per-sandbox SwapFiles.
    dedup_store: bool = True
    #: per-deployment hash salt; None generates a fresh random one
    store_salt: Optional[bytes] = None
    store_policy: Optional[StorePolicy] = None
    #: streamed wake pipeline (repro.core.inflate): ``ensure_awake``
    #: returns at the prefill-critical prefix while the tail inflates in
    #: the background.  False restores the fully-synchronous REAP wake.
    pipelined_wake: bool = True
    #: pipeline chunk size: one vectored read / one install per chunk —
    #: small enough that the critical prefix is not diluted by tail
    #: neighbours sharing its chunks, large enough to amortize syscalls
    wake_chunk_bytes: int = 256 << 10
    #: per-deployment inflator worker threads (read double-buffering +
    #: background lookahead fetches)
    inflate_workers: int = 3
    #: turn serviced faults into asynchronous next-layer prefetch
    lookahead: bool = True
    #: node-wide memory budget the :class:`~repro.core.governor.
    #: MemoryGovernor` enforces over ALL tenants (None = no budget: the
    #: governor only acts when a pressure target is passed explicitly)
    memory_budget_bytes: Optional[int] = None
    #: governor knobs (headroom, rung thresholds, terminate policy);
    #: None uses :class:`~repro.core.governor.GovernorConfig` defaults
    governor_policy: Optional[GovernorConfig] = None
    #: kept-alive metadata a hibernated husk is charged for (page tables,
    #: compiled handles).  The default is deliberately tiny; cluster
    #: benchmarks raise it to paper-realistic husk/warm ratios so the
    #: TERMINATED/MIGRATING economics have teeth.
    husk_metadata_bytes: int = 1 << 16
    #: deployment-wide resident KV prefix registry
    #: (:mod:`repro.core.prefix`): sessions whose prompt token-hash is
    #: registered COW-adopt the resident pages instead of prefilling
    prefix_sharing: bool = True
    #: prompts shorter than this never enter the registry
    prefix_min_tokens: int = 4
    #: background store-scrub cadence: every ``scrub_interval_s`` the
    #: store CRC-verifies up to ``scrub_bytes_per_round`` of segments,
    #: quarantining corruption (and repairing it from replica peers when
    #: the cluster router has installed a ``repair_source``).  None
    #: disables the daemon; requires ``dedup_store``.
    scrub_interval_s: Optional[float] = None
    scrub_bytes_per_round: int = 64 << 20
    #: zygote fork donors (:mod:`repro.core.zygote`): a
    #: :class:`~repro.core.zygote.ZygoteConfig` keeps a pool of
    #: pre-initialized per-family instances so a brand-new tenant is
    #: admitted by warm fork instead of cold init; None disables the pool
    #: (``fork_start`` then always falls back to ``cold_start``)
    zygote_pool: Optional[ZygoteConfig] = None


class InstanceManager:
    """The per-node "Serverless Platform" control plane: owns the
    instance table, the shared-weight registry, the swap/CAS tier, the
    wake pipeline, and the memory governor.  Tenants enter via
    ``cold_start`` or ``fork_start``, descend the deflation ladder via
    ``descend``, and wake via ``ensure_awake``; all entry points are
    safe under the AsyncPlatform's worker pool."""

    def __init__(self, cfg: ManagerConfig,
                 factory: Callable[[str], tuple],
                 shared_loader: Optional[Callable] = None):
        """``factory(arch_key) -> (model_cfg, params_pytree)`` builds a cold
        instance (init or checkpoint load) — the expensive cold-start work."""
        self.cfg = cfg
        self.factory = factory
        self.pool = PagePool(cfg.pool_page_elems, np.float32,
                             cfg.pool_capacity_pages)
        self.shared = (SharedWeightsRegistry(shared_loader)
                       if (shared_loader and cfg.share_base_weights) else None)
        self.store = (SwapStore(f"{cfg.spool_dir}/store.cas",
                                salt=cfg.store_salt,
                                policy=cfg.store_policy)
                      if cfg.dedup_store else None)
        if self.store is not None and cfg.scrub_interval_s is not None:
            self.store.start_scrubber(
                interval_s=cfg.scrub_interval_s,
                bytes_per_round=cfg.scrub_bytes_per_round)
        self.inflator = InflatorPool(cfg.inflate_workers)
        self.prefix_registry = (PrefixRegistry(
            self.pool, self.store, salt=cfg.store_salt,
            min_tokens=cfg.prefix_min_tokens)
            if cfg.prefix_sharing else None)
        self.hib = HibernationManager(self.shared, inflator=self.inflator,
                                      wake_chunk_bytes=cfg.wake_chunk_bytes)
        self.instances: Dict[str, ModelInstance] = {}
        self.governor = MemoryGovernor(
            self, budget_bytes=cfg.memory_budget_bytes,
            cfg=cfg.governor_policy)
        self._lock = threading.RLock()                 # instance table
        self._wake_locks: Dict[str, threading.Lock] = {}
        #: tenants migrated off this node -> target node id, so straggler
        #: requests raise ``TenantMigrated`` (rerouted by the cluster
        #: router) instead of cold-starting a duplicate here.  Entries are
        #: dropped if the tenant ever migrates back (``admit``).
        self.migrated: Dict[str, str] = {}
        #: wake-storm accounting: inflates actually performed vs callers
        #: that arrived wanting one and found it already done/in flight
        self.wakes_performed = 0
        self.wakes_deduped = 0
        #: zygote fork donors; None when the pool is not configured
        self.zygotes: Optional[ZygotePool] = \
            ZygotePool(self, cfg.zygote_pool) \
            if cfg.zygote_pool is not None else None
        #: fork-storm accounting, mirroring the wake counters: forks
        #: actually performed vs callers that found the tenant already
        #: admitted by a concurrent fork
        self.forks_performed = 0
        self.forks_deduped = 0
        #: eviction hook the platform layer registers so governor-driven
        #: TERMINATED descents also drop its per-tenant state (request
        #: queue entry, engine serve lock) — without it, tenant churn
        #: under terminate_idle_s grows those tables unboundedly
        self.on_evict: Optional[Callable[[str], None]] = None

    def _wake_lock(self, instance_id: str) -> threading.Lock:
        with self._lock:
            lock = self._wake_locks.get(instance_id)
            if lock is None:
                lock = self._wake_locks[instance_id] = threading.Lock()
            return lock

    # ------------------------------------------------------------- lifecycle
    def cold_start(self, instance_id: str, arch_key: str,
                   shared_paths=None) -> ModelInstance:
        """① Admit a tenant the expensive way: run the factory (init or
        checkpoint load), acquire the shared base weights, and enter the
        state graph through ``COLD_START`` — the path ``fork_start``
        exists to avoid.  Returns the installed instance."""
        model_cfg, params = self.factory(arch_key)
        inst = ModelInstance(
            instance_id, model_cfg, params, pool=self.pool,
            spool_dir=self.cfg.spool_dir,
            shared_paths=shared_paths if self.shared else None,
            base_id=arch_key if self.shared else None,
            store=self.store,
            metadata_bytes=self.cfg.husk_metadata_bytes,
            arch_key=arch_key)
        if self.shared and inst.base_id and inst.shared_paths:
            self.shared.acquire(inst.base_id, inst)
        inst.sm.fire(Event.COLD_START)
        with self._lock:
            self.instances[instance_id] = inst
        if self.zygotes is not None and not is_zygote_id(instance_id):
            # a cold start IS a new-tenant admission the pool missed —
            # it trains the same fork-avoidance signal a fork does
            self.zygotes.note_admission(arch_key)
        return inst

    def fork_start(self, instance_id: str, arch_key: str,
                   shared_paths=None) -> Optional[ModelInstance]:
        """Admit a brand-new tenant by specializing a zygote (warm fork).

        Returns None when no pool is configured or no live zygote of the
        family exists — the caller falls back to ``cold_start``.  The
        fork order is refcount-safe: the tenant acquires its own
        shared-registry ref *before* the donor's is released, so the
        shared base never dips to refcount 0 (no checkpoint re-read, and
        retiring the donor can never free a forked tenant's pages).  The
        tenant inherits the donor's compiled executables (same family ⇒
        same model config object from the factory cache) and copies its
        anonymous weights — a memcpy, not an init.  Concurrent callers
        for one tenant dedup on the per-instance wake lock: exactly one
        fork happens, late arrivals get the installed instance
        (``forks_deduped``).
        """
        if self.zygotes is None:
            return None
        with self._wake_lock(instance_id):
            with self._lock:
                existing = self.instances.get(instance_id)
            if existing is not None:
                self.forks_deduped += 1
                return existing
            zyg = self.zygotes.take(arch_key)
            if zyg is None:
                return None
            inst = ModelInstance(
                instance_id, zyg.cfg, zyg.params_pytree(), pool=self.pool,
                spool_dir=self.cfg.spool_dir,
                shared_paths=shared_paths if self.shared else None,
                base_id=arch_key if self.shared else None,
                store=self.store,
                metadata_bytes=self.cfg.husk_metadata_bytes,
                arch_key=arch_key)
            if self.shared and inst.base_id and inst.shared_paths:
                self.shared.acquire(inst.base_id, inst)
            inst.compiled = zyg.compiled
            inst.sm.fire(Event.FORK)
            with self._lock:
                self.instances[instance_id] = inst
            self._consume_zygote(zyg)
            self.zygotes.note_admission(arch_key)
            self.zygotes.forked += 1
            self.forks_performed += 1
            return inst

    def _consume_zygote(self, zyg: ModelInstance) -> None:
        # the donor dies by being forked: (ZYGOTE, FORK) -> DEAD.  Its
        # shared ref is released AFTER the tenant took one (fork_start
        # ordering), so release never drops the base to zero here.
        zid = zyg.instance_id
        with self._lock:
            self.instances.pop(zid, None)
            self._wake_locks.pop(zid, None)
        self.hib._release_mmap(zyg)
        zyg.sm.fire(Event.FORK)
        zyg.terminate()
        self.governor.forget(zid)
        if self.zygotes is not None:
            self.zygotes.note_evicted(zid)

    def descend(self, instance_id: str, rung, *, keys=None):
        """Walk one tenant down the deflation ladder to ``rung``.

        The single rung-addressed entry point the governor, router, and
        gateway all speak — ``rung`` is a :class:`~repro.core.state.Rung`
        and dispatch is validated against ``DEFLATE_EVENT_FOR`` (an
        unreachable rung for the tenant's current state raises
        ``InvalidTransition`` from the state machine, exactly like the
        underlying event would).

        * ``Rung.MMAP_CLEAN`` — drop the clean file-backed mmap bytes.
        * ``Rung.PARTIAL`` — swap out ``keys`` (cold unit keys); when
          ``keys`` is None the governor's partial-victim scan picks the
          coldest units, so callers without their own victim policy get
          the ladder's.
        * ``Rung.HIBERNATED`` — full deflate (working set to REAP +
          store, host state dropped).
        * ``Rung.TERMINATED`` — evict: the container is destroyed.

        Returns the rung's ``DeflateStats`` (``None`` for TERMINATED).
        """
        rung = Rung(rung)
        if rung not in DEFLATE_EVENT_FOR:
            raise ValueError(f"{rung!r} is not a deflation target")
        inst = self.instances[instance_id]
        # no request owns a deflate: the span is a trace annotation only
        with span("ladder.deflate", tenant=instance_id,
                  rung=rung.name.lower()):
            if rung == Rung.TERMINATED:
                self.evict(instance_id)
                return None
            if rung == Rung.MMAP_CLEAN:
                st = self.hib.deflate_mmap(inst)
            elif rung == Rung.PARTIAL:
                if keys is None:
                    keys = [k for _, _, k in
                            self.governor._partial_candidates(inst)]
                st = self.hib.deflate_partial(inst, keys)
            else:
                st = self.hib.deflate(inst)
        # every descent path (governor pressure, keep-alive, router)
        # accumulates the tenant's wake footprint — what a pre-inflate
        # or the elasticity demand model expects the wake to re-occupy;
        # observe_wake resets it when the bytes come back
        gov = self.governor
        gov.footprint[instance_id] = gov.footprint.get(instance_id, 0) \
            + st.swap_bytes + st.shared_bytes_released
        return st

    def ensure_awake(self, instance_id: str, trigger: str = "request",
                     priority: Optional[str] = None):
        """Inflate a hibernating instance exactly once per storm.

        Any number of threads may call this concurrently for the same
        instance (request-driven ⑦ and predictive ⑤ wakes both route
        here); the per-instance wake lock guarantees a single batched
        inflate, and late arrivals are counted in ``wakes_deduped``.
        Returns the :class:`WakeStats` for the thread that performed the
        inflate, ``None`` for everyone else.

        With the pipelined wake the performer returns as soon as the
        prefill-critical prefix is resident; late arrivals (and the
        engine's fault path) find the in-flight stream handle on
        ``inst.wake_pipeline`` and demand-pull from it rather than issuing
        their own reads.  Anticipatory wakes (``trigger="sigcont"``) run
        the same pipeline at low priority unless overridden.
        """
        inst = self.instances.get(instance_id)
        if inst is not None and inst.state == ContainerState.MIGRATING:
            # in-flight-request handoff: block on the transfer handle the
            # way late wake arrivals block on the shared wake pipeline.
            # When it completes the tenant lives on the target node (or
            # aborted back to HIBERNATE) — the caller re-resolves.
            handle = inst.migration
            self.wakes_deduped += 1
            if handle is not None:
                handle.wait()
            return None
        if inst is None or inst.state not in WAKEABLE_STATES:
            return None
        if priority is None:
            priority = "low" if trigger == "sigcont" else "high"
        with self._wake_lock(instance_id):
            state = inst.state
            if state not in WAKEABLE_STATES:
                self.wakes_deduped += 1        # someone else woke it first
                return None
            if state in (ContainerState.HIBERNATE, ContainerState.PARTIAL) \
                    and inst.inflated:
                self.wakes_deduped += 1        # someone else inflated first
                return None
            if state == ContainerState.MMAP_CLEAN and not inst.mmap_dropped:
                self.wakes_deduped += 1        # someone else re-mapped first
                return None
            if trigger == "request" and state == ContainerState.HIBERNATE \
                    and self.cfg.wake_mode != "reap":
                # pagefault mode: units fault in lazily.  Still mark the
                # cycle as woken under the wake lock, or a racing sigcont
                # wake could fire after the engine's REQUEST transition.
                inst.inflated = True
                return None
            self.wakes_performed += 1
            st = self.hib.wake(inst, mode=self.cfg.wake_mode,
                               trigger=trigger,
                               pipelined=self.cfg.pipelined_wake,
                               priority=priority)
            # the governor learns measured per-rung wake costs from here
            self.governor.observe_wake(instance_id, st)
            return st

    def predictive_wake(self, instance_id: str, priority: str = "low"):
        """⑤ control-plane wake in anticipation of a request — the
        streamed pipeline at low priority (no read double-buffering,
        yields between chunks): a real request arriving mid-stream is
        absorbed by the same pipeline via demand-pull."""
        return self.ensure_awake(instance_id, trigger="sigcont",
                                 priority=priority)

    # ------------------------------------------------------------- cluster
    def detach(self, instance_id: str, target: Optional[str] = None) -> None:
        """Migration commit on the *source* node: drop the instance from
        the table without firing EVICT (the state machine already walked
        MIGRATE -> MIGRATE_DONE -> DEAD) and remember where it went so a
        straggler request can be rerouted.  The caller owns releasing the
        instance's disk state (swap-store refs, REAP file)."""
        with self._lock:
            self.instances.pop(instance_id, None)
            self._wake_locks.pop(instance_id, None)
            if target is not None:
                self.migrated[instance_id] = target
        if self.prefix_registry is not None:
            self.prefix_registry.forget_owner(instance_id)
        self.governor.forget(instance_id)
        if self.on_evict is not None:
            self.on_evict(instance_id)

    def admit(self, inst: ModelInstance) -> None:
        """Migration commit on the *target* node: install a rebuilt
        instance (hibernated: weights/KV are digests in this node's store,
        REAP file rebuilt, recorder state shipped)."""
        with self._lock:
            self.instances[inst.instance_id] = inst
            self.migrated.pop(inst.instance_id, None)

    def evict(self, instance_id: str) -> None:
        """TERMINATED: destroy the container — release its shared mmap
        ref, its prefix sharer slots, and its swap files (§3.4); zygotes
        retire through here too (``(ZYGOTE, EVICT) -> DEAD``)."""
        with self._lock:
            inst = self.instances.pop(instance_id)
            self._wake_locks.pop(instance_id, None)
        # refcount-balanced: a ladder descent (mmap_clean/partial/full
        # deflate) already released the shared mmap; the flag knows
        self.hib._release_mmap(inst)
        inst.sm.fire(Event.EVICT)
        # release the evicted tenant's prefix sharer slots BEFORE terminate
        # frees its pool owner: a last-sharer-down spill must still find
        # the registry's own refs alive to content-address the pages
        if self.prefix_registry is not None:
            self.prefix_registry.forget_owner(instance_id)
        inst.terminate()                       # swap files deleted (§3.4)
        self.governor.forget(instance_id)
        if self.zygotes is not None:
            self.zygotes.note_evicted(instance_id)
        if self.on_evict is not None:
            self.on_evict(instance_id)

    # ------------------------------------------------------------- policy
    def resident_bytes(self) -> int:
        """Deployment-wide resident application bytes, PSS-accounted:
        private weights + proportional pool shares per tenant, shared
        base weights once per loaded ``base_id``, the prefix registry's
        own pinned share once."""
        tot = 0
        seen_shared = set()
        with self._lock:
            insts = list(self.instances.values())
        for inst in insts:
            tot += inst.weight_bytes(resident_only=True, include_shared=False)
            # PSS, not RSS: prefix pages COW-adopted by several tenants
            # (and pinned by the registry itself) are charged one
            # proportional share per mapper, never once per mapper in full
            tot += int(inst.pool.pss_bytes(inst.instance_id))
        if self.prefix_registry is not None:
            tot += int(self.pool.pss_bytes(PREFIX_OWNER))
        for inst in insts:
            if self.shared and inst.base_id and \
                    inst.base_id not in seen_shared and \
                    self.shared.is_loaded(inst.base_id) and inst.shared_paths:
                tot += inst.shared_weight_bytes()
                seen_shared.add(inst.base_id)
        return tot

    def handle_memory_pressure(self, target_bytes: Optional[int] = None,
                               try_lock: Optional[Callable] = None,
                               now: Optional[float] = None) -> List[str]:
        """Reclaim memory down to a target by walking victims down the
        deflation ladder — delegates to the :class:`MemoryGovernor`
        (cost/benefit victim selection, proportional reclaim).

        ``target_bytes=None`` uses the configured node budget
        (``ManagerConfig.memory_budget_bytes``); passing a value enforces
        a one-off target.  ``try_lock(instance_id)`` (optional) must
        return a lock to acquire non-blocking around each deflate;
        instances currently being served are skipped instead of racing
        the engine's state machine.  Returns the ids acted on.
        """
        actions = self.governor.step(now=now, try_lock=try_lock,
                                     budget_bytes=target_bytes)
        acted = list(dict.fromkeys(a.instance_id for a in actions))
        return acted

    def states(self) -> Dict[str, str]:
        """``{instance_id: state value}`` snapshot of the table."""
        with self._lock:
            return {k: v.state.value for k, v in self.instances.items()}
