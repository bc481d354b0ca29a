"""Zygote pool: pre-initialized fork donors for brand-new tenants.

Hibernation only helps tenants that have run at least once — a brand-new
tenant still pays the full cold init (factory load + prefill compile)
the deflated-container design exists to avoid.  Following Pagurus
(arXiv:2108.11240, re-purposing *other* functions' idle containers) and
HotSwap (arXiv:2409.09202, live-sharing initialized dependencies), the
:class:`ZygotePool` keeps a small set of pre-initialized per-model-family
**zygote** instances:

* base weights adopted by refcount from the shared registry (the same
  §3.5 mmap analogue every tenant shares);
* compiled prefill handles pre-built by the engine's precompile hook,
  so the fork inherits warm executables;
* governor-charged: a zygote sits on the ladder as a first-class
  ``ZYGOTE`` state, and under pressure the :class:`~repro.core.governor.
  MemoryGovernor` retires it like any other instance — scored by its
  bytes against its *fork-avoidance* value (the predicted gap until the
  family's next new-tenant admission over the cold-start wake prior).

``InstanceManager.fork_start`` consumes a zygote to admit a new tenant:
the tenant takes its own shared-registry ref *before* the donor releases
(refcount isolation — retiring a zygote never frees a forked tenant's
shared pages), copies the donor's anonymous weights (a memcpy, not an
init), inherits the compiled handles, and enters the state graph through
``(COLD, FORK) -> WARM`` so its history records a warm fork, not a cold
start.  Tenant deltas (tuned weights, KV prefixes, session state) still
arrive through the existing CAS-store / streamed-wake machinery — the
fork replaces only the cold init.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

from repro.core.instance import ModelInstance
from repro.core.state import ContainerState, Event

#: forecaster key namespace for per-family new-tenant arrival streams
#: (synthetic keys so the seasonal model can learn "new tenants of this
#: family show up every morning" without colliding with tenant ids)
NEW_TENANT_KEY = "__newtenant__:"

#: instance-id namespace for zygotes (filename-safe: ids name REAP/spool
#: files, and arch keys contain no path separators)
_ZYGOTE_PREFIX = "__zygote__."


def zygote_id(family: str, seq: int) -> str:
    """The pool's instance-id scheme for a zygote of ``family``."""
    return f"{_ZYGOTE_PREFIX}{family}.{seq}"


def is_zygote_id(instance_id: str) -> bool:
    """True when ``instance_id`` names a pool zygote, not a tenant."""
    return instance_id.startswith(_ZYGOTE_PREFIX)


@dataclass
class ZygoteConfig:
    """Pool sizing and fork-economics knobs."""
    #: live zygotes kept per model family
    per_family: int = 1
    #: hard cap on live zygotes across all families
    max_total: int = 8
    #: charge zygote bytes (anon weights + metadata) to the governor's
    #: budget.  False exempts them — shared base weights stay charged
    #: (tenants share those buffers), and the governor can still retire
    #: a zygote under pressure; only the accounting changes.
    charge_governor: bool = True
    #: the forecast daemon pre-forks a family whose predicted next
    #: new-tenant admission falls within this margin
    prefork_margin_s: float = 10.0
    #: EWMA smoothing for per-family new-tenant inter-admission gaps
    fork_gap_alpha: float = 0.3
    #: retire a zygote idle (unforked) this long even without memory
    #: pressure; None leaves retirement to the governor alone
    retire_idle_s: Optional[float] = None
    #: predicted fork gap for a family with no admission history — large,
    #: so unknown families never outrank tenants in governor scoring
    default_gap_s: float = 3600.0
    #: prompt lengths whose prefill executables the engine pre-builds at
    #: spawn (the compile a forked tenant's first request then skips)
    precompile_prompt_lens: Tuple[int, ...] = (8,)


class ZygotePool:
    """Per-manager pool of pre-initialized fork donors.

    Thread-safe: the pool lock guards its own bookkeeping; instance-table
    mutations go through the owning :class:`~repro.core.manager.
    InstanceManager`'s APIs.  Zygotes live in ``manager.instances`` like
    any tenant (the governor sees and prices them); the pool tracks which
    ids are donors and for which family.
    """

    def __init__(self, manager, cfg: Optional[ZygoteConfig] = None):
        """``manager`` is the owning InstanceManager (not imported to
        avoid a cycle); ``cfg`` defaults to :class:`ZygoteConfig`."""
        self.manager = manager
        self.cfg = cfg or ZygoteConfig()
        self._lock = threading.RLock()
        #: family -> list of live zygote ids (oldest first)
        self._by_family: Dict[str, List[str]] = {}
        self._spawned_at: Dict[str, float] = {}
        #: family -> (last_admission_ts, ewma_gap_s)
        self._admissions: Dict[str, Tuple[float, Optional[float]]] = {}
        #: family -> shared paths remembered from the last spawn/fork, so
        #: a forecast-driven pre-fork spawns donors with the same sharing
        self._shared_paths: Dict[str, Optional[frozenset]] = {}
        #: family -> last pre-fork decision ts (one-margin cooldown)
        self._last_prefork: Dict[str, float] = {}
        self._seq = 0
        #: engine-installed hook ``precompile(inst)`` that pre-builds the
        #: prefill executables a forked tenant inherits
        self.precompile: Optional[Callable[[ModelInstance], None]] = None
        self.spawned = 0
        self.forked = 0
        self.retired = 0

    # ------------------------------------------------------------- spawn
    def spawn(self, family: str, shared_paths=None
              ) -> Optional[ModelInstance]:
        """Pre-initialize one zygote for ``family`` (cap-gated).

        Runs the expensive cold-init work (factory + shared acquire +
        precompile) *now*, off any request path, so a later fork is a
        memcpy.  Returns the zygote instance, or None when the per-family
        or total cap is already met.
        """
        mgr = self.manager
        with self._lock:
            self._prune()
            live = self._by_family.get(family, [])
            total = sum(len(v) for v in self._by_family.values())
            if len(live) >= self.cfg.per_family \
                    or total >= self.cfg.max_total:
                return None
            zid = zygote_id(family, self._seq)
            self._seq += 1
            # reserve the slot before the (slow) init so concurrent
            # spawners cannot overshoot the caps
            self._by_family.setdefault(family, []).append(zid)
            self._spawned_at[zid] = time.monotonic()
            if shared_paths is not None:
                self._shared_paths[family] = frozenset(shared_paths)
            else:
                shared_paths = self._shared_paths.get(family)
        try:
            model_cfg, params = mgr.factory(family)
            inst = ModelInstance(
                zid, model_cfg, params, pool=mgr.pool,
                spool_dir=mgr.cfg.spool_dir,
                shared_paths=shared_paths if mgr.shared else None,
                base_id=family if mgr.shared else None,
                store=mgr.store,
                metadata_bytes=mgr.cfg.husk_metadata_bytes,
                arch_key=family)
            if mgr.shared and inst.base_id and inst.shared_paths:
                mgr.shared.acquire(inst.base_id, inst)
            inst.sm.fire(Event.ZYGOTE_SPAWN)
            with mgr._lock:
                mgr.instances[zid] = inst
            if self.precompile is not None:
                self.precompile(inst)
        except BaseException:
            with mgr._lock:
                mgr.instances.pop(zid, None)
            with self._lock:
                ids = self._by_family.get(family, [])
                if zid in ids:
                    ids.remove(zid)
                self._spawned_at.pop(zid, None)
            raise
        self.spawned += 1
        return inst

    def ensure(self, family: str, shared_paths=None
               ) -> Optional[ModelInstance]:
        """Spawn a zygote for ``family`` unless one is already live."""
        with self._lock:
            self._prune()
            for zid in self._by_family.get(family, []):
                inst = self.manager.instances.get(zid)
                if inst is not None:
                    return inst
        return self.spawn(family, shared_paths=shared_paths)

    def take(self, family: str) -> Optional[ModelInstance]:
        """Claim a live zygote of ``family`` for a fork (removes it from
        the pool; the manager consumes and terminates the donor)."""
        with self._lock:
            ids = self._by_family.get(family, [])
            while ids:
                zid = ids.pop(0)
                self._spawned_at.pop(zid, None)
                inst = self.manager.instances.get(zid)
                if inst is not None \
                        and inst.state == ContainerState.ZYGOTE:
                    return inst
        return None

    def _prune(self) -> None:
        # drop bookkeeping for zygotes the governor evicted underneath us
        with self._lock:
            for family, ids in list(self._by_family.items()):
                alive = [z for z in ids if z in self.manager.instances]
                if len(alive) != len(ids):
                    self._by_family[family] = alive
                    for z in set(ids) - set(alive):
                        self._spawned_at.pop(z, None)

    def note_evicted(self, instance_id: str) -> None:
        """Manager hook: a zygote left ``instances`` (governor retire)."""
        if not is_zygote_id(instance_id):
            return
        with self._lock:
            for ids in self._by_family.values():
                if instance_id in ids:
                    ids.remove(instance_id)
            self._spawned_at.pop(instance_id, None)

    # ----------------------------------------------------------- economics
    def note_admission(self, family: str,
                       now: Optional[float] = None) -> None:
        """Record a new-tenant admission for ``family`` (fork or cold).

        Feeds the per-family inter-admission EWMA and the forecaster's
        synthetic ``__newtenant__:family`` stream — the fork-avoidance
        signal the governor and the pre-fork daemon both price.
        """
        now = time.monotonic() if now is None else now
        with self._lock:
            last, gap = self._admissions.get(family, (None, None))
            if last is not None:
                a = self.cfg.fork_gap_alpha
                gap = (now - last) if gap is None else \
                    a * (now - last) + (1 - a) * gap
            self._admissions[family] = (now, gap)
        f = self.manager.governor.forecaster
        if f is not None:
            f.observe(NEW_TENANT_KEY + family, now)

    def predicted_fork_gap(self, family: str, now: float) -> float:
        """Expected seconds until the family's next new-tenant admission.

        The reactive inter-admission EWMA (``default_gap_s`` with no
        history), blended with the forecaster's seasonal/burst prediction
        for the family's synthetic arrival stream when one is configured
        — the same degradation discipline as the governor's
        ``predicted_gap``.
        """
        with self._lock:
            last, gap = self._admissions.get(family, (None, None))
        if last is None:
            reactive = self.cfg.default_gap_s
        elif gap is None:
            reactive = max(1e-3, now - last)
        else:
            reactive = max(1e-3, gap)
        f = self.manager.governor.forecaster
        if f is not None:
            blended = f.predicted_gap(NEW_TENANT_KEY + family, now,
                                      reactive)
            if blended is not None:
                return max(1e-3, blended)
        return reactive

    def prefork_candidates(self, now: float) -> List[str]:
        """Families worth pre-forking: no live zygote, predicted next
        new-tenant admission within ``prefork_margin_s``, one-margin
        per-family cooldown (a wrong prediction cannot ping-pong spawns
        every daemon pass)."""
        out: List[str] = []
        margin = self.cfg.prefork_margin_s
        with self._lock:
            self._prune()
            families = set(self._admissions) | set(self._shared_paths)
            for family in sorted(families):
                if self._by_family.get(family):
                    continue
                last = self._last_prefork.get(family)
                if last is not None and (now - last) < margin:
                    continue
                if self.predicted_fork_gap(family, now) <= margin:
                    self._last_prefork[family] = now
                    out.append(family)
        return out

    # ------------------------------------------------------------- retire
    def retire(self, zygote_id_: str) -> None:
        """Evict one zygote (``(ZYGOTE, EVICT) -> DEAD``): the normal
        manager evict path releases its shared-registry ref and deletes
        its spool files; ``note_evicted`` drops the pool bookkeeping."""
        self.manager.evict(zygote_id_)
        self.retired += 1

    def reap_idle(self, now: Optional[float] = None) -> List[str]:
        """Retire zygotes idle past ``retire_idle_s`` (no-op when that
        knob is None).  Returns the retired ids."""
        if self.cfg.retire_idle_s is None:
            return []
        now = time.monotonic() if now is None else now
        with self._lock:
            self._prune()
            stale = [z for z, t in self._spawned_at.items()
                     if (now - t) > self.cfg.retire_idle_s]
        for zid in stale:
            if zid in self.manager.instances:
                self.retire(zid)
        return stale

    # ---------------------------------------------------------- accounting
    def live(self) -> List[ModelInstance]:
        """Live zygote instances across all families."""
        with self._lock:
            self._prune()
            out = []
            for ids in self._by_family.values():
                for zid in ids:
                    inst = self.manager.instances.get(zid)
                    if inst is not None:
                        out.append(inst)
            return out

    def families(self) -> Dict[str, int]:
        """``{family: live zygote count}`` — the node's advertisement."""
        with self._lock:
            self._prune()
            return {f: len(ids) for f, ids in self._by_family.items()
                    if ids}

    def has(self, family: str) -> bool:
        """True when a live zygote of ``family`` is available to fork."""
        with self._lock:
            self._prune()
            return bool(self._by_family.get(family))

    def zygote_bytes(self, family: str) -> int:
        """Bytes of init work a fork of ``family`` would avoid here:
        anonymous weights plus the shared base the donor holds a ref on.
        The router's zygote-affinity placement term."""
        gov = self.manager.governor
        tot = 0
        with self._lock:
            self._prune()
            for zid in self._by_family.get(family, []):
                inst = self.manager.instances.get(zid)
                if inst is not None:
                    tot += gov._anon_resident_bytes(inst)
                    tot += inst.shared_weight_bytes()
        return tot

    def uncharged_bytes(self) -> int:
        """Bytes ``charge_governor=False`` exempts from the governed
        total: every live zygote's anonymous weights + metadata (shared
        base weights stay charged — live tenants share those buffers)."""
        gov = self.manager.governor
        tot = 0
        for inst in self.live():
            tot += gov._anon_resident_bytes(inst) + inst.metadata_bytes()
        return tot

    def stats(self) -> Dict[str, int]:
        """Counter snapshot for logs and benchmarks."""
        with self._lock:
            return {"spawned": self.spawned, "forked": self.forked,
                    "retired": self.retired,
                    "live": sum(len(v) for v in self._by_family.values())}
