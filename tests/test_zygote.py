"""Zygote pool: fork-vs-cold byte identity, refcount isolation,
governor retirement economics, fork-storm dedup, pre-fork daemon, and
node-death chaos (a fork writes nothing a crash can orphan)."""
import os
import threading

import numpy as np
import pytest

from repro.cluster import ClusterPolicy, ClusterRouter, Node
from repro.cluster.health import HealthPolicy
from repro.core.forecast import ForecastConfig, ForecastDaemon
from repro.core.governor import GovernorConfig
from repro.core.manager import InstanceManager, ManagerConfig
from repro.core.state import (ContainerState, Event, InvalidTransition,
                              Rung, StateMachine)
from repro.core.zygote import (NEW_TENANT_KEY, ZygoteConfig, is_zygote_id,
                               zygote_id)
from repro.serving.engine import Request, ServingEngine

S = ContainerState
ARCH = "llama3.2-3b"
FAMILIES = ["llama3.2-3b", "arctic-480b", "mamba2-130m"]
SALT = b"zygote-test-salt"


def _loader(tiny_factory):
    def loader(base_id):
        import jax

        from repro.core.instance import _path_str
        cfg, params = tiny_factory(base_id)
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        return {_path_str(p): np.asarray(v) for p, v in flat
                if _path_str(p) == "embed"}
    return loader


def _mgr(tiny_factory, spool_dir, *, shared=True, zcfg=None, **kw):
    cfg = ManagerConfig(spool_dir=spool_dir,
                        zygote_pool=zcfg or ZygoteConfig(), **kw)
    return InstanceManager(
        cfg, tiny_factory,
        shared_loader=_loader(tiny_factory) if shared else None)


def _req(cfg, iid, sid="s0", new_tokens=3):
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    return Request(iid, sid, prompt, max_new_tokens=new_tokens)


# ------------------------------------------------------------ state graph
def test_zygote_state_graph():
    """A zygote never serves: REQUEST (and every deflate event) is
    illegal in ZYGOTE; its only exits are being forked or retired."""
    sm = StateMachine()
    sm.fire(Event.ZYGOTE_SPAWN)
    assert sm.state is S.ZYGOTE
    for ev in (Event.REQUEST, Event.SIGSTOP, Event.MMAP_DROP,
               Event.PARTIAL_STOP, Event.SIGCONT, Event.MIGRATE,
               Event.COLD_START):
        with pytest.raises(InvalidTransition):
            sm.fire(ev)
    assert sm.fire(Event.FORK) is S.DEAD          # consumed by a fork
    sm2 = StateMachine()
    sm2.fire(Event.ZYGOTE_SPAWN)
    assert sm2.fire(Event.EVICT) is S.DEAD        # governor retire
    # the forked tenant is born WARM through its own transition, so its
    # history distinguishes a warm fork from a true cold start
    sm3 = StateMachine()
    assert sm3.fire(Event.FORK) is S.WARM


# ------------------------------------------------------------ fork vs cold
@pytest.mark.parametrize("arch", FAMILIES)
def test_fork_first_response_byte_identical(tiny_factory, spool_dir, arch):
    """Fork admission is an optimization, never a different model: the
    first response of a forked tenant is byte-identical to a
    cold-started one, per family."""
    mgr = _mgr(tiny_factory, spool_dir)
    eng = ServingEngine(mgr)
    cold = eng.start_instance("cold", arch, shared_paths={"embed"})
    cold_toks = list(eng.handle(_req(cold.cfg, "cold")).tokens)
    mgr.evict("cold")
    zyg = mgr.zygotes.spawn(arch, shared_paths={"embed"})
    assert zyg.state is S.ZYGOTE and is_zygote_id(zyg.instance_id)
    inst = eng.fork_instance("forked", arch, shared_paths={"embed"})
    assert inst is not None and inst.state is S.WARM
    # the donor died by being forked; the tenant inherited its handles
    assert zyg.instance_id not in mgr.instances
    assert inst.compiled is zyg.compiled
    fork_toks = list(eng.handle(_req(inst.cfg, "forked")).tokens)
    assert fork_toks == cold_toks
    assert mgr.forks_performed == 1
    # the fork entered the graph through (COLD, FORK), not COLD_START
    assert inst.sm.history[0][2] is Event.FORK


def test_fork_without_pool_or_donor_falls_back(tiny_factory, spool_dir):
    mgr = InstanceManager(ManagerConfig(spool_dir=spool_dir), tiny_factory)
    assert mgr.zygotes is None
    assert mgr.fork_start("t", ARCH) is None      # no pool configured
    mgr2 = _mgr(tiny_factory, spool_dir + "2", shared=False)
    assert mgr2.fork_start("t", ARCH) is None     # pool, but no donor


@pytest.mark.parametrize("arch,dispatches", [
    (ARCH, True), ("llava-next-34b", False), ("whisper-large-v3", False)])
def test_spawn_precompile_raises_or_skips_frontends(tiny_factory, spool_dir,
                                                    monkeypatch, arch,
                                                    dispatches):
    """A donor whose prefill cannot be built fails its spawn (and frees
    its slot) instead of hiding the fault; frontend archs, which cannot
    run on dummy tokens, are skipped without a dispatch."""
    mgr = _mgr(tiny_factory, spool_dir, shared=False)
    eng = ServingEngine(mgr)

    def refused(*a):
        raise RuntimeError("compile refused")

    monkeypatch.setattr(eng, "_compiled", lambda *a: refused)
    if dispatches:
        with pytest.raises(RuntimeError, match="compile refused"):
            mgr.zygotes.spawn(arch)
        assert not mgr.instances and not mgr.zygotes._by_family[arch]
    else:
        assert mgr.zygotes.spawn(arch).state is S.ZYGOTE


def test_platform_admits_unknown_tenant_by_fork(tiny_factory, spool_dir):
    """The serve path tries the fork first: an unknown tenant's first
    request rides a live donor (logged ``fork_start``), and only a
    pool miss cold-starts."""
    from repro.serving.scheduler import Platform, PlatformPolicy
    mgr = _mgr(tiny_factory, spool_dir)
    eng = ServingEngine(mgr)
    zyg = mgr.zygotes.spawn(ARCH, shared_paths={"embed"})
    plat = Platform(eng, PlatformPolicy(), {"t": ARCH, "u": ARCH})
    plat.submit(_req(zyg.cfg, "t"))
    resps = plat.step()
    assert len(resps) == 1 and len(resps[0].tokens) == 3
    assert any(e[1] == "fork_start" and e[2] == "t" for e in plat.log)
    assert mgr.forks_performed == 1
    plat.submit(_req(zyg.cfg, "u"))               # pool is empty now
    plat.step()
    assert any(e[1] == "cold_start" and e[2] == "u" for e in plat.log)


# ------------------------------------------------------------ refcounts
def test_retiring_donor_never_frees_forked_tenants_pages(tiny_factory,
                                                         spool_dir):
    """Refcount isolation: the tenant acquires its own shared-registry
    ref before the donor releases, so retiring every remaining zygote
    leaves the forked tenant's shared base loaded and byte-intact."""
    mgr = _mgr(tiny_factory, spool_dir,
               zcfg=ZygoteConfig(per_family=2))
    mgr.zygotes.spawn(ARCH, shared_paths={"embed"})
    inst = mgr.fork_start("t", ARCH, shared_paths={"embed"})
    assert inst is not None
    assert mgr.shared.refcount(ARCH) == 1         # tenant's own ref
    golden = np.asarray(inst.weights["embed"]).copy()
    z2 = mgr.zygotes.spawn(ARCH, shared_paths={"embed"})
    assert mgr.shared.refcount(ARCH) == 2
    mgr.zygotes.retire(z2.instance_id)
    assert mgr.shared.refcount(ARCH) == 1
    assert mgr.shared.is_loaded(ARCH)
    np.testing.assert_array_equal(np.asarray(inst.weights["embed"]),
                                  golden)
    assert mgr.zygotes.stats()["live"] == 0
    mgr.evict("t")                                # last ref drops the base
    assert mgr.shared.refcount(ARCH) == 0


# ------------------------------------------------------------ governor
def test_governor_retires_idle_zygote_under_pressure(tiny_factory,
                                                     spool_dir):
    """A zygote is governor-charged: under budget pressure its bytes are
    priced against fork avoidance and it retires through the ladder's
    TERMINATED rung (no idle gate — it was never 'used')."""
    mgr = _mgr(tiny_factory, spool_dir, shared=False)
    zyg = mgr.zygotes.spawn(ARCH)
    zid = zyg.instance_id
    gov = mgr.governor
    before = gov.governed_bytes()
    assert before > 0
    acts = gov.step(now=100.0, budget_bytes=1)
    assert any(a.instance_id == zid and a.rung_to == Rung.TERMINATED
               for a in acts)
    assert zid not in mgr.instances
    assert zyg.state is S.DEAD
    assert mgr.zygotes.stats()["live"] == 0
    assert gov.governed_bytes() < before


def test_governor_prefers_zygote_over_hot_tenant(tiny_factory, spool_dir):
    """With a hot tenant (due soon) and a never-admitted family's zygote
    (default fork gap: an hour), the zygote is the better victim — its
    fork-avoidance value (bytes x predicted admission gap / cold-start
    cost) beats the hot tenant's imminent-wake value.  The hot tenant is
    a different family, so its admissions don't train the zygote's."""
    mgr = _mgr(tiny_factory, spool_dir, shared=False)
    zyg = mgr.zygotes.spawn(ARCH)
    inst = mgr.cold_start("hot", "mamba2-130m")
    gov = mgr.governor
    now = 100.0
    for t in (98.0, 99.0, 100.0):
        gov.observe_arrival("hot", now=t)
    inst.last_used = now
    one = gov._anon_resident_bytes(inst) + inst.metadata_bytes()
    acts = gov.step(now=now, budget_bytes=gov.governed_bytes() - one // 2)
    assert acts and acts[0].instance_id == zyg.instance_id
    assert mgr.instances["hot"].state is S.WARM


def test_charge_governor_off_exempts_zygote_bytes(tiny_factory, spool_dir):
    mgr = _mgr(tiny_factory, spool_dir, shared=False)
    mgr.zygotes.spawn(ARCH)
    charged = mgr.governor.governed_bytes()
    mgr.zygotes.cfg.charge_governor = False
    exempt = mgr.governor.governed_bytes()
    assert exempt < charged
    assert charged - exempt == mgr.zygotes.uncharged_bytes()


def test_reap_idle_retires_stale_donor(tiny_factory, spool_dir):
    import time as _time
    mgr = _mgr(tiny_factory, spool_dir, shared=False,
               zcfg=ZygoteConfig(retire_idle_s=5.0))
    zyg = mgr.zygotes.spawn(ARCH)
    assert mgr.zygotes.reap_idle(_time.monotonic() + 1.0) == []
    retired = mgr.zygotes.reap_idle(_time.monotonic() + 10.0)
    assert retired == [zyg.instance_id]
    assert zyg.instance_id not in mgr.instances


# ------------------------------------------------------------ fork storms
def test_fork_storm_dedups_to_one_fork(tiny_factory, spool_dir):
    """N concurrent first-requests of one unknown tenant share a single
    fork: one donor consumed, every caller gets the same instance."""
    mgr = _mgr(tiny_factory, spool_dir, shared=False)
    mgr.zygotes.spawn(ARCH)
    n = 6
    barrier = threading.Barrier(n)
    results = [None] * n

    def storm(i):
        barrier.wait()
        results[i] = mgr.fork_start("t", ARCH)

    threads = [threading.Thread(target=storm, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r is results[0] and r is not None for r in results)
    assert mgr.forks_performed == 1
    assert mgr.forks_deduped == n - 1
    assert mgr.zygotes.stats() == {"spawned": 1, "forked": 1,
                                   "retired": 0, "live": 0}


# ------------------------------------------------------------ economics
def test_admissions_train_fork_gap_and_prefork(tiny_factory, spool_dir):
    """Cold starts and forks both feed the per-family admission EWMA
    (and the forecaster's synthetic stream); a family predicted due
    within the margin and missing a donor becomes a pre-fork candidate,
    and the daemon spawns it."""
    mgr = _mgr(tiny_factory, spool_dir, shared=False,
               zcfg=ZygoteConfig(prefork_margin_s=15.0),
               governor_policy=GovernorConfig(
                   forecast=ForecastConfig(season_period_s=100.0)))
    zp = mgr.zygotes
    for i, t in enumerate((0.0, 10.0, 20.0)):
        mgr.cold_start(f"t{i}", ARCH)
        mgr.evict(f"t{i}")
        zp.note_admission(ARCH, now=t)
    assert zp.predicted_fork_gap(ARCH, 25.0) <= 15.0
    assert mgr.governor.forecaster.stats()["observations"] >= 3
    daemon = ForecastDaemon(mgr)
    acted = daemon.step(25.0)
    assert daemon.preforked_zygotes == 1
    assert any(is_zygote_id(a) for a in acted)
    assert zp.has(ARCH)
    # cooldown: the next pass does not spawn a second donor
    assert daemon.step(26.0) == []
    # a family with no admission history predicts far away
    assert zp.predicted_fork_gap("arctic-480b", 25.0) \
        == zp.cfg.default_gap_s


def test_spawn_caps(tiny_factory, spool_dir):
    mgr = _mgr(tiny_factory, spool_dir, shared=False,
               zcfg=ZygoteConfig(per_family=1, max_total=2))
    assert mgr.zygotes.spawn(ARCH) is not None
    assert mgr.zygotes.spawn(ARCH) is None           # per-family cap
    assert mgr.zygotes.ensure(ARCH) is not None      # already live
    assert mgr.zygotes.spawn("mamba2-130m") is not None
    assert mgr.zygotes.spawn("arctic-480b") is None  # total cap
    assert mgr.zygotes.families() == {ARCH: 1, "mamba2-130m": 1}


# ------------------------------------------------------------ cluster
def test_placement_prefers_node_with_zygote(tiny_factory, spool_dir):
    """Zygote affinity: with equal headroom, a new tenant lands on (and
    forks from) the node advertising a live donor of its family."""
    def _node(nid):
        mcfg = ManagerConfig(spool_dir=os.path.join(spool_dir, nid),
                             store_salt=SALT,
                             zygote_pool=ZygoteConfig())
        return Node(nid, tiny_factory, spool_dir=spool_dir,
                    manager_cfg=mcfg)
    n0, n1 = _node("n0"), _node("n1")
    router = ClusterRouter([n0, n1])
    n1.manager.zygotes.spawn(ARCH)
    assert n1.zygote_families() == {ARCH: 1}
    assert n0.zygote_bytes(ARCH) == 0 < n1.zygote_bytes(ARCH)
    node = router.place("t", ARCH, now=0.0)
    assert node is n1
    assert n1.manager.forks_performed == 1
    assert any(e[1] == "place_fork" for e in router.log)
    router.close()


def test_chaos_node_death_mid_fork_storm_gc_clean(tiny_factory, spool_dir):
    """Kill a node mid-fork-storm: a fork writes nothing to the CAS
    store, so every store stays GC-clean (no orphans, no quarantine)
    and the tenant re-admits on the survivor."""
    policy = ClusterPolicy(replication_factor=2,
                           health=HealthPolicy(suspect_after_s=3.0,
                                               dead_after_s=10.0))

    def _node(nid):
        mcfg = ManagerConfig(spool_dir=os.path.join(spool_dir, nid),
                             store_salt=SALT,
                             zygote_pool=ZygoteConfig())
        return Node(nid, tiny_factory, spool_dir=spool_dir,
                    manager_cfg=mcfg)
    n0, n1 = _node("n0"), _node("n1")
    router = ClusterRouter([n0, n1], policy=policy)
    for n in (n0, n1):
        n.manager.zygotes.spawn(ARCH)
    barrier = threading.Barrier(4 + 1)

    def storm(i):
        barrier.wait()
        try:
            n0.manager.fork_start(f"t{i}", ARCH)
        except Exception:
            pass                      # racing the crash is the point
    threads = [threading.Thread(target=storm, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    barrier.wait()
    n0.kill()                         # mid-storm
    for t in threads:
        t.join()
    router.check_health(0.0)
    router.check_health(11.0)         # lease lapses -> DEAD -> recovery
    assert router.detector.is_dead("n0")
    for n in (n0, n1):
        assert n.store.orphan_digests(0.0) == []
        assert n.store.stats()["quarantined"] == 0
    # survivor still admits: its own donor serves the next new tenant
    node = router.place("fresh", ARCH, now=12.0)
    assert node is n1
    assert n1.manager.forks_performed == 1
    router.close()
