"""Cluster density: tenants-per-GB across 4 nodes, migration on vs off.

The single-node governor's density ceiling is structural: when skewed
placement piles tenants onto one node, that node must either thrash its
hot tenants down the ladder or TERMINATE cold husks — while a neighbour
idles.  The cluster tier (``repro.cluster``) migrates hibernated
snapshots over the CAS store instead, so provisioning follows *cluster*
load, not worst-case per-node load.

Scenario: 4 nodes, one hot tenant per node (steady traffic), plus a pile
of cold tenants that all started life on node 0 (the deployment ramped
up there before the cluster filled) and now mostly sleep.  Husk metadata
is modelled at a paper-realistic fraction of the warm footprint
(``ManagerConfig.husk_metadata_bytes``), so node 0's husk load alone
breaches a tight budget even fully deflated.

Policies, swept over per-node budgets:

  migration-on   — sustained breaches ship the most idle husks to peers
                   scored ``bytes_freed * predicted_idle /
                   (transfer_missing / link_bw + wake_cost)``; dedup
                   means base weights never cross the link (every node
                   already holds the deployment's digests).
  migration-off  — the pre-cluster world: a sustained breach falls back
                   to TERMINATED, and a terminated tenant's next request
                   pays a full cold start (seconds).

Tenants-per-GB uses provisioned cluster memory (sum of node budgets);
a row qualifies only if its p99 TTFT stays within a fixed multiple of
the unconstrained-cluster p99.  Arrivals are virtual-time (the governor
and router take ``now``), so the suite measures serve/wake/cold-start
cost, not wall-clock sleeps.
"""
from __future__ import annotations

import shutil
import time

import numpy as np

from benchmarks.common import Table, build_factory, fmt_mb, request_for
from repro.cluster import ClusterPolicy, ClusterRouter, Node
from repro.core.governor import GovernorConfig
from repro.core.metrics import percentile
from repro.core.state import Rung

ARCH = "llama3.2-3b"
N_NODES = 4
PROMPT_LEN = 24
HOT_GAP = 2.0                 # one hot tenant per node, steady traffic
COLD_GAP = 12.0               # cold husks: occasional requests
SALT = b"cluster-density-bench"
#: husk metadata as a fraction of the warm footprint — the paper's
#: deflated containers keep host state alive at a meaningful fraction
#: of warm (page tables, runtime threads, compiled handles)
HUSK_FRACTION = 4


def _mk_cluster(spool: str, per_node_budget, migration: bool,
                n_hot: int, n_cold: int, husk_bytes: int):
    shutil.rmtree(spool, ignore_errors=True)
    factory = build_factory("tiny")
    gov_cfg = GovernorConfig(min_partial_bytes=4 << 10,
                             terminate_idle_s=None)   # router owns evicts
    nodes = [Node(f"n{i}", factory, spool_dir=spool, salt=SALT,
                  budget_bytes=per_node_budget, governor_cfg=gov_cfg)
             for i in range(N_NODES)]
    for n in nodes:
        n.cfg.husk_metadata_bytes = husk_bytes
    policy = ClusterPolicy(sustained_breach_rounds=2, migration=migration,
                           max_migrations_per_round=2)
    router = ClusterRouter(nodes, policy=policy)

    # skewed placement: hot tenants one per node; EVERY cold tenant
    # began life on node 0
    tenants = []
    for i in range(n_hot):
        tenants.append((f"hot{i}", nodes[i % N_NODES], HOT_GAP))
    for i in range(n_cold):
        tenants.append((f"cold{i}", nodes[0], COLD_GAP))
    cfg0 = None
    for iid, node, _gap in tenants:
        router.placement[iid] = node.node_id
        router.arch_of[iid] = ARCH
        inst = node.engine.start_instance(iid, ARCH)
        cfg0 = inst.cfg
        # one long-lived ctx session (the tenant's private KV delta —
        # what migration actually ships) + a recorded sample request
        node.engine.handle(request_for(cfg0, iid, "ctx", PROMPT_LEN, 0,
                                       seed=hash(iid) % 1000))
        inst.recorder.start()
        node.engine.handle(request_for(cfg0, iid, "probe", PROMPT_LEN, 0,
                                       seed=1 + hash(iid) % 1000,
                                       close_session=True))
        inst.recorder.stop()
        # everyone starts hibernated: digests land in every node's store
        # (this is also what lets later migrations dedup base weights)
        node.manager.descend(iid, Rung.HIBERNATED)
    return router, nodes, tenants, cfg0


def _schedule(tenants, horizon, seed=7):
    """All Poisson arrivals within the horizon (no truncation: every
    cold tenant keeps arriving for the whole run, so a TERMINATED victim
    always comes back to pay its cold start)."""
    rng = np.random.default_rng(seed)
    evs = []
    for iid, _node, gap in tenants:
        t = rng.exponential(gap)
        while t < horizon:
            evs.append((t, iid, gap))
            t += rng.exponential(gap)
    evs.sort()
    return evs


def _run(router, cfg, tenants, horizon, rebalance=True):
    ttfts = []
    sched = _schedule(tenants, horizon)
    for t, iid, _gap in sched:
        if rebalance:
            router.rebalance(now=t)
        t0 = time.monotonic()
        router.handle(
            request_for(cfg, iid, f"s{t:.3f}", PROMPT_LEN, 0,
                        seed=int(t * 1000) % 9973, close_session=True),
            now=t)
        ttfts.append(time.monotonic() - t0)
        node = router.node_of(iid)
        inst = node.manager.instances.get(iid)
        if inst is not None:
            if inst.wake_pipeline is not None:
                inst.wake_pipeline.wait(60)
            inst.quiesce_bg()
            inst.last_used = t
    return ttfts, len(sched)


def _alive(router):
    return sum(len(n.manager.instances) for n in router.nodes.values())


def _per_gb(n, bytes_):
    return n / (bytes_ / 2**30)


def main(quick: bool = False):
    n_hot, n_cold = (N_NODES, 10) if quick else (N_NODES, 16)
    horizon = 24.0 if quick else 48.0
    n_tenants = n_hot + n_cold

    # warm-footprint reference: one unconstrained cluster measures the
    # per-tenant warm bytes, the husk size, and the p99 TTFT floor
    router, nodes, tenants, cfg = _mk_cluster(
        "/tmp/bench_cluster/ref", None, True, n_hot, n_cold, 1 << 16)
    warm_bytes = nodes[0].manager.instances["hot0"].weight_bytes(
        resident_only=False)
    husk_bytes = warm_bytes // HUSK_FRACTION
    ref_tt, _ = _run(router, cfg, tenants, horizon, rebalance=False)
    ref_p99 = percentile(ref_tt, 99)
    router.close()

    # p99 TTFT budget: the "equal latency" envelope both policies must
    # meet for their density row to qualify.  Generous enough for wake
    # and disk-writeback jitter on loaded runners (isolated wakes are
    # ~20 ms; a co-scheduled teardown can triple that); a TERMINATED
    # tenant's cold-start re-entry (re-trace + dispatch, >=0.5 s) still
    # blows it several times over.
    tt_budget = max(6.0 * ref_p99, ref_p99 + 0.15)

    # per-node budget sweep: tight fits (hot warm + cluster-fair share of
    # husks); loose fits node 0's entire skewed husk pile locally
    tight = warm_bytes + (n_cold // N_NODES + 2) * husk_bytes
    loose = warm_bytes + (n_cold + 2) * husk_bytes
    budgets = (tight, loose)

    rows = []
    mig_stats = None
    for migration in (True, False):
        for budget in budgets:
            name = (f"{'migration' if migration else 'no-migration'}"
                    f"@{fmt_mb(budget)}MB/node")
            router, nodes, tenants, cfg = _mk_cluster(
                f"/tmp/bench_cluster/{'mig' if migration else 'off'}"
                f"{budget % 997}", budget, migration, n_hot, n_cold,
                husk_bytes)
            tt, _n_ev = _run(router, cfg, tenants, horizon)
            stats = router.migration_stats()
            stats["evictions"] = router.evictions
            if migration and budget == tight:
                mig_stats = stats
            rows.append((name, budget, tt, _alive(router), stats))
            router.close()

    cluster = N_NODES
    tab = Table(
        f"Cluster density: {n_tenants} tenants / {N_NODES} nodes "
        f"({ARCH}, skewed cold pile on n0); p99 TTFT budget "
        f"{tt_budget * 1e3:.0f} ms",
        ["policy", "node MB", "cluster MB", "tenants/GB", "ttft p50 ms",
         "ttft p99 ms", "within budget", "evictions", "migrations",
         "wire MB", "full-snap MB"])
    qualifying = {True: 0.0, False: 0.0}
    for name, budget, tt, _n_alive, stats in rows:
        prov = cluster * budget
        p50, p99 = percentile(tt, 50), percentile(tt, 99)
        ok = p99 <= tt_budget
        dens = _per_gb(n_tenants, prov)
        is_mig = name.startswith("migration")
        if ok:
            qualifying[is_mig] = max(qualifying[is_mig], dens)
        tab.add(name, fmt_mb(budget), fmt_mb(prov), f"{dens:.0f}",
                f"{p50 * 1e3:.1f}", f"{p99 * 1e3:.1f}",
                "yes" if ok else "NO", int(stats["evictions"]),
                int(stats["migrations"]),
                fmt_mb(stats["wire_bytes"]),
                fmt_mb(stats["full_snapshot_bytes"]))
    print(tab.render())

    wire_ratio = (mig_stats["wire_bytes"]
                  / max(mig_stats["full_snapshot_bytes"], 1)) \
        if mig_stats and mig_stats["migrations"] else 1.0
    print(f"dedup-aware transfer: {wire_ratio:.2f}x of naive "
          f"full-snapshot bytes over {int(mig_stats['migrations'])} "
          f"migrations" if mig_stats else "no migrations ran")

    checks = [
        ("migration >=1.5x cluster tenants-per-GB vs no-migration "
         "at equal p99 TTFT",
         qualifying[True] >= 1.5 * qualifying[False] > 0),
        ("migration traffic <=0.3x naive full-snapshot bytes (dedup)",
         bool(mig_stats) and mig_stats["migrations"] >= 1
         and wire_ratio <= 0.3),
        ("migration keeps every tenant alive at the tight budget "
         "(zero TERMINATED evictions)",
         any(s["evictions"] == 0 and alive == n_tenants
             and name.startswith("migration") and budget == tight
             for name, budget, _tt, alive, s in rows)),
        ("no-migration falls back to TERMINATED at the tight budget",
         any(s["evictions"] > 0 and name.startswith("no-migration")
             and budget == tight
             for name, budget, _tt, alive, s in rows)),
    ]
    return tab, checks


if __name__ == "__main__":
    main()
