"""Cluster-wide resident KV prefix registry: COW adoption semantics.

The registry's contract, in order of how expensive a violation is:

  * adopted decode is BIT-EXACT vs recomputing the prefill privately —
    the whole point of sharing is that nobody can tell;
  * the never-overwrite discipline: a sharer writing past the prefix
    breaks COW to a private copy, the registry page stays pristine;
  * refcount sanity: deflating/terminating one sharer never frees or
    double-counts pages another sharer (or the registry) still maps;
  * last-sharer-down spills to the CAS store and revives by digest;
  * migration ships records + segments, the target rebuilds by digest.
"""
import numpy as np
import pytest

from repro.core.manager import InstanceManager, ManagerConfig
from repro.core.prefix import PREFIX_OWNER
from repro.core.state import Rung
from repro.serving.engine import Request, ServingEngine

ARCH = "llama3.2-3b"
PROMPT = list(range(100, 140))


@pytest.fixture()
def eng(tiny_factory, spool_dir):
    mgr = InstanceManager(
        ManagerConfig(spool_dir=spool_dir, wake_mode="reap"), tiny_factory)
    return ServingEngine(mgr), mgr


def _prefill(eng, iid, sid, prompt=PROMPT, n=4):
    return eng.handle(Request(iid, sid, np.asarray(prompt, np.int32),
                              max_new_tokens=n))


# ---------------------------------------------------------------- adoption
def test_register_then_adopt_same_tenant(eng):
    eng, mgr = eng
    eng.start_instance("t0", ARCH)
    r1 = _prefill(eng, "t0", "s0")
    assert not r1.adopted_prefix            # first prefill registers
    reg = mgr.prefix_registry
    assert reg.stats()["registrations"] == 1
    r2 = _prefill(eng, "t0", "s1")
    assert r2.adopted_prefix                # second session adopts
    assert r2.tokens == r1.tokens           # bit-exact, no forward pass


def test_cross_tenant_adoption_bit_exact(eng):
    eng, mgr = eng
    eng.start_instance("t0", ARCH)
    eng.start_instance("t1", ARCH)
    r1 = _prefill(eng, "t0", "s0")
    r2 = _prefill(eng, "t1", "sX")
    assert r2.adopted_prefix and r2.tokens == r1.tokens
    # and the decode continuation stays identical
    c1 = eng.handle(Request("t0", "s0", [7], max_new_tokens=4))
    c2 = eng.handle(Request("t1", "sX", [7], max_new_tokens=4))
    assert c1.tokens == c2.tokens


def test_adoption_partitions_on_arch(eng):
    """Different arch => different weights => the digest must not match."""
    eng, mgr = eng
    eng.start_instance("t0", ARCH)
    eng.start_instance("t1", "yi-6b")
    _prefill(eng, "t0", "s0")
    r = _prefill(eng, "t1", "sX")
    assert not r.adopted_prefix


def test_short_prompts_never_register(eng):
    eng, mgr = eng
    eng.start_instance("t0", ARCH)
    _prefill(eng, "t0", "s0", prompt=[1, 2])   # < prefix_min_tokens
    assert mgr.prefix_registry.stats()["registrations"] == 0


def test_prefix_sharing_off_is_inert(tiny_factory, spool_dir):
    mgr = InstanceManager(
        ManagerConfig(spool_dir=spool_dir, prefix_sharing=False),
        tiny_factory)
    eng = ServingEngine(mgr)
    assert mgr.prefix_registry is None
    eng.start_instance("t0", ARCH)
    r1 = _prefill(eng, "t0", "s0")
    r2 = _prefill(eng, "t0", "s1")
    assert not r2.adopted_prefix and r2.tokens == r1.tokens


# ---------------------------------------------------------- COW discipline
def test_donor_divergence_leaves_registry_pristine(eng):
    """The donor keeps decoding (writes the shared last page -> COW
    break); a later adopter must still see the original prefill."""
    eng, mgr = eng
    eng.start_instance("t0", ARCH)
    r1 = _prefill(eng, "t0", "s0")
    eng.handle(Request("t0", "s0", [7], max_new_tokens=6))  # diverge donor
    r2 = _prefill(eng, "t0", "s1")           # adopt AFTER divergence
    assert r2.adopted_prefix and r2.tokens == r1.tokens


def test_sharers_decode_independently(eng):
    """Three sharers of one prefix each continue with different suffixes;
    each trajectory equals the same suffix run privately."""
    eng, mgr = eng
    eng.start_instance("t0", ARCH)
    _prefill(eng, "t0", "s0")
    _prefill(eng, "t0", "s1")
    _prefill(eng, "t0", "s2")
    outs = [eng.handle(Request("t0", f"s{i}", [7 + i],
                               max_new_tokens=4)).tokens for i in range(3)]
    # private replay on a prefix-sharing-off twin
    mgr2 = InstanceManager(
        ManagerConfig(spool_dir=mgr.cfg.spool_dir + "_twin",
                      prefix_sharing=False), mgr.factory)
    eng2 = ServingEngine(mgr2)
    eng2.start_instance("t0", ARCH)
    for i in range(3):
        _prefill(eng2, "t0", f"s{i}")
    outs2 = [eng2.handle(Request("t0", f"s{i}", [7 + i],
                                 max_new_tokens=4)).tokens
             for i in range(3)]
    assert outs == outs2


# ---------------------------------------------------------- refcount/spill
def test_refcounts_balance_after_close_and_trim(eng):
    eng, mgr = eng
    eng.start_instance("t0", ARCH)
    _prefill(eng, "t0", "s0")
    _prefill(eng, "t0", "s1")
    inst = mgr.instances["t0"]
    for sid in ("s0", "s1"):
        eng.handle(Request("t0", sid, [3], max_new_tokens=1,
                           close_session=True))
    # last sharer down: the entry spilled to the CAS tier
    st = mgr.prefix_registry.stats()
    assert st["entries"] == 1 and st["resident_entries"] == 0
    assert mgr.pool.rss_bytes("t0") == 0 and not inst.kv.sessions
    assert mgr.pool.pss_bytes(PREFIX_OWNER) == 0


def test_spill_and_revive_by_digest(eng):
    eng, mgr = eng
    eng.start_instance("t0", ARCH)
    r1 = _prefill(eng, "t0", "s0")
    eng.handle(Request("t0", "s0", [3], max_new_tokens=1,
                       close_session=True))
    reg = mgr.prefix_registry
    assert reg.stats()["resident_entries"] == 0
    r2 = _prefill(eng, "t0", "sNew")         # revives from CAS, no prefill
    assert r2.adopted_prefix and r2.tokens == r1.tokens
    assert reg.stats()["revives"] == 1


def test_governor_spills_unmapped_prefix_first(eng):
    """Both sharers hibernated => the registry copy is governor-spillable
    without touching either tenant; wakes reattach by digest."""
    eng, mgr = eng
    eng.start_instance("t0", ARCH)
    eng.start_instance("t1", ARCH)
    _prefill(eng, "t0", "s0")
    r2 = _prefill(eng, "t1", "sX")
    assert r2.adopted_prefix
    for iid in ("t0", "t1"):
        eng.record_sample(iid, Request(iid, "p", [9], max_new_tokens=1,
                                       close_session=True))
        mgr.descend(iid, Rung.HIBERNATED)
    reg = mgr.prefix_registry
    cands = reg.spill_candidates()
    assert cands, "no resident sharers -> must be spillable"
    assert reg.spill(cands[0][1]) > 0
    mgr.ensure_awake("t0")
    mgr.ensure_awake("t1")
    c1 = eng.handle(Request("t0", "s0", [5], max_new_tokens=4))
    c2 = eng.handle(Request("t1", "sX", [5], max_new_tokens=4))
    assert c1.tokens == c2.tokens


def test_deflating_one_sharer_never_swaps_prefix_pages(eng):
    """Hibernating a sharer must not export the registry's pages to its
    swap tier (they are shared, not private state) nor disturb the other
    sharer's decode.

    Uses a page-aligned prompt: a partial last prefix page is COW-broken
    by the first decode write and becomes legitimately-private state."""
    eng, mgr = eng
    eng.start_instance("t0", ARCH)
    eng.start_instance("t1", ARCH)
    aligned = list(range(100, 164))          # exactly one 64-token page
    r1 = _prefill(eng, "t0", "s0", prompt=aligned)
    _prefill(eng, "t1", "sX", prompt=aligned)
    eng.record_sample("t1", Request("t1", "p", [9], max_new_tokens=1,
                                    close_session=True))
    st = mgr.descend("t1", Rung.HIBERNATED)
    inst1 = mgr.instances["t1"]
    # no ("kv", "sX", ...) page within the prefix range went to any tier
    n_prefix = inst1.kv._n_pages(inst1.kv.sessions["sX"].prefix_tokens)
    spilled = [k for k in list(inst1.swap_file.extents)
               + list(inst1.reap_file.extents)
               if k[0] == "kv" and k[1] == "sX" and k[3] < n_prefix]
    assert not spilled, spilled
    # the awake sharer still decodes off the registry pages
    c1 = eng.handle(Request("t0", "s0", [7], max_new_tokens=4))
    mgr.ensure_awake("t1")
    c2 = eng.handle(Request("t1", "sX", [7], max_new_tokens=4))
    assert c1.tokens == c2.tokens


def test_evicting_a_sharer_keeps_survivors_intact(eng):
    eng, mgr = eng
    eng.start_instance("t0", ARCH)
    eng.start_instance("t1", ARCH)
    r1 = _prefill(eng, "t0", "s0")
    _prefill(eng, "t1", "sX")
    mgr.evict("t1")
    c1 = eng.handle(Request("t0", "s0", [7], max_new_tokens=4))
    # replay privately to prove the pages were not clobbered
    mgr2 = InstanceManager(
        ManagerConfig(spool_dir=mgr.cfg.spool_dir + "_twin",
                      prefix_sharing=False), mgr.factory)
    eng2 = ServingEngine(mgr2)
    eng2.start_instance("t0", ARCH)
    _prefill(eng2, "t0", "s0")
    c2 = eng2.handle(Request("t0", "s0", [7], max_new_tokens=4))
    assert c1.tokens == c2.tokens


# ---------------------------------------------------------------- registry
def test_digest_is_salted_and_exact_matched(eng):
    eng, mgr = eng
    reg = mgr.prefix_registry
    d1 = reg.digest_of(ARCH, PROMPT)
    assert d1 != reg.digest_of(ARCH, PROMPT[:-1] + [999])
    assert d1 != reg.digest_of("other-arch", PROMPT)
    # a different deployment salt yields unrelated digests
    from repro.core.prefix import PrefixRegistry
    other = PrefixRegistry(mgr.pool, None, salt=b"y" * 16)
    assert other.digest_of(ARCH, PROMPT) != d1


def test_registry_uses_store_digest_discipline(eng):
    eng, mgr = eng
    reg = mgr.prefix_registry
    buf = ARCH.encode() + b"\x00" + \
        np.asarray(PROMPT, np.int64).tobytes()
    assert reg.digest_of(ARCH, PROMPT) == mgr.store.keyed_digest(buf)


def test_resident_bytes_counts_shared_pages_once(tiny_factory, spool_dir):
    """N adopters of one prefix must not multiply the node's governed
    bytes: PSS accounting splits each shared page across its mappers, so
    growth under sharing is far below an identical sharing-off run."""
    long_prompt = list(range(1, 161))        # 2.5 pages of prefix

    def grow(share, tag):
        mgr = InstanceManager(
            ManagerConfig(spool_dir=spool_dir + tag, prefix_sharing=share),
            tiny_factory)
        eng2 = ServingEngine(mgr)
        eng2.start_instance("t0", ARCH)
        _prefill(eng2, "t0", "s0", prompt=long_prompt)
        base = mgr.resident_bytes()
        for i in range(1, 5):
            _prefill(eng2, "t0", f"s{i}", prompt=long_prompt)
        return mgr.resident_bytes() - base

    shared, private = grow(True, "_on"), grow(False, "_off")
    assert shared < private / 2, (shared, private)
