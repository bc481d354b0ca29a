"""A session closed by its request is freed when the request ends.

A warm tenant then holds only its open sessions, and the prefix registry
keeps a closed prompt's pages and host units only in the store: a later
session with the same prompt adopts them through a revive."""
import sys
import threading

import numpy as np
import pytest

from repro.core.manager import InstanceManager, ManagerConfig
from repro.core.state import Rung
from repro.serving import Request, ServingEngine

#: a hybrid (attention pages + SSM state) and a pure-SSM family
FAMILIES = ("hymba-1.5b", "mamba2-130m")
PROMPT = list(range(100, 140))


@pytest.fixture()
def eng(tiny_factory, spool_dir):
    mgr = InstanceManager(ManagerConfig(spool_dir=spool_dir), tiny_factory)
    return ServingEngine(mgr), mgr


def _serve(eng, sid, prompt=PROMPT, n=4, close=True):
    return eng.handle(Request("t0", sid, np.asarray(prompt, np.int32),
                              max_new_tokens=n, close_session=close))


@pytest.mark.parametrize("arch", FAMILIES)
def test_closing_request_frees_its_session(eng, arch):
    eng, mgr = eng
    inst = eng.start_instance("t0", arch)
    _serve(eng, "s0")
    assert mgr.pool.rss_bytes("t0") == 0
    assert inst.kv.host_bytes() == 0 and not inst.kv.sessions
    assert inst.kv_bytes() == 0


@pytest.mark.parametrize("arch", FAMILIES)
def test_last_sharer_down_spills_host_units_too(eng, arch):
    """The entry keeps its byte count (the router's affinity term reads
    it) while its pages and host units live only in the store."""
    eng, mgr = eng
    eng.start_instance("t0", arch)
    reg = mgr.prefix_registry
    _serve(eng, "s0", close=False)
    (digest,) = reg.digests()
    e = reg.get(digest)
    assert e.resident and e.host_units and reg.held_bytes() > 0
    size, inventory = reg.entry_bytes(e), reg.inventory()
    _serve(eng, "s0", prompt=[3], n=1)
    assert not e.resident and e.host_units == {}
    assert reg.entry_bytes(e) == size and reg.inventory() == inventory
    assert reg.held_bytes() == 0


@pytest.mark.parametrize("arch", FAMILIES)
def test_adoption_after_close_revives_pages_and_host_units(eng, arch):
    """The adopter's decode reads the revived state, so its tokens equal
    the first session's only if pages and host units came back intact."""
    eng, mgr = eng
    eng.start_instance("t0", arch)
    reg = mgr.prefix_registry
    r1 = _serve(eng, "s0")
    assert not r1.adopted_prefix and reg.stats()["resident_entries"] == 0
    r2 = _serve(eng, "s1")
    assert r2.adopted_prefix and r2.tokens == r1.tokens
    assert reg.stats()["revives"] == 1
    assert reg.stats()["resident_entries"] == 0     # spilled again
    assert mgr.pool.rss_bytes("t0") == 0


@pytest.mark.parametrize("arch", FAMILIES)
def test_kv_held_bytes_stays_flat_over_closed_requests(eng, arch):
    eng, mgr = eng
    inst = eng.start_instance("t0", arch)
    rng = np.random.default_rng(0)
    held = [_serve(eng, f"s{i}", rng.integers(0, inst.cfg.vocab_size, 8),
                   n=2).kv_held_bytes for i in range(20)]
    assert held[1:] == [held[1]] * 19, held
    # an open session is what it counts
    r = _serve(eng, "open", rng.integers(0, inst.cfg.vocab_size, 8), n=2,
               close=False)
    assert r.kv_held_bytes == \
        inst.kv_bytes() + mgr.prefix_registry.held_bytes() > held[1]


def test_closing_sessions_while_installs_land_leaks_nothing(tiny_factory,
                                                           spool_dir):
    """Requests close the sessions of a woken tenant while a background
    installer (the lookahead's path) keeps restoring their swapped pages:
    an install that lands after a close finds no session, so the tenant
    ends with no page and no host unit."""
    mgr = InstanceManager(ManagerConfig(spool_dir=spool_dir,
                                        wake_mode="pagefault"), tiny_factory)
    eng = ServingEngine(mgr)
    inst = eng.start_instance("t0", "hymba-1.5b")
    n = 8
    for i in range(n):
        _serve(eng, f"s{i}", [i + 1] * 24, n=2, close=False)
    mgr.descend("t0", Rung.HIBERNATED)
    keys = [k for sid in inst.kv.sessions for k in inst.kv.keys_for(sid)]
    items = inst.swap_file.read_units([k for k in keys
                                       if k in inst.swap_file])
    stop = threading.Event()

    def installer():
        while not stop.is_set():
            inst.install_units(items)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    bg = threading.Thread(target=installer)
    try:
        bg.start()
        for i in range(n):
            _serve(eng, f"s{i}", [7], n=2)
    finally:
        stop.set()
        bg.join(60)
        sys.setswitchinterval(switch)
    assert not bg.is_alive() and not inst.kv.sessions
    assert mgr.pool.rss_bytes("t0") == 0 and inst.kv.host_bytes() == 0
