"""Multi-turn session semantics across hibernation cycles."""
import numpy as np
import pytest

from repro.core.manager import InstanceManager, ManagerConfig
from repro.serving import Request, ServingEngine
from repro.core.state import Rung


@pytest.fixture()
def eng(tiny_factory, spool_dir):
    mgr = InstanceManager(
        ManagerConfig(spool_dir=spool_dir, wake_mode="reap"), tiny_factory)
    return ServingEngine(mgr), mgr


def test_multi_turn_grows_session(eng):
    eng, mgr = eng
    inst = eng.start_instance("i", "llama3.2-3b")
    n0 = 0
    for turn in range(3):
        eng.handle(Request("i", "chat", np.asarray([turn + 1, turn + 2]),
                           max_new_tokens=2))
        sess = inst.kv.sessions["chat"]
        assert sess.num_tokens > n0          # prompt + generated appended
        n0 = sess.num_tokens


def test_session_tokens_match_across_hibernate_cycles(eng):
    """Three hibernate/wake cycles with a growing session: every
    continuation must equal the never-hibernated trajectory."""
    eng1, mgr = eng

    def run(mgr2, eng2, hibernate):
        inst = eng2.start_instance("i", "hymba-1.5b")
        out = []
        for turn in range(3):
            if hibernate and turn:
                eng2.record_sample("i", Request(
                    "i", f"p{turn}", np.asarray([9]), max_new_tokens=1,
                    close_session=True))
                mgr2.descend("i", Rung.HIBERNATED)
            r = eng2.handle(Request("i", "chat", np.asarray([turn + 3]),
                                    max_new_tokens=2))
            out += r.tokens
        return out

    base = run(mgr, eng1, hibernate=False)
    # fresh manager for the hibernating run
    import shutil
    shutil.rmtree(mgr.cfg.spool_dir, ignore_errors=True)
    mgr2 = InstanceManager(
        ManagerConfig(spool_dir=mgr.cfg.spool_dir + "_h", wake_mode="reap"),
        mgr.factory)
    hib = run(mgr2, ServingEngine(mgr2), hibernate=True)
    assert base == hib


def test_sessions_isolated(eng):
    """Two sessions on one instance never cross-contaminate state."""
    eng, mgr = eng
    inst = eng.start_instance("i", "mamba2-130m")
    ra1 = eng.handle(Request("i", "a", np.asarray([1, 2, 3]),
                             max_new_tokens=2))
    rb = eng.handle(Request("i", "b", np.asarray([9, 8]),
                            max_new_tokens=2))
    ra2 = eng.handle(Request("i", "a", np.asarray([4]), max_new_tokens=2))
    # replay session a alone on a fresh instance: same trajectory
    eng2, _ = (ServingEngine(mgr), mgr)
    inst2 = eng.start_instance("j", "mamba2-130m")
    sa1 = eng.handle(Request("j", "a", np.asarray([1, 2, 3]),
                             max_new_tokens=2))
    sa2 = eng.handle(Request("j", "a", np.asarray([4]), max_new_tokens=2))
    assert (ra1.tokens, ra2.tokens) == (sa1.tokens, sa2.tokens)


def test_close_session_frees_on_next_deflate(eng):
    """A closed session is freed when its request ends, not at the next
    deflate: its pages are back in the pool at once, and the deflate that
    follows finds none of them to swap."""
    eng, mgr = eng
    inst = eng.start_instance("i", "yi-6b")
    eng.handle(Request("i", "tmp", np.asarray([1, 2, 3, 4]),
                       max_new_tokens=2, close_session=True))
    assert mgr.pool.rss_bytes("i") == 0      # freed at close
    assert "tmp" not in inst.kv.sessions
    st = mgr.descend("i", Rung.HIBERNATED)
    assert st.kv_pages_swapped == 0          # nothing live to swap
