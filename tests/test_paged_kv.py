"""Paged KV cache: sessions, COW forks, close, swap/fault cycle."""
import numpy as np
import pytest

from repro.configs import get_config, tiny_config
from repro.core.pool import PagePool
from repro.core.swap import ReapFile, SwapFile
from repro.serving.paged_kv import PagedKVCache


@pytest.fixture()
def cache():
    cfg = tiny_config(get_config("llama3.2-3b"))
    pool = PagePool(page_elems=256, capacity_pages=1 << 14)
    return PagedKVCache("i0", cfg, pool), cfg, pool


def _rand_kv(cache, n_tok):
    rng = np.random.default_rng(0)
    return rng.standard_normal((n_tok, cache.token_elems)).astype(np.float32)


def test_write_read_roundtrip(cache):
    kv, cfg, pool = cache
    kv.new_session("s")
    data = _rand_kv(kv, 37)
    for l in range(cfg.num_layers):
        kv.write_tokens("s", l, data, 0)
    for l in range(cfg.num_layers):
        np.testing.assert_allclose(kv.read_tokens("s", l, 37), data)
    # appending more tokens extends pages
    more = _rand_kv(kv, 5)
    kv.write_tokens("s", 0, more, 37)
    np.testing.assert_allclose(kv.read_tokens("s", 0, 42),
                               np.concatenate([data, more]))


def test_fork_cow_shares_pages(cache):
    kv, cfg, pool = cache
    kv.new_session("s")
    data = _rand_kv(kv, 20)
    for l in range(cfg.num_layers):
        kv.write_tokens("s", l, data, 0)
    kv.sessions["s"].num_tokens = 20
    before = pool.used_bytes
    kv.fork_session("s", "t")
    assert pool.used_bytes == before          # no new pages: COW
    np.testing.assert_allclose(kv.read_tokens("t", 0, 20), data)
    # refcounts: freeing the original keeps the fork readable
    kv.close_session("s")
    np.testing.assert_allclose(kv.read_tokens("t", 0, 20), data)


def test_trim_reclaims_closed_sessions(cache):
    """Closing a session returns its pages to the pool and drops its host
    units at once; an install that arrives for it later is skipped."""
    kv, cfg, pool = cache
    kv.new_session("s")
    for l in range(cfg.num_layers):
        kv.write_tokens("s", l, _rand_kv(kv, 16), 0)
    kv.set_host_unit("s", "all", "state", np.ones((2, 3), np.float32))
    assert pool.used_bytes > 0 and kv.host_bytes() > 0
    kv.close_session("s")
    assert pool.used_bytes == 0 and kv.host_bytes() == 0
    assert "s" not in kv.sessions
    late = [(("kv", "s", 0, 0), np.zeros(pool.page_elems, np.float32)),
            (("kvh", "s", "all", "state"), np.ones((2, 3), np.float32))]
    assert kv.install_batch(late) == 0
    assert kv.nonresident_keys([k for k, _ in late]) == []
    assert pool.used_bytes == 0


def test_swap_cycle_restores_exact_bytes(cache, spool_dir):
    kv, cfg, pool = cache
    swap = SwapFile(f"{spool_dir}/i0.swap")
    reap = ReapFile(f"{spool_dir}/i0.reap")
    kv.new_session("s")
    data = _rand_kv(kv, 40)
    for l in range(cfg.num_layers):
        kv.write_tokens("s", l, data, 0)
    kv.sessions["s"].num_tokens = 40
    kv.set_host_unit("s", "all", "state", np.ones((2, 3), np.float32))

    ws = frozenset([("kv", "s", 0, 0), ("kvh", "s", "all", "state")])
    reap_items, swap_items = kv.export_items(ws)
    assert {k for k, _ in reap_items} == set(ws)
    reap.write_batch(reap_items)
    swap.write_units(swap_items)
    kv.drop_pages()
    assert pool.used_bytes == 0

    # REAP prefetch restores the working set only
    kv.apply_prefetch(reap.read_batch())
    np.testing.assert_allclose(
        kv.read_tokens("s", 0, kv.page_tokens)[:kv.page_tokens],
        data[:kv.page_tokens])
    np.testing.assert_array_equal(kv.get_host_unit("s", "all", "state"),
                                  np.ones((2, 3), np.float32))
    # the rest page-faults in
    missing = kv.nonresident_keys(kv.keys_for("s"))
    assert missing
    kv.fault_in(missing, swap, reap)
    for l in range(cfg.num_layers):
        np.testing.assert_allclose(kv.read_tokens("s", l, 40), data)
    swap.delete()
    reap.delete()
