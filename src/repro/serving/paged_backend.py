"""Bridge: bitmap-pool paged KV cache -> Pallas paged_attention kernel.

On TPU the decode hot loop never gathers pages into a dense cache: the
``paged_attention`` kernel reads K/V pool pages through the page table
(grid-level indirection over the Bitmap Page Allocator's pages).  This
module builds the kernel's view of a :class:`PagedKVCache`:

  k_pages/v_pages : (Hkv, P_used, page_tokens, D) — compacted pool pages
  page_table      : (B, pages_per_seq) int32 into the compacted pages
  lengths         : (B,) int32

The CPU engine uses the dense-gather path (same math, same oracle); this
bridge + its equivalence test prove the kernel serves the identical
logical cache.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import ops as pa_ops
from repro.serving.paged_kv import PagedKVCache


def kernel_view(kv: PagedKVCache, session_ids: Sequence[str], layer: int):
    """Build the kernel-layout arrays for one layer of a session batch."""
    cfg = kv.cfg
    if cfg.attention != "gqa":
        raise ValueError("paged_attention kernel serves GQA caches")
    Hkv, D, T = cfg.num_kv_heads, cfg.head_dim, kv.page_tokens

    phys_ids: List[int] = []
    index_of = {}
    rows = []
    for sid in session_ids:
        sess = kv.sessions[sid]
        row = []
        for pidx, pid in enumerate(sess.pages[layer]):
            if pid is None:
                # a shared-prefix slot can remap straight from the
                # registry (COW reattach, no disk IO); anything else is a
                # genuine swapped-out page the fault tier must restore
                pid = kv.ensure_prefix_slot(sid, layer, pidx)
            if pid is None:
                raise KeyError(("kv", sid, layer, "swapped"))
            if pid not in index_of:
                index_of[pid] = len(phys_ids)
                phys_ids.append(pid)
            row.append(index_of[pid])
        rows.append(row)
    pages_per_seq = max((len(r) for r in rows), default=1) or 1
    page_table = np.zeros((len(session_ids), pages_per_seq), np.int32)
    for b, row in enumerate(rows):
        page_table[b, :len(row)] = row

    P_used = max(len(phys_ids), 1)
    k_pages = np.zeros((Hkv, P_used, T, D), np.float32)
    v_pages = np.zeros((Hkv, P_used, T, D), np.float32)
    usable = T * kv.token_elems
    for j, pid in enumerate(phys_ids):
        phys = kv.pool._phys([pid])[0]
        page = kv.pool.data[phys][:usable].reshape(T, 2, Hkv, D)
        k_pages[:, j] = page[:, 0].transpose(1, 0, 2)
        v_pages[:, j] = page[:, 1].transpose(1, 0, 2)

    lengths = np.asarray([kv.sessions[s].num_tokens for s in session_ids],
                         np.int32)
    return (jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(page_table), jnp.asarray(lengths))


def paged_decode(kv: PagedKVCache, session_ids: Sequence[str], layer: int,
                 q, *, window: int = 0, interpret: bool = False):
    """q: (B, H, D) query for one layer -> (B, H, D) attention output,
    computed by the Pallas kernel directly over pool pages."""
    k_pages, v_pages, page_table, lengths = kernel_view(
        kv, session_ids, layer)
    return pa_ops.paged_decode_attention(
        q, k_pages, v_pages, page_table, lengths,
        window=window, interpret=interpret)
