"""Serving engine: request execution over hibernatable model instances.

The engine is the "container runtime" side of the paper: it executes user
requests (prefill + decode) against :class:`ModelInstance`s, drives the
container state machine, performs *residency faulting* (the page-fault
swap-in analogue: before compute touches a weight unit or KV page, any
non-resident unit is loaded from the swap files), and feeds the REAP
recorder with the exact unit set a request touches.

Weight residency uses a fixpoint loop: units known statically (non-expert
leaves, embedding blocks of the request's tokens) are faulted up-front;
MoE expert units are faulted as the router reveals them (experts are only
knowable by running the model — the same reason the paper needs a *sample
request* to record the working set).

Compiled functions are cached per ``(kind, batch, seq-bucket)`` in
``inst.compiled`` — they survive hibernation (the paper's kept-alive
"blocked runtime threads"), which is exactly why a woken container skips
the cold-start cost.

Concurrency: each instance has a re-entrant serve lock
(:meth:`ServingEngine.instance_lock`); ``serve_batch`` holds it for the
whole request, so the AsyncPlatform's worker pool can serve *different*
instances in parallel while each instance's state machine stays
race-free.  Wakes route through ``InstanceManager.ensure_awake`` so a
wake storm on one hibernating tenant performs exactly one inflate.

Tracing: every layer boundary of a batch is a
:class:`~repro.core.metrics.span` (``serve.lock_wait``, ``wake``,
``serve.prefill`` with
``prefill.fault|dispatch``, ``kv.write`` and ``prefix.register``,
``serve.decode`` with ``decode.fault``, ``kv.gather``, ``decode.step`` >
``decode.dispatch`` and ``kv.writeback``), so each response carries its
own breakdown in ``Response.spans`` and the profiler sees the same spans
with ``tenant``/``batch`` (and per request ``session``) stats.  The
counters beside them (``h2d_bytes``, ``decode_steps``,
``dispatch_inflight``, ``compiles``) are charged at the same boundaries;
``kv_held_bytes`` is read once per batch, after its closed sessions were
freed.
"""
from __future__ import annotations

import itertools
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.instance import ModelInstance
from repro.core.manager import InstanceManager
from repro.core.metrics import charge, span
from repro.core.state import ContainerState, Event
from repro.models import model
from repro.serving.paged_kv import PagedKVCache

S = ContainerState


# ---------------------------------------------------------------------------
# requests / responses
# ---------------------------------------------------------------------------

class TenantMigrated(RuntimeError):
    """The tenant no longer lives on this node: its snapshot migrated to
    ``target`` (a peer node id, or ``None`` if unknown).  The cluster
    router catches this and re-dispatches the request there."""

    def __init__(self, instance_id: str, target: Optional[str] = None):
        super().__init__(
            f"tenant {instance_id} migrated away"
            + (f" to node {target}" if target else ""))
        self.instance_id = instance_id
        self.target = target


class NodeDownError(RuntimeError):
    """The node serving (or queued to serve) this request crashed.  The
    request itself may be retried elsewhere — the front door re-submits
    under the same idempotency key once the router re-homes the tenant,
    and the token stream deduplicates any tokens the first attempt
    already emitted."""


#: SLO classes the front door stamps on requests: interactive work
#: drives high-priority wakes and is claimed first by the worker pool;
#: batch work rides low-priority (yielding) wakes and is shed first
#: under admission pressure.
SLO_INTERACTIVE = "interactive"
SLO_BATCH = "batch"


@dataclass
class Request:
    instance_id: str
    session_id: str
    prompt: np.ndarray                       # (S,) int32 token ids
    max_new_tokens: int = 8
    embeds: Optional[np.ndarray] = None      # VLM stub patch embeddings
    frames: Optional[np.ndarray] = None      # audio stub encoder frames
    close_session: bool = False
    #: SLO class (``SLO_INTERACTIVE`` / ``SLO_BATCH``)
    slo: str = SLO_INTERACTIVE
    #: token-level streaming sink: called with each generated token id as
    #: it is produced — the first call fires right after prefill, i.e. as
    #: soon as the wake pipeline's critical prefix is resident, so a
    #: streaming client's TTFT tracks the wake path, not full inflate.
    #: Must be cheap and must not raise (failures are swallowed).
    on_token: Optional[Callable[[int], None]] = field(
        default=None, repr=False, compare=False)

    def emit(self, token: int) -> None:
        if self.on_token is not None:
            try:
                self.on_token(token)
            except Exception:
                pass        # a broken stream sink must not kill the batch


@dataclass
class Response:
    request: Request
    tokens: List[int] = field(default_factory=list)
    state_before: str = ""
    state_after: str = ""
    spans: Dict[str, float] = field(default_factory=dict)
    faulted_bytes: int = 0
    faults: int = 0
    prefetched_bytes: int = 0
    #: True when prefill was skipped entirely: the prompt's KV pages were
    #: COW-adopted from the deployment prefix registry
    adopted_prefix: bool = False
    #: the ``serve_batch`` call that served it (the ``batch`` stat of its
    #: trace events).  Batch-level spans and the counters below marked
    #: "batch" read the same on every response of one batch.
    batch: int = -1
    #: bytes of host (numpy) arrays handed to compiled steps: this
    #: request's prefill plus its batch's decode steps
    h2d_bytes: int = 0
    #: decode steps its batch ran (batch)
    decode_steps: int = 0
    #: mean over the batch's dispatches of the compiled steps in flight in
    #: the process when each started, itself included (batch)
    dispatch_inflight: float = 0.0
    #: backend compiles on the serving thread while the batch ran (batch)
    compiles: int = 0
    #: bytes the tenant's cache and the node's prefix registry still hold
    #: once the batch finished and its closed sessions were freed (batch)
    kv_held_bytes: int = 0


@dataclass
class _Batch:
    """One ``serve_batch`` call: the responses its spans and counters are
    charged to, and the in-flight count at each of its dispatches."""

    tenant: str
    id: int
    resps: List[Response]
    inflight: List[int] = field(default_factory=list)

    def ids(self, req: Optional[Request] = None) -> Dict[str, object]:
        """Stats of its trace events; a request's own carry its session."""
        ids = {"tenant": self.tenant, "batch": self.id}
        if req is not None:
            ids["session"] = req.session_id
        return ids


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: the batch each serving thread has open (``batch``), for compile charges
_open = threading.local()
_compile_listener_guard = threading.Lock()
_compile_listener_on = False


def _charge_compile(event: str, secs: float, **_) -> None:
    """Charge a backend compile to the batch open on the compiling thread;
    compiles outside any batch (a warm-up) are nobody's."""
    batch = getattr(_open, "batch", None)
    if event == _BACKEND_COMPILE and batch is not None:
        for r in batch.resps:
            r.compiles += 1


def _listen_for_compiles() -> None:
    """Register :func:`_charge_compile` once per process."""
    global _compile_listener_on
    with _compile_listener_guard:
        if not _compile_listener_on:
            jax.monitoring.register_event_duration_secs_listener(
                _charge_compile)
            _compile_listener_on = True


def _host_bytes(tree) -> int:
    """Bytes of the host (numpy) arrays in a pytree: what a compiled step
    copies to the device when it is called with them."""
    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if isinstance(x, np.ndarray))


# ---------------------------------------------------------------------------
# jitted compute (cached per instance)
# ---------------------------------------------------------------------------

def _make_prefill(cfg, window):
    def f(params, tokens, embeds, frames):
        x, caches, aux = model.forward_hidden(
            params, cfg, tokens, embeds=embeds, enc_frames=frames,
            window=window, collect_cache=True)
        logits = model.unembed(params, cfg, x[:, -1])
        return logits, caches, aux
    return jax.jit(f)


def _make_decode(cfg, window):
    def f(params, tokens, cache):
        return model.decode_step(params, cfg, tokens, cache,
                                 window=window, with_aux=True)
    return jax.jit(f)


def _bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


class ServingEngine:
    def __init__(self, manager: InstanceManager, *, max_new_default: int = 8,
                 window: Optional[int] = None):
        self.manager = manager
        self.window = window
        self.max_new_default = max_new_default
        self._locks: Dict[str, threading.RLock] = {}
        self._locks_guard = threading.Lock()
        self._batch_ids = itertools.count()
        #: compiled steps between their call and the first read of their
        #: result, over every tenant and worker thread
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        _listen_for_compiles()
        # the zygote pool compiles through the engine: spawned donors get
        # their prefill executables pre-built so a fork inherits them
        zp = manager.zygotes
        if zp is not None and zp.precompile is None:
            zp.precompile = self.precompile_prefill

    def instance_lock(self, instance_id: str) -> threading.RLock:
        """Per-instance serve lock: held for the whole of ``serve_batch``;
        the platform's policy daemon try-acquires it before deflating so
        SIGSTOP never races an in-flight request."""
        with self._locks_guard:
            lock = self._locks.get(instance_id)
            if lock is None:
                lock = self._locks[instance_id] = threading.RLock()
            return lock

    def drop_instance_lock(self, instance_id: str) -> None:
        """Forget an evicted instance's lock (tenant churn must not grow
        the lock table unboundedly)."""
        with self._locks_guard:
            self._locks.pop(instance_id, None)

    # ------------------------------------------------------------ lifecycle
    def start_instance(self, instance_id: str, arch_key: str,
                       shared_paths=None) -> ModelInstance:
        """Cold start (①): init/load + attach the paged cache."""
        inst = self.manager.cold_start(instance_id, arch_key,
                                       shared_paths=shared_paths)
        inst.kv = PagedKVCache(instance_id, inst.cfg, self.manager.pool,
                               registry=self.manager.prefix_registry)
        return inst

    def fork_instance(self, instance_id: str, arch_key: str,
                      shared_paths=None) -> Optional[ModelInstance]:
        """Fork admission: specialize a live zygote of ``arch_key`` into
        a new tenant (warm weights memcpy, inherited compiled prefill,
        shared base by refcount) and attach a fresh paged cache.  Returns
        None when no zygote is available — callers fall back to
        ``start_instance``.  A concurrent fork of the same tenant dedups
        below (the returned instance may already carry a cache)."""
        inst = self.manager.fork_start(instance_id, arch_key,
                                       shared_paths=shared_paths)
        if inst is not None and inst.kv is None:
            inst.kv = PagedKVCache(instance_id, inst.cfg, self.manager.pool,
                                   registry=self.manager.prefix_registry)
        return inst

    def precompile_prefill(self, inst: ModelInstance) -> None:
        """Pre-build the prefill executables for a zygote — the cold-start
        cost a fork skips.  Each configured prompt length is compiled by
        an actual dummy dispatch (jit tracing alone would defer the XLA
        compile to the first real request).  Frontend archs (wanting
        embeds/frames) cannot run on dummy tokens and are skipped — the
        fork still wins on init, just not on compile.  Any other failure
        (a refused compile, device OOM) raises."""
        cfg = inst.cfg
        if cfg.frontend.kind != "none" or cfg.is_encoder_decoder:
            return
        zp = self.manager.zygotes
        lens = zp.cfg.precompile_prompt_lens if zp is not None else (8,)
        params = inst.params_pytree()
        for L in lens:
            fn = self._compiled(inst, "prefill", 1, int(L), False, False)
            logits, _, _ = fn(params, jnp.zeros((1, int(L)), jnp.int32),
                              None, None)
            jax.block_until_ready(logits)

    def _compiled(self, inst: ModelInstance, kind: str, B: int, Sb: int,
                  has_embeds: bool, has_frames: bool):
        key = (kind, B, Sb, has_embeds, has_frames)
        fn = inst.compiled.get(key)
        if fn is None:
            maker = _make_prefill if kind == "prefill" else _make_decode
            fn = maker(inst.cfg, self.window)
            inst.compiled[key] = fn
        return fn

    @contextmanager
    def _dispatch(self, batch: _Batch, name: str, resps,
                  ids: Dict[str, object]):
        """One compiled step, from building its params to the program's
        first read of its result: the span ``name``, and the steps in
        flight in the process when it started, itself included."""
        with self._inflight_lock:
            self._inflight += 1
            batch.inflight.append(self._inflight)
        try:
            with span(name, resps, **ids):
                yield
        finally:
            with self._inflight_lock:
                self._inflight -= 1

    # ------------------------------------------------------------ weights
    def _static_weight_keys(self, inst: ModelInstance,
                            tokens: np.ndarray) -> List[Tuple]:
        """Units knowable before execution: non-expert leaves + embedding
        blocks of the tokens in this request."""
        keys = []
        eb = inst.embed_block
        blocks = {int(t) // eb for t in np.asarray(tokens).ravel()}
        # tied embeddings: the LM head reads the WHOLE table every step,
        # so all embed blocks belong to the static access set
        all_embed = inst.cfg.tie_embeddings
        for u in inst.units.values():
            if u.path in inst.shared_paths:
                continue
            if u.path == "embed" and u.sub >= 0:
                if all_embed or u.sub in blocks:
                    keys.append(u.key)
            elif u.sub < 0 or "/moe/" not in u.path:
                keys.append(u.key)
        return keys

    def _embed_keys(self, inst: ModelInstance, tokens) -> List[Tuple]:
        """Embedding blocks for a set of token ids (decode feeds generated
        tokens whose rows may still be swapped out)."""
        eb = inst.embed_block
        blocks = {int(t) // eb for t in np.asarray(tokens).ravel()}
        return [u.key for u in inst.units.values()
                if u.path == "embed" and u.sub in blocks
                and u.path not in inst.shared_paths]

    def _expert_keys(self, inst: ModelInstance,
                     counts: np.ndarray) -> List[Tuple]:
        """Expert units fired by the router.  counts: (..., E) summed."""
        if counts is None:
            return []
        used = np.asarray(counts).reshape(-1, counts.shape[-1]).sum(0)
        keys = []
        for u in inst.units.values():
            if u.sub >= 0 and "/moe/" in u.path and used[u.sub] > 0:
                keys.append(u.key)
        return keys

    def _fault(self, inst: ModelInstance, keys: Sequence[Tuple],
               resp: Response) -> None:
        missing = [k for k in keys
                   if (k[0] == "w" and k not in inst.resident)]
        kv_missing = (inst.kv.nonresident_keys(
            [k for k in keys if k[0] in ("kv", "kvh")])
            if inst.kv is not None else [])
        if not missing and not kv_missing:
            return
        st = self.manager.hib.fault(inst, missing + kv_missing)
        resp.faulted_bytes += st.faulted_bytes
        resp.faults += st.faults
        inst.recorder.record_many(missing + kv_missing)
        # serviced faults become lookahead: asynchronously pull the next
        # layer's KV pages / adjacent embed blocks so the following step
        # hits residency instead of faulting
        if self.manager.cfg.lookahead:
            la = self._lookahead_keys(inst, missing + kv_missing)
            if la:
                self.manager.hib.prefetch_async(inst, la)

    def _lookahead_keys(self, inst: ModelInstance,
                        faulted: Sequence[Tuple]) -> List[Tuple]:
        """Predict the fault set's successors: when layer *k*'s KV page
        faults, layer *k+1*'s page (and the session's next page in the
        same layer) is about to be touched; when an embedding block
        faults mid-decode, its neighbour is the next most likely row
        block.  Weight leaves are layer-stacked, so weight-side lookahead
        only applies to embed blocks."""
        out: List[Tuple] = []
        kv = inst.kv
        for k in faulted:
            if k[0] == "kv" and kv is not None:
                _, sid, layer, pidx = k
                sess = kv.sessions.get(sid)
                if sess is None:
                    continue
                succ = [(layer + 1, pidx), (layer, pidx + 1)]
                for lyr, p in succ:
                    if lyr < len(sess.pages) and p < len(sess.pages[lyr]) \
                            and sess.pages[lyr][p] is None:
                        out.append(("kv", sid, lyr, p))
            elif k[0] == "w" and k[1] == "embed" and k[2] >= 0:
                nk = ("w", "embed", k[2] + 1)
                if nk in inst.units and nk not in inst.resident:
                    out.append(nk)
        return [k for k in dict.fromkeys(out)]

    # ------------------------------------------------------------ cache io
    def _dense_cache(self, inst: ModelInstance, sids: List[str],
                     max_len: int):
        """Gather sessions' pages into a dense decode cache pytree."""
        cfg, kv = inst.cfg, inst.kv
        L, B = cfg.num_layers, len(sids)
        layers: Dict[str, np.ndarray] = {}
        lengths = np.zeros((B,), np.int32)
        kv_positions = np.full((B, max_len), -1, np.int32)
        te = kv.token_elems
        if cfg.attention == "mla":
            r, rd = cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
            layers["ckv"] = np.zeros((L, B, max_len, r), np.float32)
            layers["krope"] = np.zeros((L, B, max_len, rd), np.float32)
        elif cfg.attention == "gqa":
            Hkv, D = cfg.num_kv_heads, cfg.head_dim
            layers["k"] = np.zeros((L, B, max_len, Hkv, D), np.float32)
            layers["v"] = np.zeros((L, B, max_len, Hkv, D), np.float32)
        host: Dict[str, List[np.ndarray]] = {}
        for b, sid in enumerate(sids):
            sess = kv.sessions[sid]
            n = sess.num_tokens
            lengths[b] = n
            kv_positions[b, :n] = np.arange(n)
            if te:
                for l in range(L):
                    data = kv.read_tokens(sid, l, n)       # (n, te)
                    if cfg.attention == "mla":
                        layers["ckv"][l, b, :n] = data[:, :r]
                        layers["krope"][l, b, :n] = data[:, r:]
                    else:
                        Hkv, D = cfg.num_kv_heads, cfg.head_dim
                        kd = data.reshape(n, 2, Hkv, D)
                        layers["k"][l, b, :n] = kd[:, 0]
                        layers["v"][l, b, :n] = kd[:, 1]
            for key, arr in sess.host_units.items():
                kind = key[3]
                if arr is None:
                    raise KeyError(key)
                host.setdefault(kind, [None] * B)[b] = arr
        for kind, rows in host.items():
            layers[kind] = np.stack(rows, axis=1)          # (L, B, ...)
        dtype = jnp.dtype(cfg.dtype)
        jl = {k: jnp.asarray(v, jnp.float32 if k == "state" else dtype)
              for k, v in layers.items()}
        return {"layers": jl,
                "lengths": jnp.asarray(lengths),
                "kv_positions": jnp.asarray(kv_positions)}

    def _writeback(self, inst: ModelInstance, sids: List[str], cache,
                   start_lens: np.ndarray, resp: Optional[Response]) -> None:
        """Write new tokens' KV + final host units back into pages."""
        cfg, kv = inst.cfg, inst.kv
        L = cfg.num_layers
        layers = {k: np.asarray(v) for k, v in cache["layers"].items()}
        lengths = np.asarray(cache["lengths"])
        touched: List[Tuple] = []
        for b, sid in enumerate(sids):
            sess = kv.sessions[sid]
            n0, n1 = int(start_lens[b]), int(lengths[b])
            sess.num_tokens = n1
            if kv.token_elems and n1 > n0:
                for l in range(L):
                    if cfg.attention == "mla":
                        new = np.concatenate(
                            [layers["ckv"][l, b, n0:n1],
                             layers["krope"][l, b, n0:n1]], -1)
                    else:
                        new = np.stack([layers["k"][l, b, n0:n1],
                                        layers["v"][l, b, n0:n1]], 1)
                    touched += kv.write_tokens(
                        sid, l, new.reshape(n1 - n0, kv.token_elems), n0)
            for kind in ("state", "conv", "cross_k", "cross_v"):
                if kind in layers:
                    touched.append(kv.set_host_unit(
                        sid, "all", kind, layers[kind][:, b]))
        inst.recorder.record_many(touched)

    # ------------------------------------------------------------ serving
    def handle(self, req: Request) -> Response:
        """End-to-end single request (the Fig. 6 measurement path)."""
        return self.serve_batch(req.instance_id, [req])[0]

    def serve_batch(self, instance_id: str,
                    reqs: List[Request]) -> List[Response]:
        """Continuous-batched execution of requests on one instance:
        per-request prefill, then a joint decode loop that sessions leave
        as they finish."""
        bid = next(self._batch_ids)
        batch = _Batch(instance_id, bid, [Response(r, batch=bid)
                                          for r in reqs])
        outer = getattr(_open, "batch", None)
        _open.batch = batch
        try:
            lock = self.instance_lock(instance_id)
            with span("serve.lock_wait", batch.resps, **batch.ids()):
                lock.acquire()
            try:
                return self._serve_batch_locked(batch, reqs)
            finally:
                lock.release()
        finally:
            _open.batch = outer

    def _wake(self, batch: _Batch, priority: str):
        """The request trigger's wake, with its read and install stages
        (summed over the wake's threads) charged to the batch."""
        with span("wake", batch.resps, **batch.ids()):
            st = self.manager.ensure_awake(batch.tenant, trigger="request",
                                           priority=priority)
        if st is not None:
            charge(batch.resps, "wake.read", st.io_seconds)
            charge(batch.resps, "wake.install", st.inflate_seconds)
        return st

    def _serve_batch_locked(self, batch: _Batch,
                            reqs: List[Request]) -> List[Response]:
        instance_id, resps = batch.tenant, batch.resps
        inst = self.manager.instances.get(instance_id)
        # in-flight-request handoff: a request landing on a MIGRATING
        # tenant blocks on the transfer handle (exactly like late wake
        # arrivals block on the shared wake pipeline), then either serves
        # locally (transfer aborted -> HIBERNATE) or reroutes (committed:
        # the tenant now lives on the target node)
        while inst is not None and inst.state == S.MIGRATING:
            self.manager.ensure_awake(instance_id, trigger="request")
            inst = self.manager.instances.get(instance_id)
        if inst is not None and inst.state == S.DEAD \
                and inst.migration is not None:
            # commit window: MIGRATE_DONE has fired but the source has
            # not detached yet — wait for the commit to finish (placement
            # and the forwarding address are recorded before the handle
            # resolves) rather than serving a weight-dropped husk
            inst.migration.wait()
            inst = self.manager.instances.get(instance_id)
            if inst is None or inst.state == S.DEAD:
                raise TenantMigrated(instance_id,
                                     self.manager.migrated.get(instance_id))
        if inst is None:
            if instance_id in self.manager.migrated:
                raise TenantMigrated(instance_id,
                                     self.manager.migrated[instance_id])
            raise KeyError(f"instance {instance_id} not started")
        for r in resps:
            r.state_before = inst.state.value
        t0 = time.monotonic()

        # SLO feeds the wake pipeline's priority: an all-batch claim
        # wakes low-priority (yielding, no double-buffer) so it never
        # contends with an interactive tenant's wake on the same store
        wake_priority = ("high" if any(r.slo != SLO_BATCH for r in reqs)
                         else "low")

        # ---- state machine: the request trigger (②⑥⑦ + ladder rungs)
        wake_stats = None
        if inst.state in (S.HIBERNATE, S.PARTIAL, S.WOKEN):
            if inst.state in (S.HIBERNATE, S.PARTIAL):
                # wake-storm guard: at most one batched inflate per cycle.
                # A PARTIAL wake is rung-aware: the critical prefix is
                # already resident, the cold tail restores behind us.
                wake_stats = self._wake(batch, wake_priority)
            inst.sm.fire(Event.REQUEST)       # -> HIBERNATE_RUNNING
            finish_to = S.WOKEN
        elif inst.state in (S.WARM, S.MMAP_CLEAN):
            if inst.state == S.MMAP_CLEAN:
                # re-map the shared base weights before compute touches them
                wake_stats = self._wake(batch, wake_priority)
            inst.sm.fire(Event.REQUEST)       # -> RUNNING
            finish_to = S.WARM
        else:
            raise RuntimeError(f"instance busy/unservable: {inst.state}")
        if wake_stats is not None:
            for r in resps:
                r.prefetched_bytes = wake_stats.prefetched_bytes

        # backpressure the wake stream while this request computes: the
        # tail pauses (it resumes after FINISH) and anything this request
        # needs arrives via demand-pull on our own thread
        pipe = inst.wake_pipeline
        if pipe is not None and pipe.active:
            pipe.backpressure(+1)
        else:
            pipe = None
        try:
            # ---- per-request prefill
            cfg = inst.cfg
            sids = []
            for req, resp in zip(reqs, resps):
                with span("serve.prefill", (resp,), **batch.ids(req)):
                    self._prefill_one(inst, req, resp, batch)
                sids.append(req.session_id)

            # ---- joint decode
            active = [i for i, r in enumerate(reqs) if r.max_new_tokens > 0]
            if active:
                with span("serve.decode", resps, **batch.ids()):
                    self._decode_joint(inst, reqs, resps, sids, batch)
        finally:
            if pipe is not None:
                pipe.backpressure(-1)

        # ---- finish (③⑧)
        inst.sm.fire(Event.FINISH)
        assert inst.state == finish_to
        inst.last_used = time.monotonic()
        # a closed session is freed now, not at the next deflate, so a
        # tenant kept warm holds only its open sessions.  Under the install
        # lock: a wake stream or lookahead install may be writing the cache
        with inst.install_lock:
            for req in reqs:
                if req.close_session:
                    inst.kv.close_session(req.session_id)
        held = inst.kv_bytes()
        if inst.kv.registry is not None:
            held += inst.kv.registry.held_bytes()
        inflight = (sum(batch.inflight) / len(batch.inflight)
                    if batch.inflight else 0.0)
        for r in resps:
            r.state_after = inst.state.value
            r.dispatch_inflight = inflight
            r.kv_held_bytes = held
            r.spans["e2e"] = time.monotonic() - t0
        return resps

    # ------------------------------------------------------------ internals
    def _prefill_one(self, inst: ModelInstance, req: Request,
                     resp: Response, batch: _Batch) -> None:
        cfg = inst.cfg
        kv = inst.kv
        ids, mine = batch.ids(req), (resp,)
        if req.session_id not in kv.sessions:
            if self._try_adopt_prefix(inst, req, resp, ids):
                return
            kv.new_session(req.session_id)
        sess = kv.sessions[req.session_id]

        # fault statically-known weights + this session's existing cache
        with span("prefill.fault", mine, **ids):
            static_keys = self._static_weight_keys(inst, req.prompt)
            self._fault(inst, static_keys, resp)
            inst.recorder.record_many(
                k for k in static_keys if k[0] == "w")
            if sess.num_tokens:
                prior = kv.keys_for(req.session_id, window_tokens=None)
                self._fault(inst, prior, resp)
                inst.recorder.record_many(prior)

        tokens = np.asarray(req.prompt, np.int32)[None]    # (1, S)
        Sb = tokens.shape[1]
        fn = self._compiled(inst, "prefill", 1, Sb,
                            req.embeds is not None, req.frames is not None)
        embeds = None if req.embeds is None else jnp.asarray(req.embeds)[None]
        frames = None if req.frames is None else jnp.asarray(req.frames)[None]

        # fixpoint on MoE expert residency.  The snapshot is taken BEFORE
        # dispatch: a concurrently streaming wake may install an expert
        # mid-run, and a post-run residency check would then accept logits
        # computed with zeroed (or torn) weights.  A key missing from the
        # pre-dispatch snapshot always forces one more run.
        with self._dispatch(batch, "prefill.dispatch", mine, ids):
            for _ in range(8):
                snapshot = inst.resident.copy()
                params = inst.params_pytree()
                resp.h2d_bytes += _host_bytes(params)
                logits, caches, aux = fn(params, jnp.asarray(tokens),
                                         embeds, frames)
                ek = self._expert_keys(inst, aux.get("expert_counts"))
                missing = [k for k in ek if k not in snapshot]
                inst.recorder.record_many(ek)
                if not missing:
                    break
                self._fault(inst, missing, resp)
            resp.tokens.append(int(jnp.argmax(logits[0, :cfg.vocab_size])))
        # first streamed token: fires as soon as prefill completes, which
        # on a woken tenant is right after the critical prefix landed
        req.emit(resp.tokens[-1])

        # write prefill KV into pages
        n0 = sess.num_tokens
        with span("kv.write", mine, **ids):
            S_tot = Sb + (0 if req.embeds is None or cfg.is_encoder_decoder
                          else req.embeds.shape[0])
            layers = {} if caches is None else \
                {k: np.asarray(v) for k, v in caches.items()}
            touched: List[Tuple] = []
            if kv.token_elems:
                for l in range(cfg.num_layers):
                    if cfg.attention == "mla":
                        new = np.concatenate([layers["ckv"][l, 0],
                                              layers["krope"][l, 0]], -1)
                    else:
                        new = np.stack([layers["k"][l, 0],
                                        layers["v"][l, 0]], 1)
                    touched += kv.write_tokens(
                        req.session_id, l,
                        new.reshape(S_tot, kv.token_elems), n0)
            for kind in ("state", "conv", "cross_k", "cross_v"):
                if kind in layers:
                    touched.append(kv.set_host_unit(
                        req.session_id, "all", kind, layers[kind][:, 0]))
            sess.num_tokens = n0 + S_tot
            sess.token_ids += [int(t) for t in req.prompt]
            inst.recorder.record_many(touched)

        # a fresh prompt that just paid full prefill becomes a shareable
        # prefix: later sessions (any tenant of this arch, any node after
        # migration) COW-adopt these pages instead of recomputing
        with span("prefix.register", mine, **ids):
            registry = kv.registry
            if registry is not None and n0 == 0 and inst.arch_key \
                    and req.embeds is None and req.frames is None:
                registry.register(inst.arch_key, kv, req.session_id,
                                  resp.tokens[-1])

    def _try_adopt_prefix(self, inst: ModelInstance, req: Request,
                          resp: Response, ids: Dict[str, object]) -> bool:
        """Cross-tenant prefix adoption: if the prompt's salted token-hash
        is registered, map the existing KV pages by COW refcount and emit
        the recorded first token — no prefill forward pass at all.  Static
        weights still fault in (decode needs them); the prompt must be
        pure tokens (embeds/frames make KV depend on more than token ids).
        """
        kv = inst.kv
        registry = kv.registry
        if registry is None or not inst.arch_key or \
                req.embeds is not None or req.frames is not None or \
                len(req.prompt) < registry.min_tokens:
            return False
        entry = registry.lookup(inst.arch_key,
                                [int(t) for t in req.prompt])
        if entry is None:
            return False
        with span("prefill.fault", (resp,), **ids):
            static_keys = self._static_weight_keys(inst, req.prompt)
            self._fault(inst, static_keys, resp)
            inst.recorder.record_many(k for k in static_keys if k[0] == "w")
        registry.adopt(entry.digest, kv, req.session_id)
        resp.adopted_prefix = True
        resp.tokens.append(entry.first_token)
        req.emit(resp.tokens[-1])
        inst.recorder.record_many(kv.keys_for(req.session_id))
        return True

    def _decode_joint(self, inst: ModelInstance, reqs: List[Request],
                      resps: List[Response], sids: List[str],
                      batch: _Batch) -> None:
        cfg = inst.cfg
        kv = inst.kv
        ids = batch.ids()
        max_new = max(r.max_new_tokens for r in reqs)
        max_len = _bucket(max(kv.sessions[s].num_tokens for s in sids)
                          + max_new)
        # fault every page the decode window will read
        with span("decode.fault", resps, **ids):
            for sid in sids:
                self._fault(inst, kv.keys_for(sid), resps[0])
                inst.recorder.record_many(kv.keys_for(sid))
        with span("kv.gather", resps, **ids):
            cache = self._dense_cache(inst, sids, max_len)
        start_lens = np.asarray(cache["lengths"]).copy()
        B = len(sids)
        fn = self._compiled(inst, "decode", B, max_len, False, False)
        cur = jnp.asarray([r.tokens[-1] if r.tokens else 0 for r in resps],
                          jnp.int32)
        done = np.zeros((B,), bool)
        for _step in range(max_new - 1 + 1):
            with span("decode.step", resps, **ids):
                # the fed-back tokens' embedding rows page-fault on access
                ek = self._embed_keys(inst, np.asarray(cur))
                inst.recorder.record_many(ek)
                self._fault(inst, ek, resps[0])
                # page-fault-and-retry on expert residency: re-run the SAME
                # step from the pre-step cache until every routed expert
                # was resident in the PRE-dispatch snapshot (see
                # _prefill_one for why the snapshot must precede the run)
                with self._dispatch(batch, "decode.dispatch", resps, ids):
                    for _ in range(4):
                        snapshot = inst.resident.copy()
                        params = inst.params_pytree()
                        h2d = _host_bytes(params)
                        for r in resps:
                            r.h2d_bytes += h2d
                        logits, new_cache, aux = fn(params, cur, cache)
                        counts = aux.get("expert_counts")
                        if counts is None:
                            break
                        ek = self._expert_keys(inst, np.asarray(counts))
                        inst.recorder.record_many(ek)
                        missing = [k for k in ek if k not in snapshot]
                        if not missing:
                            break
                        self._fault(inst, missing, resps[0])
                    cache = new_cache
                    nxt = np.asarray(jnp.argmax(
                        logits[:, :cfg.vocab_size], axis=-1), np.int32)
                for b, r in enumerate(resps):
                    r.decode_steps += 1
                    want = r.request.max_new_tokens
                    if not done[b] and len(r.tokens) < want:
                        r.tokens.append(int(nxt[b]))
                        r.request.emit(r.tokens[-1])
                        if len(r.tokens) >= want:
                            done[b] = True
                    else:
                        done[b] = True
                cur = jnp.asarray(nxt)
            if done.all():
                break
        with span("kv.writeback", resps, **ids):
            self._writeback(inst, sids, cache, start_lens, resps[0])

    # ------------------------------------------------------------ REAP ops
    def record_sample(self, instance_id: str, req: Request) -> frozenset:
        """§3.4.2 Record process: run a sample request with the recorder on;
        the union of touched units becomes the REAP working set."""
        inst = self.manager.instances[instance_id]
        inst.recorder.start()
        self.handle(req)
        return inst.recorder.stop()
