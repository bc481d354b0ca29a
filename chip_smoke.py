"""Chip smoke: the hibernate/wake serving path on one TPU chip at
phi4-mini-3.8b's published widths, with random bf16 weights from a seed.

    python3 chip_smoke.py [--seed 0]

Run from the root of a checkout.  One phi4-mini-3.8b tenant is served by
``InstanceManager`` -> ``ServingEngine`` -> ``AsyncPlatform``, built the
way ``repro.launch.serve`` builds them, through five phases: a cold
request, a warm request on a fresh session, a REAP recording, a deflate
to HIBERNATE, and a request that lands woken (pipelined wake).  Checks:

  (a) the woken tokens equal the warm tokens, and the woken request ran
      prefill (prefix sharing is off, so nothing is adopted);
  (b) every weight leaf's digest once the wake has fully inflated equals
      its digest before the deflate;
  (c) the engine's cached prefill/decode logits, recomputed with its own
      jitted steps on the cache it builds, agree with one uncached
      forward pass over prompt + generated tokens within ``LOGIT_TOL``,
      and greedy tokens match wherever the top-1 margin exceeds it;
  (d) each phase went through the states it should.

The times printed are a smoke's, not a benchmark's.  The last line is a
JSON verdict.  Off a TPU, or when any check fails, the script exits
non-zero and prints no verdict.  The spool (about one model's bytes of
disk) lives in ``.smoke_spool/`` and is deleted on every exit.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import shutil
import signal
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SPOOL = ROOT / ".smoke_spool"
ARCH = "phi4-mini-3.8b"
TENANT = "t0"
PROMPT_LEN = 32
NEW_TOKENS = 8
#: cached vs uncached logits, as a fraction of the largest |reference
#: logit|.  Both paths run the same bf16 weights but round activations to
#: bf16 at different points: a 32- and a 39-token forward reduce attention
#: over different key blocks, and decode attends over a padded cache.  At
#: these widths on the CPU, the largest difference measured 2^-7.3, 2^-7.1
#: and 2^-6.3 of max|ref| at 2, 4 and 8 layers, growing about as
#: sqrt(depth): about 2^-5.3 at 32 layers.  The tolerance is 2.5x that,
#: and still 16x below the O(max|ref|) error of a wrong cache slot,
#: position or weight.
LOGIT_TOL = 2.0 ** -4


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def digests(weights) -> dict:
    """blake2b of every weight leaf's bytes, keyed by path (one thread per
    leaf: hashlib releases the GIL on large buffers)."""
    import numpy as np

    def one(a):
        return hashlib.blake2b(np.ascontiguousarray(a).view(np.uint8)
                               ).hexdigest()
    with ThreadPoolExecutor() as ex:
        return dict(zip(weights, ex.map(one, weights.values())))


def count_compiles() -> dict:
    """Count XLA backend compiles (persistent-cache misses) from here on."""
    from jax import monitoring
    tally = {"n": 0, "s": 0.0}

    def listen(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            tally["n"] += 1
            tally["s"] += secs
    monitoring.register_event_duration_secs_listener(listen)
    return tally


def run(cfg, seed: int) -> None:
    """Drive the five phases on JAX's default device and run every check.
    Raises :class:`SmokeFailure` on the first failed check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.manager import InstanceManager, ManagerConfig
    from repro.core.state import ContainerState as S, Event, Rung
    from repro.models import model
    from repro.serving import (AsyncPlatform, PlatformPolicy, Request,
                               ServingEngine)
    from repro.serving.engine import _bucket, _make_decode, _make_prefill

    class CountingEngine(ServingEngine):
        """Records every jitted dispatch: its kind, the host-resident
        weight bytes it uploads, and its wall time to completion."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.dispatches = []

        def _compiled(self, inst, kind, *rest):
            fn = super()._compiled(inst, kind, *rest)

            def counted(params, *args):
                host = sum(leaf.nbytes for leaf in jax.tree.leaves(params)
                           if isinstance(leaf, np.ndarray))
                t = time.monotonic()
                out = jax.block_until_ready(fn(params, *args))
                self.dispatches.append((kind, host, time.monotonic() - t))
                return out
            return counted

    dev = jax.devices()[0]
    compiles = count_compiles()
    shutil.rmtree(SPOOL, ignore_errors=True)
    SPOOL.mkdir()
    weight_bytes = sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(jax.eval_shape(
            lambda k: model.init_params(k, cfg), jax.random.PRNGKey(0))))
    free = shutil.disk_usage(SPOOL).free
    say(f"model {cfg.arch_id}: layers={cfg.num_layers} "
        f"d_model={cfg.d_model} vocab={cfg.vocab_size} dtype={cfg.dtype} "
        f"weight_bytes={weight_bytes} spool_free_bytes={free}")
    # deflate writes the working set twice: the REAP file and its
    # content-addressed copy in the store
    check(free > weight_bytes * 9 // 4,
          f"spool {SPOOL} has {free} bytes free, deflate needs "
          f"about {2 * weight_bytes}")

    init = jax.jit(model.init_params, static_argnums=1)
    init_s = []

    def factory(arch):
        t = time.monotonic()
        params = jax.block_until_ready(init(jax.random.PRNGKey(seed), cfg))
        init_s.append(time.monotonic() - t)
        return cfg, params

    mgr = InstanceManager(ManagerConfig(spool_dir=str(SPOOL),
                                        prefix_sharing=False), factory)
    try:
        eng = CountingEngine(mgr)
        rng = np.random.default_rng(seed)
        prompt = rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)

        def request(sid):
            return Request(TENANT, sid, prompt, max_new_tokens=NEW_TOKENS,
                           close_session=True)

        def phase(name, before, after, ms, extra=""):
            say(f"phase {name:9s} {before}->{after} ms={ms:.1f} {extra}")

        policy = PlatformPolicy(tick_interval_s=3600.0)   # no daemon deflate
        with AsyncPlatform(eng, policy, {TENANT: cfg.arch_id},
                           workers=1) as plat:
            t = time.monotonic()
            cold = plat.submit(request("cold")).result()
            cold_ms = (time.monotonic() - t) * 1e3
            inst = mgr.instances[TENANT]
            check(inst.sm.history[0][2] is Event.COLD_START
                  and any(e[1:] == ("cold_start", TENANT) for e in plat.log),
                  "first request did not cold-start the tenant")
            check((cold.state_before, cold.state_after) == ("warm", "warm"),
                  f"cold request ran {cold.state_before}->"
                  f"{cold.state_after}")
            phase("cold", "cold_start+warm", cold.state_after, cold_ms,
                  f"init_ms={init_s[0] * 1e3:.1f} "
                  f"serve_ms={cold.spans['e2e'] * 1e3:.1f} "
                  f"tokens={cold.tokens}")

            warm = plat.submit(request("warm")).result()
            check((warm.state_before, warm.state_after) == ("warm", "warm"),
                  f"warm request ran {warm.state_before}->"
                  f"{warm.state_after}")
            check(warm.tokens == cold.tokens,
                  "warm tokens differ from the cold request's")
            phase("warm", warm.state_before, warm.state_after,
                  warm.spans["e2e"] * 1e3, f"tokens={warm.tokens}")

            t = time.monotonic()
            working_set = eng.record_sample(TENANT, request("record"))
            check(inst.state is S.WARM and working_set,
                  f"REAP recording left {inst.state.value}, "
                  f"{len(working_set)} units")
            phase("record", "warm", inst.state.value,
                  (time.monotonic() - t) * 1e3,
                  f"working_set_units={len(working_set)}")

            before = digests(inst.weights)
            t = time.monotonic()
            with eng.instance_lock(TENANT):
                st = mgr.descend(TENANT, Rung.HIBERNATED)
            check(inst.state is S.HIBERNATE,
                  f"descend left {inst.state.value}")
            files = {f.name: f.stat().st_size for f in SPOOL.iterdir()}
            phase("hibernate", "warm", inst.state.value,
                  (time.monotonic() - t) * 1e3,
                  f"deflate_wrote_bytes={sum(files.values())} {files} "
                  f"(working set {st.reap_bytes}, swap {st.swap_bytes})")

            woken = plat.submit(request("woken")).result()
            check((woken.state_before, woken.state_after)
                  == ("hibernate", "woken"),
                  f"woken request ran {woken.state_before}->"
                  f"{woken.state_after}")
            check(not woken.adopted_prefix,
                  "woken request adopted a prefix instead of prefilling")
            check(woken.tokens == warm.tokens,
                  f"woken tokens {woken.tokens} != warm {warm.tokens}")
            phase("woken", woken.state_before, woken.state_after,
                  woken.spans["e2e"] * 1e3,
                  f"prefetched_bytes={woken.prefetched_bytes} "
                  f"faults={woken.faults} tokens={woken.tokens}")

            t = time.monotonic()
            pipe = inst.wake_pipeline
            check(pipe is None or pipe.wait(timeout=600),
                  "wake pipeline did not finish")
            check(not inst.nonresident_keys(),
                  f"{len(inst.nonresident_keys())} weight units "
                  f"still swapped out after the wake")
            after = digests(inst.weights)
            same = sum(before[p] == after.get(p) for p in before)
            check(same == len(before) == len(after),
                  f"{len(before) - same} weight leaves changed across "
                  f"hibernate/wake")
            say(f"inflate complete +{(time.monotonic() - t) * 1e3:.1f} ms "
                f"after the woken response; {same}/{len(before)} leaf "
                f"digests equal")
        served_peak = dev.memory_stats() or {}

        kinds = [k for k, _, _ in eng.dispatches]
        per = {k: sorted(s for kk, _, s in eng.dispatches if kk == k)
               for k in ("prefill", "decode")}
        host = {b for _, b, _ in eng.dispatches}
        check(host == {weight_bytes},
              f"dispatches uploaded {sorted(host)} bytes, not the model's "
              f"{weight_bytes}")
        say(f"dispatches={len(kinds)} prefill={kinds.count('prefill')} "
            f"decode={kinds.count('decode')} "
            f"host_to_device_weight_bytes_per_dispatch={weight_bytes} "
            + " ".join(f"median_{k}_dispatch_ms={v[len(v) // 2] * 1e3:.1f}"
                       for k, v in per.items() if v))

        # (c) cached logits, through the engine's own steps and cache
        params = jax.device_put(inst.params_pytree())
        tokens = woken.tokens
        logits0, caches, _ = _make_prefill(cfg, eng.window)(
            params, jnp.asarray(prompt)[None], None, None)
        kv = inst.kv
        kv.new_session("check")
        k, v = np.asarray(caches["k"][:, 0]), np.asarray(caches["v"][:, 0])
        for layer in range(cfg.num_layers):
            new = np.stack([k[layer], v[layer]], 1)
            kv.write_tokens("check", layer,
                            new.reshape(PROMPT_LEN, kv.token_elems), 0)
        kv.sessions["check"].num_tokens = PROMPT_LEN
        cache = eng._dense_cache(inst, ["check"],
                                 _bucket(PROMPT_LEN + NEW_TOKENS))
        decode = _make_decode(cfg, eng.window)
        cached = [logits0[0]]
        for tok in tokens[:-1]:
            logits, cache, _ = decode(params, jnp.asarray([tok], jnp.int32),
                                      cache)
            cached.append(logits[0])
        V = cfg.vocab_size
        cached = np.asarray(jnp.stack(cached)[:, :V])

        seq = jnp.asarray(np.concatenate([prompt, tokens[:-1]]))[None]
        full = jax.jit(lambda p, s: model.logits_full(p, cfg, s)[0])(
            params, seq)
        ref = np.asarray(full[0, PROMPT_LEN - 1:, :V])
        del params, full
        tol = LOGIT_TOL * float(np.abs(ref).max())
        diff = float(np.abs(cached - ref).max())
        top2 = np.sort(ref, axis=-1)[:, -2:]
        decided = top2[:, 1] - top2[:, 0] > tol
        greedy_ok = all(int(np.argmax(ref[i])) == int(np.argmax(cached[i]))
                        == tokens[i] for i in np.flatnonzero(decided))
        say(f"logits cached-vs-uncached max_abs_diff={diff:.5f} "
            f"tol={tol:.5f} (2^-4 x max|ref|={np.abs(ref).max():.3f}); "
            f"greedy tokens checked at {int(decided.sum())}/{len(tokens)} "
            f"positions with top-1 margin > tol: "
            f"{'match' if greedy_ok else 'MISMATCH'}")
        check(diff <= tol, f"cached logits differ by {diff} > {tol}")
        check(greedy_ok, "greedy tokens differ where the margin is decisive")

        stats = dev.memory_stats() or {}
        say(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"(after serving: {served_peak.get('peak_bytes_in_use')}) "
            f"bytes_limit={stats.get('bytes_limit')}")
        say(f"compiles={compiles['n']} compile_s={compiles['s']:.1f}")
    finally:
        if mgr.store is not None:
            mgr.store.close()


def _exit_on_sigterm(signum, frame):
    raise SystemExit(128 + signum)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the prompt")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    say(f"device_kind={dev.device_kind} platform={dev.platform} "
        f"count={len(jax.devices())} (a smoke, not a benchmark)")
    from repro.configs import get_config

    signal.signal(signal.SIGTERM, _exit_on_sigterm)
    try:
        run(get_config(ARCH), args.seed)
    finally:
        shutil.rmtree(SPOOL, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
