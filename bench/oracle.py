"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window answered, drawn from the seed with the longest in
it, goes through the family's plain float32 reference: the prompt and the
served tokens, one forward pass.  For each served token the reference's
best logit minus the served token's logit is its gap; the widest gap is
compared with the mix's ``check.logit_gap_limit``.  The reference makes
each tenant's weights itself from the seed; their bytes are compared with
the program's after every tenant was woken to full residency, so what a
wake restored is checked bit for bit (limit 0).  Every request due in the
window has to be answered with all its tokens (limit 0).

``control_gaps`` is the reference put in the program's place in fp8: at
each position the token that fp8 puts first, and its gap under the f32
reference.  The benchmark's runs do not call it (see ``bench/control.py``).
"""
from __future__ import annotations

import hashlib
from typing import Dict, List

import numpy as np

from bench import traffic as traffic_mod


def leaf_paths(tree, prefix="") -> Dict[str, object]:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(leaf_paths(v, path))
        else:
            out[path] = v
    return out


def sample(run) -> List:
    """Answered requests due in the window: the longest first, then an
    order drawn from the seed, until ``check.sample_tokens`` served tokens
    are in."""
    recs = [r for r in run.due() if r.ok]
    if not recs:
        return []
    rng = traffic_mod.seed_rng(run.seed, 4)
    order = [recs[i] for i in rng.permutation(len(recs))]
    longest = max(order, key=lambda r: (len(r.plan.prompt)
                                        + len(r.resp.tokens)))
    out, n = [], 0
    for r in [longest] + [r for r in order if r is not longest]:
        out.append(r)
        n += len(r.resp.tokens)
        if n >= int(run.spec.mix["check"]["sample_tokens"]):
            break
    return out


def rows(recs, length: int):
    """Token ids (R, length), gap start positions (R,), served tokens
    (R, T) padded with -1."""
    T = max(len(r.resp.tokens) for r in recs)
    tokens = np.zeros((len(recs), length), np.int32)
    starts = np.zeros((len(recs),), np.int32)
    served = np.full((len(recs), T), -1, np.int32)
    for i, r in enumerate(recs):
        seq = np.concatenate([r.plan.prompt, r.resp.tokens[:-1]])
        tokens[i, :len(seq)] = seq
        starts[i] = len(r.plan.prompt) - 1
        served[i, :len(r.resp.tokens)] = r.resp.tokens
    return tokens, starts, served


def gap_fns(conf: dict):
    """Jitted (program gaps, control gaps) over one block of rows."""
    import jax
    import jax.numpy as jnp
    from bench.harness import family
    from bench.reference.ssm import exact_dot, fp8_dot

    ref = family("reference", conf)

    def at(logits, starts, T):
        idx = starts[:, None] + jnp.arange(T)[None]
        idx = jnp.clip(idx, 0, logits.shape[1] - 1)
        return jnp.take_along_axis(logits, idx[..., None], axis=1)

    def gaps(params, tokens, starts, served):
        lg = at(ref.forward(params, conf, tokens, exact_dot), starts,
                served.shape[1])
        pick = jnp.clip(served, 0)[..., None]
        return lg.max(-1) - jnp.take_along_axis(lg, pick, -1)[..., 0]

    def control(params, tokens, starts, served):
        T = served.shape[1]
        ex = at(ref.forward(params, conf, tokens, exact_dot), starts, T)
        lo = at(ref.forward(params, conf, tokens, fp8_dot), starts, T)
        pick = jnp.argmax(lo, -1)[..., None]
        return ex.max(-1) - jnp.take_along_axis(ex, pick, -1)[..., 0]

    return jax.jit(gaps), jax.jit(control)


def cell_length(mix: dict) -> int:
    """Tokens the reference sees for the cell's longest request."""
    return (max(int(k) for k in mix["prompt_len"])
            + max(int(k) for k in mix["output_len"]) - 1)


def reference_gaps(run, recs, with_control: bool = False):
    """Per tenant: the reference's weights, their digests, and the gaps of
    ``recs`` (and of the control).  Returns (gaps, control_gaps,
    leaves_differing)."""
    import jax
    from bench.harness import family, tenant_key

    conf = run.conf
    ref = family("reference", conf)
    init = jax.jit(lambda k: ref.init(k, conf))
    gaps_fn, control_fn = gap_fns(conf)
    length, R = cell_length(run.spec.mix), ref.ROWS
    by_tenant: Dict[int, list] = {}
    for r in recs:
        by_tenant.setdefault(r.plan.tenant, []).append(r)
    gaps, control, differing = [], [], 0
    for t in range(int(run.spec.mix["tenants"])):
        if not by_tenant.get(t) and f"t{t}" not in run.check_digests:
            continue
        params = init(tenant_key(run.seed, t))
        want = run.check_digests.get(f"t{t}")
        if want is not None:
            for path, leaf in leaf_paths(params).items():
                got = hashlib.blake2b(np.ascontiguousarray(leaf).view(np.uint8)
                                      ).hexdigest()
                differing += got != want.get(path)
            differing += len(set(want) - set(leaf_paths(params)))
        todo = by_tenant.get(t, [])
        for i in range(0, len(todo), R):
            block = todo[i:i + R]
            tokens, starts, served = rows(block + block[:1] * (R - len(block)),
                                          length)
            g = np.asarray(gaps_fn(params, tokens, starts, served))
            mask = served >= 0
            gaps.append(g[:len(block)][mask[:len(block)]])
            if with_control:
                c = np.asarray(control_fn(params, tokens, starts, served))
                control.append(c[:len(block)][mask[:len(block)]])
        del params
    cat = (lambda xs: np.concatenate(xs) if xs else np.zeros((0,)))
    return cat(gaps), cat(control), differing


def check(run) -> Dict[str, dict]:
    due = run.due()
    recs = sample(run)
    gaps, _, differing = reference_gaps(run, recs)
    lim = run.spec.mix["check"]["logit_gap_limit"]
    return {
        "unanswered": {"value": sum(not r.ok for r in due), "limit": 0},
        "weight_leaves_differing": {"value": int(differing), "limit": 0},
        "logit_gap": {"value": float(gaps.max()) if len(gaps) else None,
                      "limit": lim, "tokens": int(len(gaps)),
                      "requests": len(recs)},
    }


def passes(c: dict) -> bool:
    return (c["value"] is not None and c["limit"] is not None
            and c["value"] <= c["limit"])
