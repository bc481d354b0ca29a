"""The plain references against the served path, at tiny width.

The served path's tokens (prefill, then decode through the paged KV and
SSM state cache, across hibernate/wake in the SSM cell) agree with the
float32 reference within the limit, and the reference put in the
program's place in fp8 (the control) fails the same comparison.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny

from bench import oracle
from bench.reference import hybrid, ssm


def program_cfg(conf):
    from bench import harness
    return harness.program_config(conf)


@pytest.mark.parametrize("ref", [ssm, hybrid])
def test_reference_weights_and_logits_match_the_program_in_f32(ref):
    from repro.models import model

    import json
    name = "mamba2-130m" if ref is ssm else "hymba-1.5b-L8"
    conf = json.loads((bench_tiny.REPO / f"bench/configs/{name}.json")
                      .read_text())
    conf.update(num_layers=2, d_model=64, vocab_size=300, dtype="float32",
                ssm=dict(conf["ssm"], state_dim=8, head_dim=16, chunk_size=8))
    if ref is hybrid:
        conf.update(num_heads=4, num_kv_heads=2, head_dim=16, d_ff=96,
                    sliding_window=12)
    cfg = program_cfg(conf)
    key = np.array([7, 2**31 + 9], np.uint32)
    served = model.init_params(key, cfg)
    mine = ref.init(key, conf)
    flat = dict(jax.tree_util.tree_flatten_with_path(served)[0])
    assert set(flat) == set(dict(jax.tree_util.tree_flatten_with_path(mine)[0]))
    for path, leaf in jax.tree_util.tree_flatten_with_path(mine)[0]:
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(flat[path]))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 300, (2, 30)),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _, _ = model.forward_hidden(served, cfg, tokens,
                                       window=conf.get("sliding_window"))
        want = model.unembed(served, cfg, x)[..., :300]
    got = ref.forward(mine, conf, tokens)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    low = ref.forward(mine, conf, tokens, ssm.fp8_dot)
    assert float(jnp.abs(low - want).max()) > 100 * float(
        jnp.abs(got - want).max())


@pytest.mark.parametrize("cell", [bench_tiny.SSM_CELL, bench_tiny.HYBRID_CELL])
def test_served_tokens_pass_and_the_fp8_control_fails(cell, tmp_path,
                                                      monkeypatch):
    root = bench_tiny.make_root(tmp_path)
    harness = bench_tiny.on_cpu(monkeypatch)
    result, run = harness.run_cell(cell, 2**31 + 77, 2.0, False,
                                   time.monotonic(), root=root)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in run.spec.end_to_end}
    recs = oracle.sample(run)
    gaps, control, differing = oracle.reference_gaps(run, recs,
                                                     with_control=True)
    assert differing == 0 and len(gaps) >= 20
    assert gaps.max() <= bench_tiny.GAP_LIMIT[cell] < control.max()
    if cell == bench_tiny.SSM_CELL:
        woken = [r for r in recs if r.resp.state_before == "hibernate"]
        assert woken, "the sample holds no request served after a wake"
