"""A run whose timed path is broken underneath comes out not correct.

The harness runs as on the chip (set-up, window, drain, check) with the
chip check stubbed; the engine's compiled decode step is broken in one of
the ways a serving cell can break: a token altered where it is produced,
or a step that returns its cache (the KV and SSM state) unchanged.
"""
import time

import jax.numpy as jnp
import pytest

import bench_tiny


def broken_engine(harness, fault):
    make = harness.make_engine_class

    def factory():
        Base = make()

        class Broken(Base):
            def _compiled(self, inst, kind, B, Sb, *rest):
                fn = super()._compiled(inst, kind, B, Sb, *rest)
                if kind != "decode":
                    return fn
                V = inst.cfg.vocab_size

                def step(params, tokens, cache):
                    logits, new_cache, aux = fn(params, tokens, cache)
                    if fault == "state_unchanged":
                        return logits, cache, aux
                    top = jnp.argmax(logits[:, :V], -1)
                    rows = jnp.arange(logits.shape[0])
                    return logits.at[rows, (top + 1) % V].set(1e4), \
                        new_cache, aux
                return step
        return Broken
    return factory


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
@pytest.mark.parametrize("cell", [bench_tiny.SSM_CELL, bench_tiny.HYBRID_CELL])
def test_a_broken_step_is_not_correct(cell, fault, tmp_path, monkeypatch):
    root = bench_tiny.make_root(tmp_path)
    harness = bench_tiny.on_cpu(monkeypatch)
    monkeypatch.setattr(harness, "make_engine_class",
                        broken_engine(harness, fault))
    result, _ = harness.run_cell(cell, 2**31 + 78, 1.5, False,
                                 time.monotonic(), root=root)
    assert result["correct"] is False
    gap = result["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
