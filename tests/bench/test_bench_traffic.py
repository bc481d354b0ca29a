"""Every seed of a mix yields the same work in another order."""
import json
from collections import Counter

import numpy as np
import pytest

from bench_tiny import REPO, WAKE_MIX as WAKE

from bench import traffic

WARM = json.loads((REPO / "bench/workloads/mamba2-130m.warm_closed.json")
                  .read_text())


def summary(plans):
    return (Counter(p.tenant for p in plans),
            Counter(len(p.prompt) for p in plans),
            Counter(p.max_new for p in plans),
            sorted(np.round(np.diff([p.due_s for p in plans] + [51.0]), 9)))


@pytest.mark.parametrize("seed", [0, 2**31 + 11, 2**40 + 3])
def test_open_loop_same_multiset_every_seed(seed):
    ref = traffic.Traffic(WAKE, 50280, 1).open_loop(51.0)
    got = traffic.Traffic(WAKE, 50280, seed).open_loop(51.0)
    assert len(got) == round(WAKE["arrivals"]["rate_per_s"] * 51.0)
    assert summary(got)[:3] == summary(ref)[:3]
    assert np.allclose(summary(got)[3], summary(ref)[3])
    assert all(0 <= p.due_s < 51.0 for p in got)
    assert len({p.prompt.tobytes() for p in got}) == len(got)
    # Zipf: the first tenant is the most popular
    counts = summary(got)[0]
    assert counts[0] == max(counts.values())


def test_closed_loop_deck_holds_the_shares():
    t = traffic.Traffic(WARM, 50280, 2**31 + 5)
    stream = t.client_stream(2)
    plans = [next(stream) for _ in range(traffic.DECK * 3)]
    assert {p.tenant for p in plans} == {t.clients()[2]}
    assert Counter(len(p.prompt) for p in plans) == {128: 42, 512: 18}
    assert Counter(p.max_new for p in plans) == {8: 30, 32: 30}
    again = traffic.Traffic(WARM, 50280, 2**31 + 5).client_stream(2)
    assert all(np.array_equal(next(again).prompt, p.prompt) for p in plans)


def test_deck_counts_by_largest_remainder():
    rng = np.random.default_rng(0)
    d = traffic.deck({"128": 0.7, "512": 0.3}, 7, rng)
    assert Counter(d.tolist()) == {128: 5, 512: 2}
