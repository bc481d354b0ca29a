"""The one traffic generator: every cell's mix is a data file it reads.

A mix (``bench/workloads/<cell>.json``) gives the tenants and their
popularity, the arrival process, and the prompt and output lengths as
``{length: share}``.  For one mix every seed yields the same multiset of
lengths, tenants and inter-arrival gaps, only in another order, so runs
with different seeds do the same work.

* ``open_poisson``: ``round(rate * seconds)`` requests due in the window.
  The gaps are the exponential distribution's quantiles at ``(i + 0.5)/n``,
  shuffled and scaled so the last request is due before the window closes.
* ``closed``: ``clients_per_tenant`` clients per tenant, each with an
  endless stream of requests drawn from decks of :data:`DECK` requests
  that hold the mix's shares exactly.

Prompt token ids are drawn from the seed and are unique within a run.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional

import numpy as np

#: requests per deck in a closed loop: the length shares hold exactly in
#: every run of DECK consecutive requests of one client
DECK = 20


@dataclass
class Planned:
    """One request as the mix plans it."""

    tenant: int
    prompt: np.ndarray            # (S,) int32 token ids
    max_new: int
    due_s: Optional[float] = None    # open loop: offset from window start


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    """A generator for ``seed`` (any whole number) and a salt."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 64), *salt]))


def deck(shares: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` values whose counts follow ``shares`` as closely as ``n``
    allows (largest remainder), in an order drawn from ``rng``."""
    values = sorted(shares, key=float)
    w = np.asarray([float(shares[v]) for v in values], np.float64)
    if n <= 0 or w.sum() <= 0:
        raise ValueError(f"bad deck: n={n} shares={shares}")
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    out = np.repeat(np.asarray([int(float(v)) for v in values]), counts)
    return rng.permutation(out)


def zipf_shares(tenants: int, s: float) -> Dict[int, float]:
    """Popularity of tenant i proportional to 1 / (i + 1) ** s."""
    return {i: 1.0 / (i + 1) ** s for i in range(tenants)}


class Traffic:
    """The requests of one run of one mix, from its seed."""

    def __init__(self, mix: dict, vocab_size: int, seed: int):
        self.mix = mix
        self.vocab_size = vocab_size
        self.seed = seed
        self.tenants = int(mix["tenants"])
        self.kind = mix["arrivals"]["kind"]
        if self.kind not in ("open_poisson", "closed"):
            raise ValueError(f"unknown arrival kind {self.kind!r}")
        self._seen = set()
        self._lock = threading.Lock()      # closed-loop clients share it

    # -------------------------------------------------------------- shapes
    def prompt_lens(self) -> List[int]:
        return sorted(int(k) for k in self.mix["prompt_len"])

    def output_lens(self) -> List[int]:
        return sorted(int(k) for k in self.mix["output_len"])

    def _prompt(self, rng: np.random.Generator, n: int) -> np.ndarray:
        while True:
            p = rng.integers(0, self.vocab_size, n, dtype=np.int64)
            with self._lock:
                if p.tobytes() not in self._seen:
                    self._seen.add(p.tobytes())
                    return p.astype(np.int32)

    # -------------------------------------------------------------- open
    def open_loop(self, seconds: float) -> List[Planned]:
        """Every request due in a window of ``seconds``, by due time."""
        rate = float(self.mix["arrivals"]["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        rng = seed_rng(self.seed, 1)
        q = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-q) / rate)
        gaps *= seconds / gaps.sum()
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        pop = zipf_shares(self.tenants, float(self.mix["popularity"]["zipf_s"]))
        tenants = deck(pop, n, rng)
        plens = deck(self.mix["prompt_len"], n, rng)
        olens = deck(self.mix["output_len"], n, rng)
        return [Planned(int(t), self._prompt(rng, int(p)), int(o), float(d))
                for t, p, o, d in zip(tenants, plens, olens, due)]

    # -------------------------------------------------------------- closed
    def clients(self) -> List[int]:
        """Tenant of each closed-loop client."""
        per = int(self.mix["arrivals"].get("clients_per_tenant", 1))
        return [t for t in range(self.tenants) for _ in range(per)]

    def client_stream(self, client: int) -> Iterator[Planned]:
        """The endless request stream of one closed-loop client."""
        tenant = self.clients()[client]
        rng = seed_rng(self.seed, 2, client)
        while True:
            plens = deck(self.mix["prompt_len"], DECK, rng)
            olens = deck(self.mix["output_len"], DECK, rng)
            for p, o in zip(plens, olens):
                yield Planned(tenant, self._prompt(rng, int(p)), int(o))
