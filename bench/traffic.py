"""The one traffic generator: reads a traffic file's parameters and makes a
run's requests from ``--seed``.

Kinds:

* ``full``: back-to-back full-graph forwards; nothing to generate.
* ``open``: an open loop.  ``rate_per_s`` Poisson arrivals over the window,
  each asking for ``targets_min``..``targets_max`` target ids (uniform).
* ``closed``: ``clients`` clients, each keeping one such request
  outstanding.

Ids are ``uniform`` or ``zipf`` (exponent ``zipf_s``) over a permutation of
the target type drawn from the seed, so the hot set moves with the seed.
Every seed gets the same multiset of request sizes and inter-arrival gaps
(drawn from the file's ``base_seed``), in an order drawn from the seed: a
seed changes which ids and in what order, not how much work arrives.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def zipf_probs(n: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return p / p.sum()


class Ids:
    """Seeded id source over ``n`` targets."""

    def __init__(self, spec: Dict, n: int, rng: np.random.Generator):
        self.rng = rng
        self.n = n
        self.perm = rng.permutation(n)
        self.p = (zipf_probs(n, float(spec["zipf_s"]))
                  if spec["ids"] == "zipf" else None)

    def draw(self, counts: np.ndarray) -> List[np.ndarray]:
        """One id array per entry of ``counts``."""
        k = int(np.sum(counts))
        if self.p is None:
            flat = self.rng.integers(0, self.n, size=k)
        else:
            flat = self.perm[self.rng.choice(self.n, size=k, p=self.p)]
        return np.split(flat.astype(np.int64), np.cumsum(counts)[:-1])


def sizes(spec: Dict, count: int, rng: np.random.Generator) -> np.ndarray:
    """``count`` request sizes: the base multiset, in a seeded order."""
    base = np.random.default_rng(spec["base_seed"]).integers(
        spec["targets_min"], spec["targets_max"] + 1, size=count)
    return rng.permutation(base)


def open_loop(spec: Dict, seconds: float, n_targets: int,
              seed: int) -> List[Dict]:
    """``[{"due": s, "targets": ids}, ...]`` with due times in
    ``[0, seconds)``: ``round(rate * seconds)`` requests whose exponential
    gaps (the first one from the window's start) are the base multiset,
    shuffled by the seed and scaled to end half a mean gap before the
    window does."""
    rng = np.random.default_rng(seed)
    count = max(1, int(round(float(spec["rate_per_s"]) * seconds)))
    gaps = np.random.default_rng(spec["base_seed"] + 1).exponential(
        size=count)
    due = np.cumsum(rng.permutation(gaps))
    due *= (seconds - 0.5 * seconds / count) / due[-1]
    ids = Ids(spec, n_targets, rng).draw(sizes(spec, count, rng))
    return [{"due": float(t), "targets": x} for t, x in zip(due, ids)]


class ClosedLoop:
    """``clients`` clients; :meth:`next_batch` gives each its next
    request, from a pool of ``period`` requests made before the window and
    taken in turn."""

    def __init__(self, spec: Dict, n_targets: int, seed: int,
                 period: int = 32768):
        rng = np.random.default_rng(seed)
        self.clients = int(spec["clients"])
        self.pool = Ids(spec, n_targets, rng).draw(sizes(spec, period, rng))
        self.i = 0

    def next_batch(self) -> List[np.ndarray]:
        out = [self.pool[(self.i + c) % len(self.pool)]
               for c in range(self.clients)]
        self.i += self.clients
        return out
