"""The one generator of open-loop traffic that every mix file feeds.

A mix states its ``rate`` (requests per virtual second) and a
``requests`` count. The generator takes the ``requests`` quantiles of a
unit-rate exponential as the gaps between arrivals, puts them in an
order drawn from the seed, and divides by the rate. So every seed offers
the same number of requests and the same gaps in another order; what the
seed changes is which request comes when. Prompts are drawn by the
served runtime from the process's ``seed`` (see ``prompts``).
"""
from __future__ import annotations

import numpy as np


def unit_gaps(n: int) -> np.ndarray:
    """The n quantiles (at (k + 1/2) / n) of a unit-rate exponential."""
    return -np.log1p(-(np.arange(n) + 0.5) / n)


class MixArrivals:
    """An arrival process in the shape the served runtime reads:
    ``generate(horizon)``, ``rates(seconds)`` and ``seed``."""

    def __init__(self, mix: dict, seed: int):
        self.seed = int(seed)
        self.rate = float(mix["rate"])
        rng = np.random.default_rng([self.seed, 0x61727276])
        gaps = rng.permutation(unit_gaps(int(mix["requests"])))
        self.times = np.cumsum(gaps) / self.rate
        self.times.flags.writeable = False

    @property
    def span(self) -> float:
        """Virtual seconds from the first to the last arrival, plus one."""
        return float(self.times[-1]) + 1.0

    def generate(self, horizon: float) -> np.ndarray:
        return self.times[self.times < horizon]

    def rates(self, seconds: int) -> np.ndarray:
        """Expected arrivals in each whole virtual second."""
        return np.full(int(seconds), self.rate)


def prompts(seed: int, n: int, vocab: int, seq_len: int) -> np.ndarray:
    """The prompts the served runtime draws for ``n`` arrivals of a
    process with ``seed``: tokens in [1, vocab), ``seq_len`` each, from a
    NumPy generator seeded with ``seed + 1``, one request after another.
    The harness draws them again to check what reached the first stage."""
    rng = np.random.default_rng(seed + 1)
    return np.stack([rng.integers(1, vocab, size=seq_len).astype(np.int32)
                     for _ in range(n)])
