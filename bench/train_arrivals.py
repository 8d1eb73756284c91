"""The arrival streams a training mix feeds: a copy of the program's own.

The program trains on the twin with one arrival process per environment
and episode, drawn by ``ScenarioSpec.train_arrivals(stream)``
(``api/specs.py``) from the registered scenario (``serving/arrivals.py``).
This file states the same streams from the mix file alone, so the
yardstick stays here: the driver refuses to run where the program's
stream differs from this one, and the plain replay of the twin reads its
arrivals from here.

- the rate profile, per whole virtual second: ``base_rate`` with a
  sinusoid of ``diurnal_amplitude`` and period ``diurnal_period``, and
  square bursts to ``burst_rate`` for ``burst_len`` seconds every
  ``period`` seconds;
- the stream's seed: the scenario's seed plus ``seed_stride`` times the
  stream's number plus one;
- the arrivals: from a NumPy generator with that seed, every second's
  count drawn at once (Poisson at that second's rate), then every offset
  within its second (uniform), sorted, those before ``horizon`` kept.

So the count of each second is Poisson, and the seed changes both how
many requests come and when.
"""
from __future__ import annotations

import numpy as np


def rates(mix: dict, seconds: int) -> np.ndarray:
    """The mix's rate in each whole virtual second (req/s)."""
    t = np.arange(int(seconds), dtype=np.float64)
    lam = mix["base_rate"] * (1.0 + mix["diurnal_amplitude"]
                              * np.sin(2 * np.pi * t / mix["diurnal_period"]))
    lam[(t % mix["period"]) < mix["burst_len"]] = mix["burst_rate"]
    return lam


def stream_seed(mix: dict, scenario_seed: int, stream: int) -> int:
    return int(scenario_seed) + mix["seed_stride"] * (int(stream) + 1)


def times(mix: dict, scenario_seed: int, stream: int) -> np.ndarray:
    """Stream ``stream``'s sorted arrival instants in [0, horizon)."""
    horizon = mix["horizon"]
    seconds = int(np.ceil(horizon))
    rng = np.random.default_rng(stream_seed(mix, scenario_seed, stream))
    counts = rng.poisson(np.clip(rates(mix, seconds), 0.0, None))
    base = np.repeat(np.arange(seconds, dtype=np.float64), counts)
    out = np.sort(base + rng.random(int(counts.sum())))
    return out[out < horizon]
