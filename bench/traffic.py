"""The one traffic generator.  A traffic mix is a JSON file under
``bench/traffic/`` that this module reads; nothing here knows a mix by name.

Every seed gets the same *set* of arrivals in another order: the gaps
between arrivals are the quantiles of an exponential distribution at
``rate_per_s`` (a Poisson process conditioned on its count), permuted by the
seed.  So two seeds do the same work, and a seed changes only the order and
the content.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` (any non-negative int) and a sub-stream."""
    return np.random.default_rng([int(seed), *stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def arrival_times(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds) of ``round(rate_per_s * seconds)``
    open-loop arrivals with exponential gaps (the Poisson arithmetic of
    ``repro.serve.frontdoor.poisson_arrivals``, with its gaps taken at fixed
    quantiles so every seed offers the same load)."""
    if rate_per_s <= 0 or seconds <= 0:
        raise ValueError("rate_per_s and seconds must be > 0")
    n = max(1, round(rate_per_s * seconds))
    gaps = -np.log1p(-_quantiles(n)) / rate_per_s
    gaps = rng(seed, 1).permutation(gaps)
    t = np.cumsum(gaps)
    # the first arrival comes one gap in; the window holds all n of them
    return t * (seconds / (t[-1] + gaps.mean()))

