"""Seeds and the arrivals of open-loop traffic, made on the host.  The
deployments' data comes from their generators (``generators/<name>.py``).
"""
from __future__ import annotations

import numpy as np


def rng_for(*seed: int) -> np.random.Generator:
    """A generator for any non-negative seeds, however large."""
    return np.random.default_rng([int(s) for s in seed])


def arrival_offsets(rate_hz: float, seconds: float, base_seed: int,
                    seed: int) -> np.ndarray:
    """Due times (s from the window's start) of ``round(rate·seconds)``
    requests.  The gaps are Poisson (exponential) gaps drawn once from
    ``base_seed`` and scaled to span the window; ``seed`` only orders them,
    so every run offers the same requests over the same time."""
    count = max(1, int(round(rate_hz * seconds)))
    gaps = np.random.default_rng(base_seed).exponential(1.0, count)
    gaps *= seconds / gaps.sum()
    gaps = gaps[rng_for(seed).permutation(count)]
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])
