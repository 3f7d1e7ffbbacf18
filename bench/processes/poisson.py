"""Poisson arrivals with the count fixed: ``round(rate * seconds)`` uniform
order statistics over the window (a Poisson process conditioned on its count)."""
from __future__ import annotations

import numpy as np


def times(rng, rate_hz: float, seconds: float, params: dict) -> np.ndarray:
    return np.sort(rng.uniform(0.0, seconds, int(round(rate_hz * seconds))))
