"""Poisson arrivals: exponential gaps at the mean rate ``rate_rps``."""

import numpy as np


def offsets(spec, q: np.ndarray, seconds: float) -> np.ndarray:
    """Due offsets inside ``[0, seconds)`` of ``len(q)`` arrivals whose
    gaps are the exponential distribution's quantiles ``q`` (in the order
    given), scaled so that their mean is ``seconds / len(q)``; the first
    arrival is due at 0."""
    gaps = -np.log1p(-q)
    gaps = gaps / gaps.sum() * seconds
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
