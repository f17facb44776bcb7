"""Log-normal lengths: ``{"dist": "lognormal", "median" or "mean",
"sigma", "min", "max"}``; a stated mean sets the median to
``mean * exp(-sigma**2 / 2)``, before the clip to ``[min, max]``."""

import math
import statistics

import numpy as np


def ppf(spec, q: np.ndarray) -> np.ndarray:
    sigma = float(spec["sigma"])
    if "median" in spec:
        median = float(spec["median"])
    else:
        median = float(spec["mean"]) * math.exp(-sigma * sigma / 2)
    z = np.array([statistics.NormalDist().inv_cdf(float(v)) for v in q])
    return median * np.exp(sigma * z)
