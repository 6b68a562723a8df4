"""Lognormal lengths: `median` and `sigma` (of the log)."""
import statistics

import numpy as np


def quantiles(spec: dict, u: np.ndarray) -> np.ndarray:
    z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
    return np.round(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
