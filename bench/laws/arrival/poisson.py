"""Open-loop Poisson arrivals at `rate_per_s`: the gaps are the
exponential law's quantiles, shuffled."""
import math

import numpy as np


def count(spec: dict, seconds: float) -> int:
    return int(math.ceil(float(spec["rate_per_s"]) * seconds))


def due(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return np.cumsum(rng.permutation(-np.log1p(-u) / float(spec["rate_per_s"])))
