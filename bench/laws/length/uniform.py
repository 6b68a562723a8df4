"""Uniform lengths over the whole numbers from `min` to `max`."""
import numpy as np


def quantiles(spec: dict, u: np.ndarray) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    return np.floor(lo + u * (hi - lo + 1))
