"""Zipf popularity over popularity rank with exponent `zipf_alpha`
(0, the default, is uniform)."""
import numpy as np


def weights(adapters: dict) -> np.ndarray:
    alpha = float(adapters.get("zipf_alpha", 0.0))
    w = 1.0 / np.arange(1, adapters["count"] + 1) ** alpha
    return w / w.sum()
