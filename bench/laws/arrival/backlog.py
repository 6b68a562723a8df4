"""A backlog: `count` requests, all due at the window's start."""
import numpy as np


def count(spec: dict, seconds: float) -> int:
    return int(spec["count"])


def due(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return np.zeros(n)
