"""A mixture of length laws, such as bimodal prompts: `parts` is a list of
length specs, each with a `weight`. Each part takes its share of the
probability axis in turn, so the values are the mixture's quantiles with
the parts' shares fixed: a part of weight w holds about w of the
requests at every count."""
import numpy as np


def quantiles(spec: dict, u: np.ndarray) -> np.ndarray:
    from bench import traffic
    parts = spec["parts"]
    w = np.array([float(p["weight"]) for p in parts])
    edges = np.concatenate([[0.0], np.cumsum(w / w.sum())])
    out = np.empty(len(u))
    for j, p in enumerate(parts):
        inside = (u >= edges[j]) & (u < edges[j + 1]) if j + 1 < len(parts) \
            else u >= edges[j]
        if inside.any():
            v = (u[inside] - edges[j]) / (edges[j + 1] - edges[j])
            out[inside] = traffic.lengths_at(p, v)
    return out
