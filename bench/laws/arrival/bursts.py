"""On/off bursts: Poisson at `rate_on_per_s` for `on_s` seconds, then at
`rate_off_per_s` (default 0) for `off_s`, repeated from the window's
start. Unit-rate exponential gaps (quantiles, shuffled) are laid out in
the process's own time and mapped to wall time through its cumulative
rate, so every seed has the same bursts and the same number in each."""
import math

import numpy as np


def _rates(spec):
    on, off = float(spec["on_s"]), float(spec["off_s"])
    r_on, r_off = float(spec["rate_on_per_s"]), float(spec.get(
        "rate_off_per_s", 0.0))
    return on, off, r_on, r_off


def count(spec: dict, seconds: float) -> int:
    on, off, r_on, r_off = _rates(spec)
    cycles, rest = divmod(seconds, on + off)
    mass = cycles * (r_on * on + r_off * off) + r_on * min(rest, on) \
        + r_off * max(0.0, rest - on)
    return int(math.ceil(mass))


def due(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    on, off, r_on, r_off = _rates(spec)
    u = (np.arange(n) + 0.5) / n
    x = np.cumsum(rng.permutation(-np.log1p(-u)))
    per = r_on * on + r_off * off
    k, r = np.divmod(x, per)
    in_on = r < r_on * on
    t_off = on + (r - r_on * on) / r_off if r_off > 0 else np.full_like(r, on + off)
    return k * (on + off) + np.where(in_on, r / r_on, t_off)
