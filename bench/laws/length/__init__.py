"""Length laws: `quantiles(spec, u)` gives the law's values at the
probabilities `u` (ascending, in (0, 1)), as floats; `bench/traffic.py`
rounds and clips them to the spec's `min` and `max`."""
