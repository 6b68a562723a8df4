"""Numerics backend (the readback in `core/backend.py`
`DecodePipeline._drain_one`): 90th percentile of the wait from the
dispatch of the request's prefill program to its first token in
`RequestState.generated` (`first_token_s - prefill_s`), over the requests
due in the window: the prefill on the device, the steps queued before it,
and the readback one step behind; the second part of
`first_token_lag_p90_ms`. Host clock, read from the program."""
import numpy as np

from bench.metrics import _stamps


def read(ctx):
    v = _stamps.gaps_ms(ctx, "prefill_s", "first_token_s")
    return float(np.percentile(v, 90)) if v else None
