"""Numerics backend (`core/backend.py` `prefill_admitted` and
`prefill_chunk`): 90th percentile of the wait from the batch row to the
dispatch of the request's prefill program (`RequestState.prefill_s -
admit_s`), over the requests due in the window: the admitting step's host
work before its prefill, the first part of `first_token_lag_p90_ms`. Host
clock, read from the program."""
import numpy as np

from bench.metrics import _stamps


def read(ctx):
    v = _stamps.gaps_ms(ctx, "admit_s", "prefill_s")
    return float(np.percentile(v, 90)) if v else None
