"""Numerics backend (`core/backend.py` `prefill_admitted`, the readback in
`DecodePipeline._drain_one`): 90th percentile of the wait from the batch
row to the first token in `RequestState.generated` (`first_token_s -
admit_s`), over the requests due in the window: the admitting step, its
prefill, and the readback one step behind. Host clock, read from the
program."""
import numpy as np

from bench.metrics import _stamps


def read(ctx):
    v = _stamps.gaps_ms(ctx, "admit_s", "first_token_s")
    return float(np.percentile(v, 90)) if v else None
