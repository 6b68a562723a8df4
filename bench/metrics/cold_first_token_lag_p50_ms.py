"""Cold start and CPU assist (`core/cold_start.py`, `core/lora.py`): median
wait from the batch row to the first token (`RequestState.first_token_s -
admit_s`) of the requests due in the window whose adapter was not on the
device when they were admitted (`cold_start`). Host clock, read from the
program."""
import numpy as np

from bench.metrics import _stamps


def read(ctx):
    v = _stamps.gaps_ms(ctx, "admit_s", "first_token_s", cold=True)
    return float(np.percentile(v, 50)) if v else None
