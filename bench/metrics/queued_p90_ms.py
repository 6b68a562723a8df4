"""Admission (`core/admission.py` `AdmissionPlane.admit`): 90th percentile
of the wait from `InferenceServer.submit` to the batch row
(`RequestState.admit_s - submit_s`), over the requests due in the window:
all rows full, or the KV pages or adapter slots run out. Host clock, read
from the program."""
import numpy as np

from bench.metrics import _stamps


def read(ctx):
    v = _stamps.gaps_ms(ctx, "submit_s", "admit_s")
    return float(np.percentile(v, 90)) if v else None
