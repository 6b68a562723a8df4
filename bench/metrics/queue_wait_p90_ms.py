"""Admission (`core/admission.py`): 90th percentile of the wait from a
request's due time to the end of the first `step()` after which it holds a
batch row, over the requests due in the window. Host clock."""
import numpy as np


def read(ctx):
    waits = [(r.admitted - r.due) * 1e3 for r in ctx["recs"]
             if r.due < ctx["t_end"] and r.admitted is not None]
    return float(np.percentile(waits, 90)) if waits else None
