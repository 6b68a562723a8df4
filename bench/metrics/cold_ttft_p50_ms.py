"""Cold start and CPU assist (`core/cold_start.py`, `core/lora.py`):
median time to first token of the requests due in the window whose adapter
was not on the device when they were admitted (`RequestState.cold_start`).
Host clock."""
import numpy as np


def read(ctx):
    v = [(r.stamps[0] - r.due) * 1e3 for r in ctx["recs"]
         if r.due < ctx["t_end"] and r.stamps and r.st.cold_start]
    return float(np.percentile(v, 50)) if v else None
