"""Event loop (`core/engine.py` `InferenceServer.step`): 90th percentile of
the wait from a request's due time to the server's own stamp in
`InferenceServer.submit` (`RequestState.submit_s`), over the requests due
in the window. The open-loop client submits only between steps, so this
is the wait for the step in flight. Host clock, read from the program."""
import numpy as np

from bench.metrics import _stamps


def read(ctx):
    v = _stamps.gaps_ms(ctx, "due", "submit_s")
    return float(np.percentile(v, 90)) if v else None
