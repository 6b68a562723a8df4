"""The client's time to first token, as an open-loop user feels it: the
first token at the host minus the due time, 90th percentile over every
request due in the window (the whole window, not only the traced
stretch); a request without a first token counts as waiting until the
drain ended. Host clock.

`ttft_p90_ms` measured this as an end-to-end metric until its runs on one
chip spread wider than any bound the benchmark may set; here it stands
per layer, beside the readers that split it."""
import numpy as np


def read(ctx):
    v = [((r.stamps[0] if r.stamps else ctx["t_drained"]) - r.due) * 1e3
         for r in ctx["recs"] if r.due < ctx["t_end"]]
    return float(np.percentile(v, 90)) if v else None
