"""Shared by the readers of the server's own wall-clock request stamps
(`RequestState.submit_s`, `admit_s`, `prefill_s`, `first_token_s`): the gap between two
stamps of each request due in the window."""
from typing import List, Optional


def gaps_ms(ctx, start: str, end: str, cold: bool = False
            ) -> Optional[List[float]]:
    """`end - start` in ms for each request due in the window that holds
    both stamps (`start` "due" is the client's due time); with `cold`,
    only requests admitted with their adapter off the device. None where
    the program keeps no such stamps."""
    out = []
    for r in ctx["recs"]:
        if r.st is None or r.due >= ctx["t_end"] \
                or (cold and not r.st.cold_start):
            continue
        a = r.due if start == "due" else getattr(r.st, start, None)
        b = getattr(r.st, end, None)
        if a is not None and b is not None:
            out.append((b - a) * 1e3)
    return out or None
