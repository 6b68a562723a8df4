"""Device: the share of the time with at least one request in flight in
which no operation ran on the chip, in percent. Time with nothing in
flight (the harness in `bench.wait_arrival`) is left out, so a faster
server does not read idler. Device trace and harness spans."""
from bench import trace as trace_lib


def read(ctx):
    tr, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    if not tr.ops:
        return None
    waits = trace_lib.clip(trace_lib.span_intervals(tr, "bench.wait_arrival"),
                           lo, hi)
    live = trace_lib.subtract([(lo, hi)], waits)
    span = trace_lib.total(live)
    if not span:
        return None
    shares = []
    for chip in range(len(tr.ops)):
        busy = trace_lib.busy(tr, chip, lo, hi)
        inside = trace_lib.total(busy) - trace_lib.total(
            trace_lib.clip(trace_lib.union(
                [(s, e) for w in waits for s, e in
                 trace_lib.clip(busy, w[0], w[1])]), lo, hi))
        shares.append(1.0 - inside / span)
    return 100.0 * sum(shares) / len(shares)
