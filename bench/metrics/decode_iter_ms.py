"""Numerics backend, decode (`_decode_fused_fn`, `_megastep_fn`): device
time of the decode and megastep programs in the traced stretch per decode
iteration (`transfer_stats["decode_steps"]`). Device trace."""
from bench import trace as trace_lib


def read(ctx):
    if ctx["programs"] is None:
        return None
    ns, _ = trace_lib.step_ns(ctx["programs"], ctx["calls"],
                              ("decode", "megastep"))
    iters = ctx["stats"].get("decode_steps", 0)
    if not ns or not iters:
        return None
    return ns / 1e6 / iters
