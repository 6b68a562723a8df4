"""Numerics backend, prefill (`NumericsBackend.prefill_admitted`): device
time of the prefill programs in the traced stretch per 1,000 prompt tokens
they admitted (true lengths, not padding). Device trace."""
from bench import trace as trace_lib


def read(ctx):
    if ctx["programs"] is None:
        return None
    ns, _ = trace_lib.step_ns(ctx["programs"], ctx["calls"], ("prefill",))
    tokens = sum(n for call in ctx["calls"] if call[0] == "prefill"
                 for n, _ in call[1])
    if not ns or not tokens:
        return None
    return ns / 1e6 / (tokens / 1e3)
