"""Model step (`models/transformer.py` through the backend): the model
FLOPs of every prefill, decode and megastep program in the traced stretch
(`bench/flops.py`: true lengths, contexts and ranks) over their device
time times the chip's bf16 peak, in percent. Device trace."""
from bench import flops
from bench import trace as trace_lib


def read(ctx):
    if ctx["programs"] is None:
        return None
    conf = ctx["conf"]
    ns, _ = trace_lib.step_ns(ctx["programs"], ctx["calls"],
                              ("prefill", "decode", "megastep"))
    work = 0
    for call in ctx["calls"]:
        if call[0] == "prefill":
            work += sum(flops.prefill_flops(conf, n, r) for n, r in call[1])
        else:
            work += flops.decode_flops(conf, call[1])
    if not ns or not work:
        return None
    return 100.0 * work / (ns / 1e9 * ctx["peak"]["bf16_flops_per_s"])
