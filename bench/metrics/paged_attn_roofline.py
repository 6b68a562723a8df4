"""Kernel (`kernels/paged.py`): the paged decode attention kernel's share
of its roofline: the larger of (bytes it must move / HBM bandwidth) and
(FLOPs / bf16 peak), over the kernel's device time in the traced stretch,
in percent. The kernel is the custom call (Pallas) named
`paged_attn_decode*` inside the decode and megastep programs, so another
kernel in those programs adds nothing to its time; bytes and FLOPs count
each row's real context (`bench/flops.py`), not its padded pages. Device
trace."""
from bench import flops
from bench import trace as trace_lib

KERNEL = "paged_attn_decode"


def read(ctx):
    if ctx["programs"] is None:
        return None
    conf, pk = ctx["conf"], ctx["peak"]
    _, spans = trace_lib.step_ns(ctx["programs"], ctx["calls"],
                                 ("decode", "megastep"))
    kernel = trace_lib.ops_inside(ctx["trace"], 0, spans, "custom-call",
                                  prefix=KERNEL)
    ns = trace_lib.total(kernel)
    ctxs = [c for call in ctx["calls"] if call[0] != "prefill"
            for c, _ in call[1]]
    if not ns or not ctxs:
        return None
    t_bytes = flops.paged_attn_bytes(conf, ctxs) / pk["hbm_bytes_per_s"]
    t_flops = flops.paged_attn_flops(conf, ctxs) / pk["bf16_flops_per_s"]
    return 100.0 * max(t_bytes, t_flops) / (ns / 1e9)
