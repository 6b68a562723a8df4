"""Event loop (`core/engine.py` `InferenceServer.step`): the server's own
count of its wall time inside `step()` less the time blocked in the
readback's `jax.device_get` (`transfer_stats` `step_ns - readback_ns`),
per decode iteration (`decode_steps`), over the traced stretch: the inside
twin of `host_ms_per_iter`. Host clock, read from the program."""


def read(ctx):
    s = ctx["stats"]
    if "step_ns" not in s or "readback_ns" not in s:
        return None
    iters = s.get("decode_steps", 0)
    if not iters:
        return None
    return (s["step_ns"] - s["readback_ns"]) / iters / 1e6
