"""Event loop (`core/engine.py` `InferenceServer.step`): host wall time
inside `step()` less the time blocked reading tokens back
(`DecodePipeline._drain_one`), per decode iteration, over the traced
stretch. Host clock."""


def read(ctx):
    iters = ctx["stats"].get("decode_steps", 0)
    if not iters:
        return None
    return (ctx["step_s"] - ctx["readback_s"]) / iters * 1e3
