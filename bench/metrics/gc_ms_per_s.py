"""Event loop (`core/engine.py` `InferenceServer.step`, `core/tracing.py`):
milliseconds of Python garbage collection inside the server's steps
(`transfer_stats["gc_ns"]`) per second of the traced stretch. Host clock,
read from the program."""


def read(ctx):
    if "gc_ns" not in ctx["stats"]:
        return None
    secs = (ctx["hi"] - ctx["lo"]) / 1e9
    if secs <= 0:
        return None
    return ctx["stats"]["gc_ns"] / 1e6 / secs
