"""Host-side tracing of the serving loop: the server's counter dict and the
process-wide garbage-collection clock.

Spans (`jax.profiler.TraceAnnotation`, names prefixed `serve.`) are written
where the work happens; this module holds what they share:

* `new_stats` — the one counter dict a server owns. The backend counts
  host-link crossings and decode steps into it and exposes it as
  `transfer_stats`; the server adds its wall time (`step_ns`), the time
  blocked reading tokens back (`readback_ns`) and the garbage collection
  that ran inside its steps (`gc_ns`, `gc_runs`).
* `install_gc_hook` — one `gc.callbacks` hook per process (not per
  server), which times every collection and marks it with a `serve.gc`
  span; `gc_totals` reads its running totals.

With no profiler running a span costs about a microsecond.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, Tuple

import jax

STATS = ("h2d", "h2d_bytes", "d2h", "d2h_bytes", "decode_steps",
         "megasteps", "megastep_iters", "prefills", "prefill_chunks",
         "step_ns", "readback_ns", "gc_ns", "gc_runs")

_gc = {"ns": 0, "runs": 0, "t0": 0, "span": None}


def new_stats() -> Dict[str, int]:
    return dict.fromkeys(STATS, 0)


def count_upload(stats: Dict[str, int], nbytes: int):
    """One host-to-device copy of `nbytes` (an adapter's weights)."""
    stats["h2d"] += 1
    stats["h2d_bytes"] += nbytes


def _on_gc(phase: str, info: dict):
    if phase == "start":
        _gc["span"] = jax.profiler.TraceAnnotation(
            "serve.gc", generation=info["generation"])
        _gc["span"].__enter__()
        _gc["t0"] = time.perf_counter_ns()
        return
    if _gc["span"] is None:
        return                      # hook installed mid-collection
    _gc["ns"] += time.perf_counter_ns() - _gc["t0"]
    _gc["runs"] += 1
    _gc["span"].__exit__(None, None, None)
    _gc["span"] = None


def install_gc_hook():
    """Time every garbage collection in this process from now on
    (idempotent: servers call it on construction)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_totals() -> Tuple[int, int]:
    """Nanoseconds spent in, and number of, collections since the hook was
    installed."""
    return _gc["ns"], _gc["runs"]
