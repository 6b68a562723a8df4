"""RetraceSan — steady-state jit retrace detector.

A jitted callable retraces when it sees a new (shape, dtype, static-arg)
signature; in steady-state decode that means an avoidable compile on the
hot path. `RetraceSan.observe(name, fn)` samples ``fn._cache_size()`` after
each dispatch; once `mark_steady()` is called, any growth of a previously
observed callable's cache is recorded as a violation and `assert_clean()`
raises. Warmup retraces (before `mark_steady`) are expected and ignored —
the engine's megastep pipeline traces once per (K, batch-signature) bucket
and must then stay trace-stable.

Hooked into `core.backend.NumericsBackend` behind `sanitizers.enabled()`;
tests drive `mark_steady`/`assert_clean` directly.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from repro.analysis.sanitizers import SanitizerError


class RetraceError(SanitizerError):
    pass


def _cache_size(fn) -> Optional[int]:
    size = getattr(fn, "_cache_size", None)
    return None if size is None else int(size())


class RetraceSan:
    def __init__(self):
        self._sizes: Dict[str, int] = {}
        self._steady = False
        self.violations: List[str] = []

    def observe(self, name: str, fn) -> None:
        """Record the trace-cache size of `fn` after a dispatch under
        `name`. Growth after `mark_steady()` is a violation."""
        size = _cache_size(fn)
        if size is None:
            return
        prev = self._sizes.get(name)
        if prev is not None and size > prev and self._steady:
            self.violations.append(
                f"{name}: trace cache grew {prev} -> {size} after "
                "steady state")
        self._sizes[name] = size

    def mark_steady(self) -> None:
        """Declare warmup over: every observed callable must now be
        trace-stable."""
        self._steady = True

    def reset(self) -> None:
        self._sizes.clear()
        self._steady = False
        self.violations.clear()

    def assert_clean(self) -> None:
        if self.violations:
            raise RetraceError(
                "RetraceSan: steady-state retrace detected — "
                + "; ".join(self.violations))
