"""What compiled, and when: ``jax.monitoring`` duration events, stamped with
the host clock, and the new files in the persistent compile cache."""

from __future__ import annotations

import os
import time

#: trace + lower + compile (or cache load) of a program's first call
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    def __init__(self, cache_dir: str | None):
        self.events: list[tuple[float, str, float]] = []
        self.cache_dir = cache_dir
        self._cache_before = self._cache_entries()

    def install(self) -> None:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, duration: float, **_kw) -> None:
        if name in COMPILE_EVENTS:
            self.events.append((time.monotonic(), name, float(duration)))

    def _cache_entries(self) -> set:
        if not self.cache_dir or not os.path.isdir(self.cache_dir):
            return set()
        return {f for f in os.listdir(self.cache_dir)
                if not f.endswith("-atime")}

    def compile_seconds_before(self, t: float) -> float:
        """Seconds spent tracing, lowering and compiling (or loading from
        the cache) before ``t``, summed over threads."""
        return sum(d for (at, _n, d) in self.events if at <= t)

    def backend_compiles_between(self, t0: float, t1: float) -> int:
        return sum(1 for (at, n, _d) in self.events
                   if n == BACKEND_COMPILE and t0 < at <= t1)

    def traces_between(self, t0: float, t1: float) -> int:
        return sum(1 for (at, n, _d) in self.events
                   if n != BACKEND_COMPILE and t0 < at <= t1)

    def new_cache_entries(self) -> list[str]:
        return sorted(self._cache_entries() - self._cache_before)
