"""Compilation seen through ``jax.monitoring``.

Copied from ``chip_smoke.CompileClock`` (the union of tracing, lowering and
backend-compile intervals, plus persistent-cache hits), with event counts
so that the harness can report how many programs were lowered or compiled
inside the measured window.
"""

from __future__ import annotations

import time

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        import jax

        self._spans: list[tuple[float, float]] = []
        self.counts = {"lowerings": 0, "backend_compiles": 0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event in (TRACE, LOWER, COMPILE):
            end = time.perf_counter()
            self._spans.append((end - secs, end))
        if event == LOWER:
            self.counts["lowerings"] += 1
        elif event == COMPILE:
            self.counts["backend_compiles"] += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.counts["cache_hits"] += 1

    @property
    def seconds(self) -> float:
        """Length of the union of all compile-event intervals so far."""
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self._spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total

    def snapshot(self) -> dict:
        return dict(self.counts, seconds=self.seconds)
