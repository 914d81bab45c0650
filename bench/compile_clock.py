"""Counts and times jax compilation, from jax.monitoring events.

Copied from the repository's ``chip_smoke.CompileClock`` and extended to
count: every backend-compile event that the persistent cache did not serve
is a compilation. A window that traces and lowers again (the search driver
builds new jitted functions per campaign) shows as trace time, not as a
compile.
"""
from __future__ import annotations

TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Sums trace, lowering and backend-compile seconds; counts compiles."""

    def __init__(self, monitoring):
        self.trace_s = 0.0
        self.backend_s = 0.0
        self.n_backend = 0
        self.n_cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in TRACE_EVENTS:
            self.trace_s += duration
        elif event == BACKEND_EVENT:
            self.backend_s += duration
            self.n_backend += 1

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.n_cache_hits += 1

    @property
    def n_compiles(self) -> int:
        return self.n_backend - self.n_cache_hits

    def snapshot(self) -> dict:
        return {"compiles": self.n_compiles, "trace_s": self.trace_s,
                "backend_s": self.backend_s, "cache_hits": self.n_cache_hits}
