"""Seconds and counts of XLA compiles, from JAX's own monitoring events
(a copy of ``chip_smoke.CompileClock``, kept with the benchmark)."""
from __future__ import annotations


class CompileClock:
    """Compile seconds, compile count and persistent-cache hits so far."""

    def __init__(self):
        import jax
        self.s = 0.0
        self.n = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.s += duration
            self.n += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snap(self) -> tuple[float, int, int]:
        return self.s, self.n, self.cache_hits

    def since(self, snap) -> dict:
        return dict(compile_s=self.s - snap[0], compiles=self.n - snap[1],
                    cache_hits=self.cache_hits - snap[2])
