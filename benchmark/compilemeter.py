"""Compile accounting from JAX's own monitoring events, so that a compile is
counted wherever it happens: during warm-up (set-up) or, wrongly, inside the
measured window. A copy of chip_smoke.py's `_CompileMeter` (the yardstick
lives with the benchmark; PERF.md lists the original under Open questions)."""

from __future__ import annotations


class CompileMeter:
    def __init__(self):
        import jax.monitoring as mon
        self.backend_compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_writes = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += secs
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1  # emitted when an entry is written

    def snapshot(self) -> dict:
        """`compiles` counts every backend compile request, a cache hit
        included (it deserializes); `compiles - cache_hits` were built."""
        return {"backend_compile_s": self.backend_compile_s,
                "compiles": self.compiles, "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}
