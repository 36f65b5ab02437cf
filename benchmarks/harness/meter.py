"""Backend-compile seconds and persistent-cache traffic of this process,
from jax's own monitoring events (copied from chip_smoke.py's CompileMeter;
see PERF.md, Open questions)."""
from __future__ import annotations


class CompileMeter:
    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.programs += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"programs={self.programs} "
                f"backend_compile_s={self.compile_s:.2f} "
                f"persistent_cache_hits={self.hits} misses={self.misses}")
