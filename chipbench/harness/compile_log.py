"""Backend compiles and persistent-cache hits, from JAX's own monitoring
events (a copy of the program's ``chip_smoke.CompileLog``)."""
from __future__ import annotations


class CompileLog:
    def __init__(self):
        from jax import monitoring
        self.secs, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.secs, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}

    def new_programs(self, since: dict) -> int:
        """Programs compiled or loaded from the cache since ``since``."""
        now = self.snapshot()
        return ((now["compiles"] - since["compiles"])
                + (now["cache_hits"] - since["cache_hits"]))
