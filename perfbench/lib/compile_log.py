"""Backend compile seconds and persistent-cache hits, per phase of a run,
from ``jax.monitoring`` (a cache hit's duration is its retrieval time)."""

from __future__ import annotations

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self):
        self.phase = "setup"
        self.seconds: dict = {}
        self.programs: dict = {}
        self.hits: dict = {}

    def on_duration(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) + secs
            self.programs[self.phase] = self.programs.get(self.phase, 0) + 1

    def on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.hits[self.phase] = self.hits.get(self.phase, 0) + 1

    def install(self) -> "CompileLog":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def of(self, phase: str) -> dict:
        return {"seconds": self.seconds.get(phase, 0.0),
                "programs": self.programs.get(phase, 0),
                "hits": self.hits.get(phase, 0)}
