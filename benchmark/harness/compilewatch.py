"""Counts the executables this process builds, from jax's own monitoring
events. A copy of ``chip_smoke.CompileWatch`` (PERF.md, Open questions)."""

from __future__ import annotations

import contextlib
import time

_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
           "/jax/core/compile/jaxpr_to_mlir_module_duration",
           "/jax/core/compile/backend_compile_duration")


class CompileWatch:
    """A persistent-cache hit still counts as an executable (the program
    was new to this process) but costs almost no seconds."""

    def __init__(self):
        import jax

        self.executables = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in _EVENTS:
            self.seconds += seconds
            self.executables += event == _EVENTS[2]

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    @contextlib.contextmanager
    def window(self):
        """Yields a dict that on exit holds what happened inside the block:
        ``executables`` built, ``compile_s`` spent on them, persistent
        ``cache_hits`` and the block's ``wall_s``."""
        out = {}
        before = (self.executables, self.seconds, self.cache_hits)
        t0 = time.perf_counter()
        try:
            yield out
        finally:
            out.update(executables=self.executables - before[0],
                       compile_s=self.seconds - before[1],
                       cache_hits=self.cache_hits - before[2],
                       wall_s=time.perf_counter() - t0)
