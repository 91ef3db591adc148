"""Counts programs traced or compiled while a block runs: the measured
window should count none."""
from __future__ import annotations

import jax

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/backend_compile_duration",
          "/jax/compilation_cache/cache_retrieval_time_sec")


class Counter:
    def __init__(self):
        self.count = 0

    def _listen(self, event, duration, **_):
        if event in EVENTS:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False
