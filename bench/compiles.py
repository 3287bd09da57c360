"""Count JAX compilations, so a run can show that none fell inside its
measured window. JAX reports each backend compile (or persistent-cache
load) and each jaxpr trace through :mod:`jax.monitoring`."""
from __future__ import annotations

import threading

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        self._lock = threading.Lock()
        self.traces = 0
        self.compiles = 0

    def _on(self, event: str, secs: float, **_) -> None:
        with self._lock:
            if event == TRACE_EVENT:
                self.traces += 1
            elif event == COMPILE_EVENT:
                self.compiles += 1

    def install(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def snapshot(self):
        with self._lock:
            return self.traces, self.compiles
