"""Count what JAX traces and compiles while a block runs."""

from __future__ import annotations

import jax.monitoring as monitoring

TRACE = "/jax/core/compile/jaxpr_trace_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """``with CompileCounter() as c: ...`` then ``c.traces``, ``c.count``
    (backend compiles, persistent-cache hits excluded)."""

    def __init__(self):
        self.traces = 0
        self.count = 0

    def _on(self, name: str, secs: float, **kw) -> None:
        if name == TRACE:
            self.traces += 1
        elif name == COMPILE:
            self.count += 1

    def __enter__(self):
        monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        monitoring.unregister_event_duration_listener(self._on)
        return False
