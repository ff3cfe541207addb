"""Compile requests, persistent-cache hits and the seconds spent tracing,
lowering and compiling, from ``jax.monitoring`` events."""

from __future__ import annotations


class CompileCounter:
    """Counts every backend compile request (a persistent-cache hit is a
    request too) and sums trace, lower and compile seconds."""

    _STEPS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self, jax):
        self.requests = 0
        self.cache_hits = 0
        self.seconds = dict.fromkeys(self._STEPS.values(), 0.0)
        self._monitoring = jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def close(self) -> None:
        """Stop listening (a process may run more than one cell in tests)."""
        self._monitoring.unregister_event_duration_listener(self._duration)
        self._monitoring.unregister_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        step = self._STEPS.get(event)
        if step is not None:
            self.seconds[step] += secs
        if step == "compile":
            self.requests += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def fresh(self) -> int:
        return self.requests - self.cache_hits
