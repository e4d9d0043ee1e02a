"""Compilations, counted from ``jax.monitoring`` (copied from the program's
``chip_smoke.CompileWatch``)."""

from __future__ import annotations


class CompileWatch:
    """Compile seconds, lowerings and compilations (``compiles``), and
    persistent-cache hits."""

    # lowering and XLA compile; tracing is left out, since the traces of
    # nested jitted functions are timed inside their caller's
    _COMPILE_EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self._COMPILE_EVENTS:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
