"""Host spans around the calls into each layer, and compile accounting.

While the profiler runs, each span is a ``TraceAnnotation`` named
``bench:<name>``, so the trace holds it on the device trace's clock; the
per-layer readers take spans from there.  Without the profiler a span
costs nothing.

Compiles are counted from JAX's ``/jax/core/compile/`` monitoring events,
as ``chip_smoke.py`` counts them: one ``backend_compile_duration`` event
per executable built or loaded from the persistent cache.
"""

from __future__ import annotations

import contextlib

PREFIX = "bench:"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(PREFIX + name)


class Compiles:
    """Counts executables built (or loaded) and the seconds spent."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def install(self) -> "Compiles":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, name: str, secs: float, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
        if name == BACKEND_COMPILE:
            self.count += 1
