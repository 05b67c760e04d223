"""The benchmark's own host spans and counters.

Spans are recorded from the benchmark's files around each call into a layer
of the program. Each is also a ``jax.profiler.TraceAnnotation`` named
``bench:<name>``, so that in a traced run it lands on the profiler's clock
and an idle gap of the device can be attributed to it.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench:"


class HostLog:
    def __init__(self):
        self.t0 = time.perf_counter()
        self.spans: List[Tuple[str, float, float]] = []
        self.counters: Dict[str, float] = {}
        self.annotate = False     # drivers switch it on inside a trace

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
            ann.__enter__()
        t = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, t, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def seconds(self, name: str) -> float:
        return sum(e - s for n, s, e in self.spans if n == name)


class CompileWatch:
    """Counts XLA compilations (cache reads included) by listening to JAX's
    own duration events; ``since`` tells whether any fell in a window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.n += 1

    def since(self, mark: int) -> int:
        return self.n - mark
