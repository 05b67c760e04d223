"""Compile counter: what JAX traced, lowered and compiled in this process.

Reads JAX's own ``jax.monitoring`` events, so it sees every program
whoever jitted it, and needs no hook in the code that compiles. Always on,
like the rest of ``metrics()``:

* histograms ``compile_trace_s``, ``compile_lower_s``, ``compile_backend_s``
  (count and sum): jaxpr tracing, lowering to an MLIR module, and the
  backend compile, where a read from the persistent cache counts as a
  (short) compile. A jitted function traced inside another's trace lies
  inside its caller's seconds, so only the outermost trace on a thread is
  observed: the three sums never overlap and may be added.
* counters ``compile_cache_requests``, ``compile_cache_hits``,
  ``compile_cache_misses``: the persistent cache's traffic (JAX writes an
  entry on a miss only for a compile above its minimum duration).

While the span recorder is on, each observation is also a ``lower:compile``
span (``phase=trace|lower|backend``, ``program=<jit name>``), which is what
answers "which step recompiled". The events report a duration when the
work is over, so these spans are written then and carry no profiler
annotation. Recorder on or off, a backend compile or cache read that has
ended also bumps the step log's ``compiles`` (telemetry/trace.py), which a
step reads as it starts and as it ends.
"""

from __future__ import annotations

import threading
from typing import Dict

from tepdist_tpu.telemetry import trace
from tepdist_tpu.telemetry.metrics import metrics

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_CACHE = {
    "/jax/compilation_cache/compile_requests_use_cache":
        "compile_cache_requests",
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
}

_depth = threading.local()      # open jaxpr traces on this thread
_installed = False
_install_lock = threading.Lock()


def _on_scalar(event: str, value, **_) -> None:
    # JAX records the start time of a timed section as a scalar under the
    # section's event name: the only sign that a trace has begun.
    if event == _TRACE_EVENT:
        _depth.n = getattr(_depth, "n", 0) + 1


def _on_duration(event: str, seconds: float, **kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    if phase == "trace":
        _depth.n = n = max(getattr(_depth, "n", 1) - 1, 0)
        if n:
            return
    metrics().histogram(f"compile_{phase}_s").observe(seconds)
    if phase == "backend":
        trace.STEP_LOG.compiles += 1
    t = trace.tracer()
    if t.enabled:
        t.record_finished("lower:compile", "lower", int(seconds * 1e9),
                          phase=phase, program=str(kw.get("fun_name", "")))


def _on_event(event: str, **_) -> None:
    name = _CACHE.get(event)
    if name is not None:
        metrics().counter(name).inc()


def install() -> None:
    """Register the listeners, once per process (``telemetry`` does so when
    it is imported)."""
    global _installed
    import jax

    with _install_lock:
        if _installed:
            return
        jax.monitoring.register_scalar_listener(_on_scalar)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True


def compile_stats() -> Dict[str, float]:
    """The counter as one flat dict: ``<phase>_n`` and ``<phase>_s`` for
    trace, lower and backend, ``seconds`` (their sum) and the cache's
    ``cache_requests``, ``cache_hits``, ``cache_misses``."""
    reg = metrics()
    out: Dict[str, float] = {}
    for phase in _PHASES.values():
        h = reg.histogram(f"compile_{phase}_s").to_dict()
        out[f"{phase}_n"] = h["count"]
        out[f"{phase}_s"] = h["sum"]
    out["seconds"] = sum(out[f"{p}_s"] for p in _PHASES.values())
    for name in _CACHE.values():
        out[name[len("compile_"):]] = reg.counter(name).value
    return out
