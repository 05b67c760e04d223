"""Unified telemetry: span tracing, metrics registry, Perfetto export.

Usage::

    from tepdist_tpu.telemetry import span, metrics

    with span("compute:fwd", cat="compute", stage=0) as sp:
        ...work...
        sp.set(bytes=n)
    metrics().counter("steps").inc()

Spans are gated by ``TEPDIST_TRACE`` (or ``DEBUG``) and cost one branch
when disabled; metrics are always on, the compile counter
(``compile_stats()``, telemetry/compiles.py) among them, and so is the step
log: ``step_log()`` holds one record for every finished
``TrainingPlan.step()`` (wall, host phases, the wait before it, compiles and
collector pauses inside it), spans on or off.
``start_device_trace(log_dir)`` / ``stop_device_trace()`` run
``jax.profiler`` with every span also on its clock as ``tepdist:<name>``.
``GetTelemetry`` (rpc/protocol.py)
pulls both from every worker; ``session.dump_trace()`` merges them into
one Perfetto-loadable timeline.
"""

from tepdist_tpu.telemetry.metrics import (  # noqa: F401
    MetricsRegistry,
    metrics,
)
from tepdist_tpu.telemetry.trace import (  # noqa: F401
    _NULL_SPAN,
    Span,
    Tracer,
    configure,
    enabled,
    span,
    start_device_trace,
    step_log,
    stop_device_trace,
    tracer,
)
from tepdist_tpu.telemetry.export import (  # noqa: F401
    CLIENT_PID,
    build_trace,
    dump_merged_trace,
    to_chrome_events,
    to_prometheus,
    write_trace,
)
from tepdist_tpu.telemetry import compiles, trace
from tepdist_tpu.telemetry.compiles import compile_stats  # noqa: F401
from tepdist_tpu.telemetry import calibrate  # noqa: F401
from tepdist_tpu.telemetry import fidelity  # noqa: F401
from tepdist_tpu.telemetry import flight  # noqa: F401
from tepdist_tpu.telemetry import ledger  # noqa: F401
from tepdist_tpu.telemetry import observatory  # noqa: F401
from tepdist_tpu.telemetry.watchtower import (  # noqa: F401
    HealthAlert,
    TrainingSentinel,
    WatchHalt,
    Watchtower,
    active_alerts,
)

compiles.install()
trace.install_gc_hook()
