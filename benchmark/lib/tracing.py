"""A ``jax.profiler`` trace of a short window, reduced with
``trace_reduce.py``. Only a traced run ever imports this, and a
``--trace 2`` run only once its measured window has closed. The trace is
started and stopped through the program's own control
(``tepdist_tpu.telemetry.start_device_trace``), which also switches the
program's span recorder on and lays its spans on the profiler's clock."""

from __future__ import annotations

import contextlib
import os
import shutil


class WindowTrace:
    """Profile from ``start()`` to ``stop()``; host spans in between land on
    the profiler's clock, the whole of it under the ``window`` span."""

    def __init__(self, root: str, cell_name: str, host):
        self.path = os.path.join(root, ".bench_trace", cell_name)
        self.host = host
        self._span = None

    def start(self) -> None:
        from tepdist_tpu import telemetry
        discard(self.path)
        os.makedirs(self.path, exist_ok=True)
        # The profiler's start, the process's first included, is over before
        # ``bench:window`` opens and before a driver takes its clock, so what
        # it costs falls into no number (PERF.md section 6, PR 24).
        telemetry.start_device_trace(self.path)
        self.host.annotate = True
        self._span = self.host.span("window")
        self._span.__enter__()

    def stop(self) -> None:
        from tepdist_tpu import telemetry
        if self._span is None:
            return
        self._span.__exit__(None, None, None)
        self._span = None
        self.host.annotate = False
        telemetry.stop_device_trace()


@contextlib.contextmanager
def traced_window(root: str, cell_name: str, host):
    trace = WindowTrace(root, cell_name, host)
    trace.start()
    try:
        yield trace.path
    finally:
        trace.stop()


def discard(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)


def reduce_trace(path: str):
    from benchmark import trace_reduce
    return trace_reduce.reduce_file(trace_reduce.find_xplane(path))
