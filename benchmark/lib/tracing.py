"""A ``jax.profiler`` trace of a short window, reduced with
``trace_reduce.py``. Only a ``--trace 1`` run ever imports this."""

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
        import jax
        shutil.rmtree(self.path, ignore_errors=True)
        os.makedirs(self.path, exist_ok=True)
        jax.profiler.start_trace(self.path)
        self.host.annotate = True
        self._span = self.host.span("window")
        self._span.__enter__()

    def stop(self) -> None:
        import jax
        if self._span is None:
            return
        self._span.__exit__(None, None, None)
        self._span = None
        self.host.annotate = False
        jax.profiler.stop_trace()


@contextlib.contextmanager
def traced_window(root: str, cell_name: str, host):
    trace = WindowTrace(root, cell_name, host)
    trace.start()
    try:
        yield trace.path
    finally:
        trace.stop()


def reduce_trace(path: str):
    from benchmark import trace_reduce
    return trace_reduce.reduce_file(trace_reduce.find_xplane(path))
