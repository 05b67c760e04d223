"""Milliseconds of a step of the measured window: median ``wall`` (entry of
``plan.step()`` to its return) of the window's records in the program's step
log. The window's own counterpart of ``step_device_ms.train`` +
``step_host_ms.train``, which are one traced step after it."""

from benchmark.layer_metrics import _step_log

NAME, UNIT, LAYER = "window_step_ms.train", "ms", "runtime"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "program_span"


def read(trace, host, cell):
    found = _step_log.window(trace, host, cell)
    return None if found is None else found["step_ms"]
