"""How far the measured window's longest step lies over its median step, per
cent: 100 x (largest ``wall`` / median ``wall`` - 1) over the window's
records in the program's step log. One step that stalled shows here; a window
slow in every step does not (``window_step_ms.train`` has that)."""

from benchmark.layer_metrics import _step_log

NAME, UNIT, LAYER = "window_slowest_step_excess.train", "%", "runtime"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "program_span"


def read(trace, host, cell):
    found = _step_log.window(trace, host, cell)
    return None if found is None else found["slowest_excess"]
