"""Share of the measured window in which the program waited on its caller,
per cent: the ``between`` of the window's records in the program's step log
(a step's entry minus the previous step's return; from the window's second
step on) over the walls and those waits together. Here the caller is the
driver making the next batch."""

from benchmark.layer_metrics import _step_log

NAME, UNIT, LAYER = "window_between_steps_share.train", "%", "runtime"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "program_span"


def read(trace, host, cell):
    found = _step_log.window(trace, host, cell)
    return None if found is None else found["between_share"]
