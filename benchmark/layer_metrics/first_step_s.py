"""Seconds of the first ``step()``: the call path's compile, or its read
from the persistent cache, and the first transfer of the state."""

NAME, UNIT, LAYER, MOVES = "first_step_s", "s", "lowering", "setup_s"
KINDS = ("train",)
SOURCE = "host_clock"


def read(trace, host, cell):
    seconds = host["spans"].seconds("first_step")
    return seconds if seconds > 0 else None
