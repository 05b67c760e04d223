"""Seconds in ``plan_training``: exploration, the chosen plan's lowering
and, for an explored plan, the winner's post-check compile or cache read."""

NAME, UNIT, LAYER, MOVES = "plan_s", "s", "planner", "setup_s"
KINDS = ("train",)
SOURCE = "host_clock"


def read(trace, host, cell):
    seconds = host["spans"].seconds("plan")
    return seconds if seconds > 0 else None
