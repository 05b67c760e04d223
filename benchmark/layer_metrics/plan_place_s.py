"""Seconds to make the optimizer's state and put parameters and state on
the devices: the program's ``plan:place`` spans (``optimizer.init`` and the
``device_put`` of the state), whole."""

from benchmark.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = "plan_place_s", "s", "runtime", "setup_s"
KINDS = ("train",)
SOURCE = "program_span"


def read(trace, host, cell):
    found = _program_spans.self_seconds(_program_spans.recorded(host))
    return found["plan:place"][1] if "plan:place" in found else None
