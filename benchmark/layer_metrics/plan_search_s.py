"""Seconds of the strategy search: self time of the program's ``plan:search``
spans (``plan_axes`` to the end of the strategy post-passes)."""

from benchmark.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = "plan_search_s", "s", "planner", "setup_s"
KINDS = ("train",)
SOURCE = "program_span"


def read(trace, host, cell):
    found = _program_spans.self_seconds(_program_spans.recorded(host))
    return found["plan:search"][0] if "plan:search" in found else None
