"""Seconds ``plan_training`` spent tracing the step to a graph: self time of
the program's ``plan:trace`` spans (``trace_graph`` in ``auto_parallel`` and,
when it runs, the sync-free analysis's trace). Exploration traces under a
span of its own, ``explore:trace``, printed with the rest of the split."""

from benchmark.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = "plan_trace_s", "s", "planner", "setup_s"
KINDS = ("train",)
SOURCE = "program_span"


def read(trace, host, cell):
    found = _program_spans.self_seconds(_program_spans.recorded(host))
    # Printed once, by the first of the plan readers: every planner span's
    # self seconds, so the split of ``plan_s`` is whole.
    print("program spans of set-up (self s, whole s, count): " + str(
        {k: (round(a, 4), round(b, 4), n) for k, (a, b, n) in found.items()
         if k.startswith(("plan", "explore"))}), flush=True)
    return found["plan:trace"][0] if "plan:trace" in found else None
