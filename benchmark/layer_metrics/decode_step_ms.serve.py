"""Median device time of one run of the paged decode program. The program
jits it unnamed (``jit__unknown`` in the trace), so it is found as the
longest program run inside each of the benchmark's ``decode_batch`` spans,
which wait for the step's logits."""

import statistics

NAME, UNIT, LAYER, MOVES = "decode_step_ms.serve", "ms", "serving", \
    "itl_p95_ms"
KINDS = ("serve",)
SOURCE = "device_trace"


def read(trace, host, cell):
    runs = trace.module_runs_within("decode_batch")
    return 1e3 * statistics.median(runs) if runs else None
