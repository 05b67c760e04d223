"""Share of the traced window in which the device sat idle under one of
the three calls into the model that the benchmark wraps (a prefill chunk,
the decode call, ``pick``). The scheduler's own Python between those calls
(the ``engine_step`` span in the breakdown) is not counted: with it every
gap is explained and this would be the device's idle share again."""

NAME, UNIT, LAYER, MOVES = "host_gap_share.serve", "%", "runtime", \
    "itl_p95_ms"
KINDS = ("serve",)
SOURCE = "device_trace"
CALLS = ("prefill_chunk", "decode_batch", "pick")


def read(trace, host, cell):
    return 100.0 * sum(s for name, s in trace.idle_gaps
                       if name in CALLS) / trace.window_s
