"""Share of the traced window in which no operation ran on the device."""

NAME, UNIT, LAYER = "device_idle_share.serve", "%", "device"
MOVES = "serve_tokens_per_s"
KINDS = ("serve",)
SOURCE = "device_trace"


def read(trace, host, cell):
    return 100.0 * trace.idle_share
