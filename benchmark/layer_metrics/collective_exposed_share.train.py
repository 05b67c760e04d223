"""Share of the traced window in which a collective ran on a device and no
other operation did, mean over the chips. One chip has no collectives and
nothing to read."""

NAME, UNIT, LAYER = "collective_exposed_share.train", "%", "transport"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    if len(trace.devices) < 2:
        return None
    exposed = sum(d.collective_exposed_s for d in trace.devices) \
        / len(trace.devices)
    return 100.0 * exposed / trace.window_s
