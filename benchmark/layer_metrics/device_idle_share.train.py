"""Share of the traced window in which no operation ran on the device,
mean over the chips used."""

NAME, UNIT, LAYER = "device_idle_share.train", "%", "device"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    return 100.0 * trace.idle_share
