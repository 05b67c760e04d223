"""Share of the traced window the device spent in the flash attention
kernels (Mosaic custom calls), mean over the chips used."""

from benchmark.layer_metrics import _flash

NAME, UNIT, LAYER = "flash_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    seconds = _flash.seconds(trace)
    return 100.0 * seconds / trace.window_s if seconds > 0 else None
