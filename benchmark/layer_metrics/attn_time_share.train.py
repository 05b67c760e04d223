"""Share of the traced window the device spent in the flash attention
kernels, found by the names the program gives them (``tepdist_flash_*``):
for a cell that runs other Mosaic custom calls beside them, which
``flash_time_share.train`` would count in."""

from benchmark.layer_metrics import _moe

NAME, UNIT, LAYER = "attn_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    seconds = trace.op_seconds(_moe.is_attention)
    return 100.0 * seconds / trace.window_s if seconds > 0 else None
