"""Share of the traced window the device spent in the expert layer's grouped
matmuls alone (gate, up and down projections; forward, remat, input and
weight gradients), mean over the chips used."""

from benchmark.layer_metrics import _moe

NAME, UNIT, LAYER = "gmm_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    seconds = trace.op_seconds(_moe.is_gmm)
    return 100.0 * seconds / trace.window_s if seconds > 0 else None
