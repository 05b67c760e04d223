"""Share of the traced window the device spent in the scalar-decay
delta-rule kernels (``tepdist_gdn_fwd``, once a layer and micro batch where
the walk keeps its forward, and ``tepdist_gdn_bwd``; any ``tepdist_gdn_``
event), mean over the chips used."""

from benchmark.layer_metrics import _gdn

NAME, UNIT, LAYER = "gdn_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    seconds = trace.op_seconds(_gdn.is_gdn)
    return 100.0 * seconds / trace.window_s if seconds > 0 else None
