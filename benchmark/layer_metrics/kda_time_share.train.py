"""Share of the traced window the device spent in the delta-rule kernels
(``tepdist_kda_fwd``, twice a layer and micro batch where the walk makes a
block again, and ``tepdist_kda_bwd``; any ``tepdist_kda_`` event), mean over
the chips used."""

from benchmark.layer_metrics import _kda

NAME, UNIT, LAYER = "kda_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    seconds = trace.op_seconds(_kda.is_kda)
    return 100.0 * seconds / trace.window_s if seconds > 0 else None
