"""Share of the traced window the device spent in the state-space-dual
kernels (``tepdist_ssd_fwd``, in a layer's forward and again in its
recomputation, and ``tepdist_ssd_bwd``; any ``tepdist_ssd_`` event), mean
over the chips used."""

from benchmark.layer_metrics import _ssd

NAME, UNIT, LAYER = "ssd_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    seconds = trace.op_seconds(_ssd.is_ssd)
    return 100.0 * seconds / trace.window_s if seconds > 0 else None
