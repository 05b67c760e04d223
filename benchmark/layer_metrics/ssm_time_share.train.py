"""Share of the traced window the device spent in the selective-scan kernels
(``tepdist_ssm_fwd`` in the forward walk and again in the backward walk's
recomputation, ``tepdist_ssm_bwd``), mean over the chips used."""

from benchmark.layer_metrics import _ssm

NAME, UNIT, LAYER = "ssm_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    seconds = trace.op_seconds(_ssm.is_ssm)
    return 100.0 * seconds / trace.window_s if seconds > 0 else None
