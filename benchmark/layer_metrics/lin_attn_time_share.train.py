"""Share of the traced window the device spent in the linear-attention
kernels (``tepdist_lightning_fwd`` in the forward walk and again in the
backward walk's recomputation, ``tepdist_lightning_bwd_dq`` and
``_bwd_dkv``), mean over the chips used."""

from benchmark.layer_metrics import _sala

NAME, UNIT, LAYER = "lin_attn_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    seconds = trace.op_seconds(_sala.is_lightning)
    return 100.0 * seconds / trace.window_s if seconds > 0 else None
