"""Share of the traced window the device spent in the latent-attention
kernels (``tepdist_mla_fwd``, once a layer and micro batch where the walk
keeps its forward, ``tepdist_mla_dq`` and ``tepdist_mla_dkv``), mean over
the chips used."""

from benchmark.layer_metrics import _mla

NAME, UNIT, LAYER = "mla_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    seconds = trace.op_seconds(_mla.is_mla)
    return 100.0 * seconds / trace.window_s if seconds > 0 else None
