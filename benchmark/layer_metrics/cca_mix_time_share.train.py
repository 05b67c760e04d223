"""Share of the traced window the device spent in the compressed attention's
mixing kernels (``tepdist_cca_mix_fwd``, twice a layer and micro batch where
a walked block is rematerialised, and ``tepdist_cca_mix_bwd``), mean over the
chips used."""

from benchmark.layer_metrics import _cca

NAME, UNIT, LAYER = "cca_mix_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    seconds = trace.op_seconds(_cca.is_cca_mix)
    return 100.0 * seconds / trace.window_s if seconds > 0 else None
