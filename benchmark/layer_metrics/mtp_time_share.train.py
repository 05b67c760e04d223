"""Share of the traced window the device spent in the multi-token-prediction
module: self seconds of everything under the program's ``mtp`` scope
(``_mtp.py``: its input projection, its layer, its norm and its loss through
the shared head), forward, recomputed and backward, mean over the chips
used."""

from benchmark.layer_metrics import _mtp

NAME, UNIT, LAYER = "mtp_time_share.train", "%", "models"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    seconds = _mtp.seconds(trace, cell)
    return 100.0 * seconds / trace.window_s if seconds else None
