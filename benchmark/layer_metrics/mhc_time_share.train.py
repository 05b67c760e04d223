"""Share of the traced window the device spent on the hyper-connection
maps: self seconds under the program's ``mhc_maps``, ``mhc_read`` and
``mhc_write`` sub-scopes (``_mhc.py``), forward, recomputed and backward,
mean over the chips used."""

from benchmark.layer_metrics import _mhc

NAME, UNIT, LAYER = "mhc_time_share.train", "%", "models"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    seconds = _mhc.seconds(trace, cell)
    return 100.0 * seconds / trace.window_s if seconds else None
