"""Share of the traced window the device spent in the shared MLP beside the
routed experts: self seconds under the program's ``moe_shared`` scope
(``_moe_scopes.py``), forward, recomputed and backward, mean over the chips
used."""

from benchmark.layer_metrics import _moe_scopes

NAME, UNIT, LAYER = "moe_shared_time_share.train", "%", "models"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    return _moe_scopes.share(trace, cell, ("moe_shared",))
