"""Share of the traced window's device self seconds under the program's
``part_optimizer`` scope: the optimizer's update and the gradient
accumulation outside the layer walks (``_scopes.py``; the six parts and
``unscoped`` sum to 100), mean over the chips used."""

from benchmark.layer_metrics import _scopes

NAME, UNIT, LAYER = "scope_optimizer_share.train", "%", "models"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    return _scopes.part_share(trace, cell, "optimizer")
