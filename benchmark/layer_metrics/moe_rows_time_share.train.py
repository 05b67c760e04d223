"""Share of the traced window the device spent moving an expert part's rows
and choosing them: self seconds under the program's ``moe_router``,
``moe_dispatch`` and ``moe_combine`` scopes (``_moe_scopes.py``: the router's
matmul, softmax and choice, the layout's sorts, the rows gathered into the
layout and summed out of it, forward, recomputed and backward): what ten
choices a token over narrow experts make large beside the grouped matmuls
(``gmm_time_share.train``), mean over the chips used."""

from benchmark.layer_metrics import _moe_scopes

NAME, UNIT, LAYER = "moe_rows_time_share.train", "%", "models"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    return _moe_scopes.share(
        trace, cell, ("moe_router", "moe_dispatch", "moe_combine"))
