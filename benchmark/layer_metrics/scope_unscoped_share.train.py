"""Share of the traced window's device self seconds under none of the
program's ``part_*`` scopes (``_scopes.py``, which prints the longest such
operations: loop plumbing, copies the compiler makes, whatever the next scope
should name), mean over the chips used."""

from benchmark.layer_metrics import _scopes

NAME, UNIT, LAYER = "scope_unscoped_share.train", "%", "models"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    return _scopes.part_share(trace, cell, _scopes.UNSCOPED)
