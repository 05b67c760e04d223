"""Share of the traced window's device self seconds spent making a forward
pass again: the layer walks' ``walk_recompute`` phase (a block's forward from
its saved input before its backward, ``models/layers.py:scan_blocks``) and
what a ``jax.checkpoint`` inside a block makes again in the backward pass
(``rematted_computation``: ``over_sequence``'s chunks), every part's. Another
cut of the seconds the ``scope_*_share.train`` parts split (``_scopes.py``),
mean over the chips used."""

from benchmark.layer_metrics import _scopes

NAME, UNIT, LAYER = "scope_recompute_share.train", "%", "models"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    return _scopes.phase_share(trace, cell, _scopes.RECOMPUTED)
