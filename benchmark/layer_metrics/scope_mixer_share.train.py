"""Share of the traced window's device self seconds under the program's
``part_mixer`` scope: the blocks' sequence mixing whole: its norm,
projections, rotary, the attention, scan, linear-attention or latent
kernels, the output projection, a gate (``_scopes.py``; the six parts and
``unscoped`` sum to 100), mean over the chips used."""

from benchmark.layer_metrics import _scopes

NAME, UNIT, LAYER = "scope_mixer_share.train", "%", "models"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    return _scopes.part_share(trace, cell, "mixer")
