"""Shared by the hyper-connection readers: the traced window's device self
seconds under the program's ``mhc_maps``, ``mhc_read`` and ``mhc_write``
sub-scopes (``tepdist_tpu/models/layers.py:hyper_maps`` / ``hyper_read`` /
``hyper_write``: a sub-layer's three maps with their Sinkhorn loop, its
input read out of the residual stream's lanes, the lanes mixed and written
back), whichever part (``mixer``, ``mlp``, ``moe``) they serve.

Read from ``_scopes.traced``'s table of sub-scopes, so an operation counts
where its innermost scope is one of the three: a fusion is charged to its
root's scope, and a map's pass that the compiler fuses into a neighbour's
operation goes with that neighbour. A program without the scopes has no such
row and the readers return nothing.
"""

from benchmark.layer_metrics import _scopes

SCOPES = ("mhc_maps", "mhc_read", "mhc_write")


def seconds(trace, cell):
    """Self seconds under the three sub-scopes, mean over the devices; None
    where the trace names none of them."""
    found = _scopes.traced(trace, cell)
    if not found:
        return None
    return sum(s for (_, sub), s in found["by_sub"].items()
               if sub in SCOPES) or None
