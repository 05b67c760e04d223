"""Shared by the multi-token-prediction readers: the traced window's device
self seconds of every operation under the program's ``mtp`` scope
(``tepdist_tpu/models/xing.py``: the prediction module's input projection,
its whole layer under its own parts, its final norm and its loss through the
shared head, forward, recomputed and backward).

The scope stands outside the parts, so ``_scopes.place``'s sub-scope (the
innermost word after the part) never names it: the word is looked for
anywhere in an operation's ``tf_op``, as a part's is. A program without the
scope has no such operation and the readers return nothing.
"""

from benchmark import trace_reduce
from benchmark.layer_metrics import _scopes

_MTP = _scopes._whole_word(["mtp"])


def seconds(trace, cell):
    """Self seconds of the operations under ``mtp``, mean over the devices;
    None where the trace names no such operation (or the run was not
    traced)."""
    path = cell.facts.get("trace_path")
    if not path:
        return None
    scopes = _scopes.operation_scopes(trace_reduce.find_xplane(path))
    total = sum(seconds for d in trace.devices
                for op, seconds in d.op_self_s.items()
                if _MTP.search(scopes.get(op, "")))
    return total / len(trace.devices) or None
