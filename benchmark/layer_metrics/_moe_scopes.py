"""Shared by the readers of an expert part's own sub-scopes: the traced
window's device self seconds of every operation under the named
``jax.named_scope``s of ``tepdist_tpu/ops/grouped_matmul.py`` and of a
model's ``moe`` (``moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``, ``moe_shared``), forward, recomputed and backward.

``_scopes.place``'s sub-scope is a kernel's name where an operation's path
ends in one (the routers' ``tepdist_router_choice``, the rows' copies
``tepdist_rows_*``), so a scope that holds kernels is looked for as a whole
word anywhere in the operation's ``tf_op``, as ``_mtp.py`` looks for its
scope. A program without the scopes has no such operation and the readers
return nothing.
"""

from benchmark import trace_reduce
from benchmark.layer_metrics import _scopes


def share(trace, cell, words):
    """Per cent of the traced window the device spent under the scopes
    ``words``, mean over the devices; None where the trace names no such
    operation (or the run was not traced)."""
    path = cell.facts.get("trace_path")
    if not path:
        return None
    found = _scopes._whole_word(words)
    if "operation_scopes" not in cell.facts:      # read once a run
        cell.facts["operation_scopes"] = _scopes.operation_scopes(
            trace_reduce.find_xplane(path))
    scopes = cell.facts["operation_scopes"]
    total = sum(seconds for d in trace.devices
                for op, seconds in d.op_self_s.items()
                if found.search(scopes.get(op, "")))
    seconds = total / len(trace.devices)
    return 100.0 * seconds / trace.window_s if seconds else None
