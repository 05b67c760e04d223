"""Shared by the scalar-decay delta-rule readers: which trace events are the
``tepdist_gdn_*`` kernels, and what each call found should cost at the
roofline.

The kernels are found by the names the program gives them
(``tepdist_tpu/ops/pallas/gdn_attention.py``), inside the instruction's own
name (autodiff and remat put their words around it): ``tepdist_gdn_fwd`` and
``tepdist_gdn_bwd``. A call's sizes are read from its HLO text, the
operands' shapes as ``operand_layout_constraints`` lists them: ``q``, ``k``
``[batch, T, Hk * K]``, ``v`` ``[batch, T, Hv * V]``, the float32 ``g`` and
``beta`` ``[batch, T, Hv]``, in that order whichever the kernel (``K = V``:
the kernels' own condition). The backward kernel is costed at the whole
backward. A program without the kernels has no such event and the readers
return nothing.
"""

from benchmark import trace_reduce
from benchmark.kernels import gdn_cost
from benchmark.layer_metrics._flash import _BYTES
from benchmark.layer_metrics._moe import _least
from benchmark.layer_metrics._sala import _operands

GDN_NAME = "tepdist_gdn_"


def is_gdn(text: str) -> bool:
    return GDN_NAME in trace_reduce.short_name(text)


def parse(text: str):
    """(kind, tokens, Hk, Hv, K, activation bytes) of one kernel event, or
    None."""
    ops = _operands(text)
    if len(ops) < 5 or any(len(dims) != 3 for _, dims in ops[:5]):
        return None
    (dtype, (batch, T, HkK)), (_, (_, _, HvV)), (_, (_, _, Hv)) = \
        ops[0], ops[2], ops[4]
    if HvV % Hv or HkK % (HvV // Hv):
        return None
    K = HvV // Hv
    kind = "forward" if "gdn_fwd" in trace_reduce.short_name(text) \
        else "backward"
    return kind, batch * T, HkK // K, Hv, K, _BYTES.get(dtype, 2)


def call_cost(parsed) -> dict:
    kind, tokens, Hk, Hv, K, act = parsed
    return getattr(gdn_cost, kind)(tokens, Hk, Hv, K, K, act)


def roofline_seconds(trace, peaks):
    """(least seconds for the calls found, which peak bounds most of it,
    calls by kind, operations, bytes); None when the trace has no such
    kernel it can read."""
    items = []
    for text, _, calls in trace.ops(is_gdn):
        parsed = parse(text)
        if parsed is None:
            return None
        items.append((parsed[0], calls, call_cost(parsed)))
    least = _least(items, peaks)
    if least is None:
        return None
    return least + tuple(sum(calls * cost[k] for _, calls, cost in items)
                         for k in ("ops", "bytes"))
