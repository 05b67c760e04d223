"""Shared by the delta-rule readers: which trace events are the
``tepdist_kda_*`` kernels, and what each call found should cost at the
roofline.

The kernels are found by the names the program gives them
(``tepdist_tpu/ops/pallas/kda_attention.py``), inside the instruction's own
name (autodiff and remat put their words around it): ``tepdist_kda_fwd``,
``tepdist_kda_bwd_states`` and ``tepdist_kda_bwd``. A call's sizes are read
from its HLO text, the operands' shapes as ``operand_layout_constraints``
lists them: ``q``, ``k``, ``v`` ``[batch, T, H * K]``, the float32 ``g`` of
the same shape and ``beta`` ``[batch, T, H]``, in that order whichever the
kernel. The backward kernel is costed at the whole backward; a sweep that
makes the states before every chunk again (``tepdist_kda_bwd_states``: only
where a backward is asked for without the states a differentiated forward
writes; no step of the cell has it) is work of the implementation and costed
at nothing. A program without the kernels has no such event and the readers
return nothing.
"""

from benchmark import trace_reduce
from benchmark.kernels import kda_cost
from benchmark.layer_metrics._flash import _BYTES
from benchmark.layer_metrics._moe import _least
from benchmark.layer_metrics._sala import _operands

KDA_NAME = "tepdist_kda_"
_NOTHING = {"ops": 0.0, "bytes": 0.0}


def is_kda(text: str) -> bool:
    return KDA_NAME in trace_reduce.short_name(text)


def parse(text: str):
    """(kind, tokens, H, K, activation bytes) of one kernel event, or
    None."""
    ops = _operands(text)
    if len(ops) < 5 or any(len(dims) != 3 for _, dims in ops[:5]):
        return None
    (dtype, (batch, T, HK)), (_, (_, _, H)) = ops[0], ops[4]
    if HK % H:
        return None
    name = trace_reduce.short_name(text)
    kind = "forward" if "kda_fwd" in name else \
        "states_again" if "kda_bwd_states" in name else "backward"
    return kind, batch * T, H, HK // H, _BYTES.get(dtype, 2)


def call_cost(parsed) -> dict:
    kind, tokens, H, K, act = parsed
    if kind == "states_again":
        return _NOTHING
    return getattr(kda_cost, kind)(tokens, H, K, K, act)


def roofline_seconds(trace, peaks):
    """(least seconds for the calls found, which peak bounds most of it,
    calls by kind, operations, bytes); None when the trace has no such
    kernel it can read."""
    items = []
    for text, _, calls in trace.ops(is_kda):
        parsed = parse(text)
        if parsed is None:
            return None
        items.append((parsed[0], calls, call_cost(parsed)))
    least = _least(items, peaks)
    if least is None:
        return None
    return least + tuple(sum(calls * cost[k] for _, calls, cost in items)
                         for k in ("ops", "bytes"))
