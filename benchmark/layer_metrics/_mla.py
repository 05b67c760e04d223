"""Shared by the latent-attention readers: which trace events are the
``tepdist_mla_*`` kernels, and what each call found should cost at the
roofline.

``tepdist_tpu/ops/pallas/mla_attention.py`` names a call
``tepdist_mla_<fwd|dq|dkv>__c<causal>__s<scale>__h<heads>``; autodiff and
remat put their words around it inside the instruction's name. A call's sizes
are read from its own HLO text, the operands' shapes as
``operand_layout_constraints`` lists them: ``q_nope [B*H, T, Dn]``, ``q_rope
[B*H, T, Dr]``, ``k_nope``, **``k_rope [B, T, Dr]``** (one a batch row) and
``v [B*H, T, Dv]``, in that order whichever the kernel. A program without the
kernels has no such event and the readers return nothing.
"""

import re

from benchmark import trace_reduce
from benchmark.kernels import mla_cost
from benchmark.layer_metrics._flash import _BYTES
from benchmark.layer_metrics._moe import _least
from benchmark.layer_metrics._sala import _operands

NAME = re.compile(r"tepdist_mla_(fwd|dq|dkv)__c([01])__s[^_]+__h(\d+)")
KINDS = {"fwd": "forward", "dq": "backward_dq", "dkv": "backward_dkv"}


def is_mla(text: str) -> bool:
    return NAME.search(trace_reduce.short_name(text)) is not None


def call_cost(text: str):
    """(kind, operations and bytes) of one kernel event, or None where its
    operands are not the kernels' five."""
    which, causal, heads = NAME.search(trace_reduce.short_name(text)).groups()
    ops = _operands(text)
    if len(ops) < 5 or any(len(dims) != 3 for _, dims in ops[:5]):
        return None
    (dtype, (bh, T, Dn)), (_, (_, _, Dr)), _, (_, (B, _, _)), \
        (_, (_, _, Dv)) = ops[:5]
    if bh != B * int(heads):
        return None
    return KINDS[which], getattr(mla_cost, KINDS[which])(
        (B, int(heads), T), (Dn, Dr, Dv), _BYTES.get(dtype, 2), causal == "1")


def roofline_seconds(trace, peaks):
    """(least seconds for the calls found, which peak bounds most of it,
    calls by kind, operations, bytes); None when the trace has no such
    kernel it can read."""
    items = []
    for text, _, calls in trace.ops(is_mla):
        found = call_cost(text)
        if found is None:
            return None
        items.append((found[0], calls, found[1]))
    least = _least(items, peaks)
    if least is None:
        return None
    return least + tuple(sum(calls * cost[k] for _, calls, cost in items)
                         for k in ("ops", "bytes"))
