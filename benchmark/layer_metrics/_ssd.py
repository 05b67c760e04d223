"""Shared by the state-space-dual readers: which trace events are the
``tepdist_ssd_*`` kernels, and what each call found should cost at the
roofline.

The kernels are found by the names the program gives them
(``tepdist_tpu/ops/pallas/ssd_attention.py``), inside the instruction's own
name (autodiff and remat put their words around it): ``tepdist_ssd_fwd__g<
groups>`` and ``tepdist_ssd_bwd__g<groups>``. A call's sizes are read from
its name (the groups) and its HLO text, the operands' shapes as
``operand_layout_constraints`` lists them: ``u`` ``[batch, T, H * P]``,
``B``, ``C`` ``[batch, T, G * N]`` and the float32 ``Delta`` ``[batch, T,
H]``, in that order whichever the kernel. The backward kernel is costed at
the whole backward. A program without the kernels has no such event and the
readers return nothing.
"""

import re

from benchmark import trace_reduce
from benchmark.kernels import ssd_cost
from benchmark.layer_metrics._flash import _BYTES
from benchmark.layer_metrics._moe import _least
from benchmark.layer_metrics._sala import _operands

SSD_NAME = "tepdist_ssd_"
_CALL = re.compile(r"tepdist_ssd_(fwd|bwd)\w*?__g(\d+)")


def is_ssd(text: str) -> bool:
    return SSD_NAME in trace_reduce.short_name(text)


def parse(text: str):
    """(kind, tokens, H, P, G, N, activation bytes) of one kernel event, or
    None."""
    named = _CALL.search(trace_reduce.short_name(text))
    ops = _operands(text)
    if named is None or len(ops) < 4 \
            or any(len(dims) != 3 for _, dims in ops[:4]):
        return None
    (dtype, (batch, T, HP)), (_, (_, _, GN)), (_, (_, _, H)) = \
        ops[0], ops[1], ops[3]
    G = int(named[2])
    if HP % H or GN % G or H % G:
        return None
    kind = "forward" if named[1] == "fwd" else "backward"
    return kind, batch * T, H, HP // H, G, GN // G, _BYTES.get(dtype, 2)


def call_cost(parsed) -> dict:
    kind, *sizes = parsed
    return getattr(ssd_cost, kind)(*sizes)


def roofline_seconds(trace, peaks):
    """(least seconds for the calls found, which peak bounds most of it,
    calls by kind, operations, bytes); None when the trace has no such
    kernel it can read."""
    items = []
    for text, _, calls in trace.ops(is_ssd):
        parsed = parse(text)
        if parsed is None:
            return None
        items.append((parsed[0], calls, call_cost(parsed)))
    least = _least(items, peaks)
    if least is None:
        return None
    return least + tuple(sum(calls * cost[k] for _, calls, cost in items)
                         for k in ("ops", "bytes"))
