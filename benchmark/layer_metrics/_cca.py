"""Shared by the compressed attention's mixing readers: which trace events
are the ``tepdist_cca_mix_*`` kernels, and what each call found should cost
at the roofline.

``tepdist_tpu/ops/pallas/cca_mix.py`` names its calls ``tepdist_cca_mix_fwd``
and ``tepdist_cca_mix_bwd``; autodiff and remat put their words around the
name inside the instruction's. A call's sizes are read from its own HLO text,
the operands' shapes as ``operand_layout_constraints`` lists them: the query
latent ``[B, T, H D]`` first, the key latent ``[B, T, Hkv D]`` second, and
the heads' matrices ``[2, N, D, D]`` among the rest, whichever the kernel. A
program without the kernels has no such event and the readers return nothing.
"""

import re

from benchmark import trace_reduce
from benchmark.kernels import cca_mix_cost
from benchmark.layer_metrics._flash import _BYTES
from benchmark.layer_metrics._moe import _least
from benchmark.layer_metrics._sala import _operands

NAME = re.compile(r"tepdist_cca_mix_(fwd|bwd)")
KINDS = {"fwd": "forward", "bwd": "backward"}


def is_cca_mix(text: str) -> bool:
    return NAME.search(trace_reduce.short_name(text)) is not None


def call_cost(text: str):
    """(kind, operations and bytes) of one kernel event, or None where its
    operands are not the kernels'."""
    which = NAME.search(trace_reduce.short_name(text)).group(1)
    ops = _operands(text)
    matrices = [dims for _, dims in ops if len(dims) == 4 and dims[0] == 2
                and dims[2] == dims[3]]
    if len(ops) < 2 or len(ops[0][1]) != 3 or len(ops[1][1]) != 3 \
            or not matrices:
        return None
    dtype, (B, T, q_width) = ops[0]
    _, N, D, _ = matrices[0]
    if q_width + ops[1][1][2] != N * D:
        return None
    return KINDS[which], getattr(cca_mix_cost, KINDS[which])(
        B * T, N, D, _BYTES.get(dtype, 2))


def roofline_seconds(trace, peaks):
    """(least seconds for the calls found, which peak bounds most of it,
    calls by kind, operations, bytes); None when the trace has no such
    kernel it can read."""
    items = []
    for text, _, calls in trace.ops(is_cca_mix):
        found = call_cost(text)
        if found is None:
            return None
        items.append((found[0], calls, found[1]))
    least = _least(items, peaks)
    if least is None:
        return None
    return least + tuple(sum(calls * cost[k] for _, calls, cost in items)
                         for k in ("ops", "bytes"))
