"""Shared by the selective-scan readers: which trace events are the scan
kernels, and what each call found should cost at the roofline.

The kernels are found by the names the program gives them,
``tepdist_ssm_fwd`` / ``tepdist_ssm_bwd`` (``tepdist_tpu/ops/pallas/
selective_scan.py``), inside the instruction's own name (autodiff and remat
put their words around it). A call's sizes are read from its HLO text, from
the operands' shapes as ``operand_layout_constraints`` lists them (a trace
event's text and a compiled module's both carry it): the first operand is
``c`` ``[batch, T, Di]`` at the activations' width, the second ``delta`` at
its own, and ``A`` is the one ``[N, Di]`` float32 operand. A program without the kernels has no such event and the readers
return nothing.
"""

import re

from benchmark import trace_reduce
from benchmark.kernels import ssm_cost
from benchmark.layer_metrics._flash import _BYTES, _SHAPE

SSM_NAME = "tepdist_ssm_"
# Up to the next attribute: the shapes' own layouts hold braces and commas.
_OPERANDS = re.compile(r"operand_layout_constraints=\{(.*?)\}(?:, \w+=|$)")


def is_ssm(text: str) -> bool:
    return SSM_NAME in trace_reduce.short_name(text)


def parse(text: str):
    """(kind, batch, T, Di, N, activation bytes, delta bytes) of one kernel
    event, or None."""
    listed = _OPERANDS.search(text)
    operands = _SHAPE.findall(listed.group(1)) if listed else []
    if len(operands) < 2 or operands[0][1].count(",") != 2:
        return None
    batch, T, Di = (int(x) for x in operands[0][1].split(","))
    states = [int(dims.split(",")[0]) for dtype, dims in operands
              if dtype == "f32" and dims.count(",") == 1
              and dims.endswith(f",{Di}") and not dims.startswith("1,")]
    if not states:
        return None
    kind = "backward" if "ssm_bwd" in trace_reduce.short_name(text) \
        else "forward"
    return (kind, batch, T, Di, states[0], _BYTES.get(operands[0][0], 2),
            _BYTES.get(operands[1][0], 4))


def roofline_seconds(trace, peaks):
    """(least seconds for the calls found, which peak bounds most of it,
    calls by kind); None when the trace has no scan kernel it can read."""
    least, by_bound, kinds = 0.0, {}, {}
    for text, _, calls in trace.ops(is_ssm):
        parsed = parse(text)
        if parsed is None:
            return None
        kind, batch, T, Di, N, act, delta = parsed
        r = ssm_cost.roofline_seconds(
            getattr(ssm_cost, kind)(batch * T, Di, N, act, delta), peaks)
        least += calls * r["seconds"]
        by_bound[r["bound"]] = by_bound.get(r["bound"], 0.0) \
            + calls * r["seconds"]
        kinds[kind] = kinds.get(kind, 0) + calls
    if not kinds:
        return None
    return least, max(by_bound, key=by_bound.get), kinds
