"""Shared by the flash readers: which trace events are the attention
kernels, and what each call found should cost at the roofline.

A Pallas kernel runs as an XLA ``custom-call`` whose target is
``tpu_custom_call``; the trace names each event by the instruction's HLO
text, operand shapes included. Only the program's forward kernel carries a
name of its own, so the three kernels are told apart by their signatures:
the forward takes q, k, v; both backward kernels take six operands, and the
dQ kernel returns one array where the dK/dV kernel returns two. The call's
``[B*H, T, D]`` is read from its first operand.
"""

import re

from benchmark.kernels import flash_cost

TARGET = 'custom_call_target="tpu_custom_call"'
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def is_flash(text: str) -> bool:
    return TARGET in text and " custom-call(" in text


def parse(text: str):
    """(kind, [B*H, T, D], dtype bytes) of one kernel event, or None."""
    head, _, rest = text.partition(" custom-call(")
    operands = _SHAPE.findall(rest.split("), custom_call_target")[0])
    results = _SHAPE.findall(head.partition(" = ")[2])
    if not operands or len(operands[0][1].split(",")) != 3:
        return None
    dtype, dims = operands[0]
    shape = [int(x) for x in dims.split(",")]
    if len(operands) == 3:
        kind = "forward"
    elif len(operands) == 6:
        kind = "backward_dq" if len(results) == 1 else "backward_dkv"
    else:
        return None
    return kind, shape, _BYTES.get(dtype, 2)


def seconds(trace) -> float:
    return trace.op_seconds(is_flash)


def roofline_seconds(trace, peaks):
    """(least seconds for the calls found, which peak bounds most of it,
    calls by kind); None when the trace has no kernel it can read."""
    least, by_bound, kinds = 0.0, {}, {}
    for text, _, calls in trace.ops(is_flash):
        parsed = parse(text)
        if parsed is None:
            return None
        kind, (bh, t, d), dtype_bytes = parsed
        cost = getattr(flash_cost, kind)((1, bh, t, d), dtype_bytes, True)
        r = flash_cost.roofline_seconds(cost, peaks)
        least += calls * r["seconds"]
        by_bound[r["bound"]] = by_bound.get(r["bound"], 0.0) \
            + calls * r["seconds"]
        kinds[kind] = kinds.get(kind, 0) + calls
    if not kinds:
        return None
    return least, max(by_bound, key=by_bound.get), kinds
