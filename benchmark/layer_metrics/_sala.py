"""Shared by the linear-attention and the block top-k attention readers:
which trace events are theirs, and what each kernel call found should cost
at the roofline.

The kernels are found by the names the program gives them, inside the
instruction's own name (autodiff and remat put their words around it):
``tepdist_lightning_fwd`` / ``_bwd_dq`` / ``_bwd_dkv``
(``tepdist_tpu/ops/pallas/lightning_attention.py``) and
``tepdist_topk_attn_fwd`` / ``_bwd`` (``block_topk_attention.py``). A call's
sizes are read from its HLO text, from the operands' shapes as
``operand_layout_constraints`` lists them: a lightning call's first operand
is the float32 ``[H]`` of its decays and the next ``[batch, T, H * D]``; a
top-k call's first is the flat int32 of its sets, then ``q`` ``[batch, T, G,
R, D]``. The sparse layer's **choice** runs as XLA operations with no name of
the program's; they are found by the arrays only the choice has (the
compressed positions' count as a last dimension, the per-chunk block scores
and sets), their sizes from the cell's own files. A program without the
kernels has no such event and the readers return nothing.
"""

import re

from benchmark import trace_reduce
from benchmark.kernels import lightning_cost, topk_attn_cost
from benchmark.layer_metrics._flash import _BYTES, _SHAPE
from benchmark.layer_metrics._moe import _least

LIGHTNING_NAME = "tepdist_lightning_"
TOPK_NAME = "tepdist_topk_attn_"
# Query tokens a step of the program's choice (block_topk_attention.py).
SCORE_CHUNK = 1024
# Up to the next attribute: the shapes' own layouts hold braces and commas.
_OPERANDS = re.compile(r"operand_layout_constraints=\{(.*?)\}(?:, \w+=|$)")


def is_lightning(text: str) -> bool:
    return LIGHTNING_NAME in trace_reduce.short_name(text)


def is_topk_kernel(text: str) -> bool:
    return TOPK_NAME in trace_reduce.short_name(text)


def _operands(text: str):
    listed = _OPERANDS.search(text)
    return [(dtype, [int(x) for x in dims.split(",") if x])
            for dtype, dims in _SHAPE.findall(listed.group(1))] \
        if listed else []


def parse_lightning(text: str):
    """(kind, tokens, H, D, activation bytes) of one kernel event, or
    None."""
    ops = _operands(text)
    if len(ops) < 2 or len(ops[0][1]) != 1 or len(ops[1][1]) != 3:
        return None
    H = ops[0][1][0]
    batch, T, HD = ops[1][1]
    kind = "forward" if "lightning_fwd" in trace_reduce.short_name(text) \
        else "backward"
    return kind, batch * T, H, HD // H, _BYTES.get(ops[1][0], 2)


def parse_topk(text: str):
    """(kind, batch, T, H, G, D, sets' entries a query and group, activation
    bytes) of one kernel event, or None."""
    ops = _operands(text)
    if len(ops) < 2 or len(ops[0][1]) != 1 or len(ops[1][1]) != 5:
        return None
    batch, T, G, R, D = ops[1][1]
    kind = "forward" if "topk_attn_fwd" in trace_reduce.short_name(text) \
        else "backward"
    return (kind, batch, T, G * R, G, D, ops[0][1][0] // (batch * G * T),
            _BYTES.get(ops[1][0], 2))


def lightning_roofline_seconds(trace, peaks):
    """(least seconds for the calls found, which peak bounds most of it,
    calls by kind); None when the trace has no such kernel it can read. The
    two backward kernels of a call share one backward's cost: each event is
    costed at half of it."""
    items = []
    for text, _, calls in trace.ops(is_lightning):
        parsed = parse_lightning(text)
        if parsed is None:
            return None
        kind, tokens, H, D, act = parsed
        cost = getattr(lightning_cost, kind)(tokens, H, D, act)
        if kind == "backward":
            cost = {k: v / 2 for k, v in cost.items()}
        items.append((kind, calls, cost))
    return _least(items, peaks)


def block_size(cell):
    return int(cell.config.get("sparse_config", {}).get("block_size", 0))


def topk_roofline_seconds(trace, peaks, cell):
    """As above for the top-k attention kernels; the block size is the
    configuration's, the sets' size the call's own."""
    bs = block_size(cell)
    if not bs:
        return None
    items = []
    for text, _, calls in trace.ops(is_topk_kernel):
        parsed = parse_topk(text)
        if parsed is None:
            return None
        kind, batch, T, H, G, D, K, act = parsed
        cost = getattr(topk_attn_cost, kind)(T, H, G, D, bs, K, act)
        items.append((kind, calls,
                      {k: batch * v for k, v in cost.items()}))
    return _least(items, peaks)


def choice_matcher(cell):
    """``match(HLO text)`` for the operations of the sparse layer's choice
    (compressed keys' scores, their softmax, the pooling, the top-k and its
    sort), or None where the cell's sequences are at or under ``dense_len``
    or the configuration has no sparse layer."""
    geo = cell.config.get("sparse_config")
    T = int(cell.traffic["seq"])
    if not geo or T <= int(geo["dense_len"]):
        return None
    n = (T - int(geo["kernel_size"])) // int(geo["kernel_stride"]) + 1
    nb = T // int(geo["block_size"])
    K = min(int(geo["topk"]), nb)
    Tc = SCORE_CHUNK if T % SCORE_CHUNK == 0 else T
    # The compressed positions as a last dimension (also padded for the
    # pooling), and a chunk's block scores and sets.
    marks = [f",{m}]" for m in range(n, n + 4)] \
        + [f"{Tc},{nb}]", f"{Tc},{K}]"]

    def match(text: str) -> bool:
        return not is_topk_kernel(text) and any(m in text for m in marks)
    return match
