"""Shared by the expert layer's readers: which trace events belong to the
routed expert layer, which of them are its grouped matmuls, and what each
grouped matmul found should cost at the roofline; and, for a cell whose
grouped matmuls are Mosaic custom calls too, the attention kernels told
apart by the names the program gave them.

The events on ``XLA Ops`` are named by the instruction's HLO text: its own
name, its result's shape and its operands' shapes, no scope of the program.
So the expert layer's operations are found by what only they touch:

* the kernels by their names, ``tepdist_gmm_fwd`` / ``_dx`` / ``_dw``
  (``tepdist_tpu/ops/pallas/grouped_matmul.py``);
* everything else (router matmul and softmax, top-k, the two sorts and the
  index arithmetic, the gathers into and out of the tile-aligned layout, the
  gated activation, forward, remat and backward) by an array only the
  expert layer has: ``rows`` rows of the layout (``[81920,...``), one entry
  an assignment (``[65536]``), or the router's ``[tokens, experts]`` and
  ``[tokens, k]`` (``[8192,64]``, ``[8192,8]``, and per sequence
  ``[2,4096,64]``). The sizes come from the cell's own configuration and
  traffic. A program without a routed expert layer has no such array and
  the readers return nothing.

Not counted: the optimizer's update of the expert weights and the
accumulation of their gradients over micro batches (the optimizer's and the
accumulation scan's, whatever the layer).
"""

import re

from benchmark import trace_reduce
from benchmark.kernels import flash_cost, gmm_cost
from benchmark.layer_metrics._flash import _BYTES, _SHAPE

# Inside an instruction's name: autodiff and remat put their own words
# around a kernel's (``%transpose_jvp_tepdist_flash_dq__...``).
GMM_NAME = "tepdist_gmm_"
_FLASH = re.compile(r"tepdist_flash_(fwd|dq|dkv)__c([01])__")
_FLASH_KINDS = {"fwd": "forward", "dq": "backward_dq", "dkv": "backward_dkv"}


def sizes(cell):
    """(tokens a micro batch, sequences a micro batch, sequence length,
    experts, experts a token, rows of the layout), or None where the
    configuration routes nothing."""
    c, t = cell.config, cell.traffic
    if "num_experts_per_tok" not in c:
        return None
    seqs = int(t["batch"]) // int(t.get("num_micro_batches") or 1)
    T, E, k = int(t["seq"]), int(c["num_experts"]), \
        int(c["num_experts_per_tok"])
    tile = int(c.get("program", {}).get("moe_tile_m", 1))
    rows = (-(-seqs * T * k // tile) + (E if tile > 1 else 0)) * tile
    return seqs * T, seqs, T, E, k, rows


def is_gmm(text: str) -> bool:
    return GMM_NAME in trace_reduce.short_name(text)


def moe_matcher(cell):
    """``match(HLO text)`` for the expert layer's operations, or None."""
    found = sizes(cell)
    if found is None:
        return None
    S, seqs, T, E, k, rows = found
    marks = (f"[{rows},", f"[{rows}]", f"[{S * k}]", f"[{S * k},1]",
             f"[{S},{E}]", f"[{S},{k}]", f"[{seqs},{T},{E}]",
             f"[{seqs},{T * k},{E}]", f"[{seqs},{T * k},1]")

    def match(text: str) -> bool:
        return is_gmm(text) or any(m in text for m in marks)
    return match


def _least(calls_costs, peaks):
    """(least seconds, the peak that bounds most of them, calls by label)
    of ``(label, calls, cost)`` items; None for none."""
    least, by_bound, labels = 0.0, {}, {}
    for label, calls, cost in calls_costs:
        r = gmm_cost.roofline_seconds(cost, peaks)
        least += calls * r["seconds"]
        by_bound[r["bound"]] = by_bound.get(r["bound"], 0.0) \
            + calls * r["seconds"]
        labels[label] = labels.get(label, 0) + calls
    if not labels:
        return None
    return least, max(by_bound, key=by_bound.get), labels


def gmm_roofline_seconds(trace, peaks, cell):
    """(least seconds for the grouped matmuls found, which peak bounds most
    of it, calls by kernel name); None when the trace has none it can
    read. A call's rows are the assignments of one micro batch: the rows
    routed, not the rows of the padded layout."""
    found = sizes(cell)
    if found is None:
        return None
    S, _, _, E, k, _ = found
    items = []
    for text, _, calls in trace.ops(is_gmm):
        # The one [E, K, N] array of the call, operand or result.
        weights = [(d, [int(x) for x in dims.split(",")])
                   for d, dims in _SHAPE.findall(text)
                   if dims.count(",") == 2 and dims.startswith(f"{E},")]
        if not weights:
            return None
        dtype, (_, K, N) = weights[0]
        name = next(n for n in ("gmm_fwd", "gmm_dx", "gmm_dw", "gmm")
                    if n in trace_reduce.short_name(text))
        items.append((name, calls, gmm_cost.grouped_matmul(
            S * k, K, N, E, _BYTES.get(dtype, 2))))
    return _least(items, peaks)


def is_attention(text: str) -> bool:
    return _FLASH.search(trace_reduce.short_name(text)) is not None


def attention_roofline_seconds(trace, peaks):
    """As ``_flash.roofline_seconds``, the kernels found by name
    (``tepdist_flash_<fwd|dq|dkv>__c<causal>__...``) and not by being the
    trace's only custom calls."""
    items = []
    for text, _, calls in trace.ops(is_attention):
        which, causal = _FLASH.search(trace_reduce.short_name(text)).groups()
        operands = _SHAPE.findall(text.partition(" custom-call(")[2])
        if not operands or operands[0][1].count(",") != 2:
            return None
        dtype, dims = operands[0]
        bh, t, d = (int(x) for x in dims.split(","))
        kind = _FLASH_KINDS[which]
        items.append((kind, calls, getattr(flash_cost, kind)(
            (1, bh, t, d), _BYTES.get(dtype, 2), causal == "1")))
    return _least(items, peaks)
