"""For the roofline reader of a cell whose attention kernels are not all
alike: every ``tepdist_flash_*`` event found by its name, and each costed
by what **its own name** says of it.

``tepdist_tpu/ops/pallas/flash_attention.py`` names a call
``tepdist_flash_<fwd|dq|dkv>__c<causal>__s<scale>__h<query heads>`` and,
where the call has them, ``__w<window>`` and ``__kv<key/value heads>``.
``_moe.py``'s roofline reader costs every such kernel as full causal
attention with as many key/value heads as query heads; a window kernel that
skips most of its blocks would read above its roofline there (its time
reader, ``attn_time_share.train``, goes by the name alone and serves such a
cell too). This goes by ``kernels/window_flash_cost.py``. A program whose
kernels carry neither field is costed as ``_moe.py`` costs it; one without
such kernels gives nothing to read.
"""

import re

from benchmark import trace_reduce
from benchmark.kernels import window_flash_cost
from benchmark.layer_metrics import _moe
from benchmark.layer_metrics._flash import _BYTES, _SHAPE

NAME = re.compile(r"tepdist_flash_(fwd|dq|dkv)__c([01])__s[^_]+__h(\d+)"
                  r"(?:__w(\d+))?(?:__kv(\d+))?")
KINDS = {"fwd": "forward", "dq": "backward_dq", "dkv": "backward_dkv"}


def is_attention(text: str) -> bool:
    return NAME.search(trace_reduce.short_name(text)) is not None


def call_cost(text: str):
    """(label, operations and bytes) of one kernel event, or None where its
    first operand is not the kernel's ``[B * heads, T, D]`` q."""
    which, causal, heads, window, kv = NAME.search(
        trace_reduce.short_name(text)).groups()
    operands = _SHAPE.findall(text.partition(" custom-call(")[2])
    if not operands or operands[0][1].count(",") != 2:
        return None
    dtype, dims = operands[0]
    bh, t, d = (int(x) for x in dims.split(","))
    heads = int(heads)
    if bh % heads:
        return None
    label = KINDS[which] + (f"_w{window}" if window else "")
    return label, getattr(window_flash_cost, KINDS[which])(
        (bh // heads, heads, t, d), _BYTES.get(dtype, 2), causal == "1",
        int(window) if window else None, int(kv) if kv else None)


def roofline_seconds(trace, peaks):
    """(least seconds for the calls found, which peak bounds most of it,
    calls by label); None when the trace has no kernel it can read."""
    items = []
    for text, _, calls in trace.ops(is_attention):
        found = call_cost(text)
        if found is None:
            return None
        items.append((found[0], calls, found[1]))
    return _moe._least(items, peaks)
