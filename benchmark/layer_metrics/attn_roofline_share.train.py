"""The flash attention kernels' share of their roofline
(``kernels/flash_cost.py`` against ``peaks.json``), the kernels found by
name: ``flash_roofline_share.train`` for a cell that runs other Mosaic
custom calls beside them."""

from benchmark.layer_metrics import _moe

NAME, UNIT, LAYER = "attn_roofline_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    taken = trace.op_seconds(_moe.is_attention)
    found = _moe.attention_roofline_seconds(trace, host["peaks"])
    if taken <= 0 or found is None:
        return None
    least, bound, kinds = found
    print(f"attention roofline: least {least:.6f} s of {taken:.6f} s taken, "
          f"bound by {bound}, calls {kinds}", flush=True)
    return 100.0 * least / taken
