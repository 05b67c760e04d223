"""The block top-k attention kernels' share of their roofline (the kernels
alone, not the choice): the least time the chip could take for the calls
found in the trace (``kernels/topk_attn_cost.py``: two matmuls forward and
five backward over the (query, key) pairs the geometry visits, every operand
and result across HBM once, against ``peaks.json``) over the device time
those calls took."""

from benchmark.layer_metrics import _sala

NAME, UNIT, LAYER = "topk_attn_roofline_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    taken = trace.op_seconds(_sala.is_topk_kernel)
    found = _sala.topk_roofline_seconds(trace, host["peaks"], cell)
    if taken <= 0 or found is None:
        return None
    least, bound, kinds = found
    print(f"block top-k attention roofline: least {least:.6f} s of "
          f"{taken:.6f} s taken, bound by {bound}, calls {kinds}", flush=True)
    return 100.0 * least / taken
