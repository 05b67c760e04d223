"""The linear-attention kernels' share of their roofline: the least time the
chip could take for the calls found in the trace
(``kernels/lightning_cost.py``: ``q``, ``k``, ``v``, ``o`` and their
gradients across HBM once and the recurrence's own operations, against
``peaks.json``) over the device time those calls took. A chunked form's
products inside a chunk and a backward's second read of its operands are in
the time and not in the count."""

from benchmark.layer_metrics import _sala

NAME, UNIT, LAYER = "lin_attn_roofline_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    taken = trace.op_seconds(_sala.is_lightning)
    found = _sala.lightning_roofline_seconds(trace, host["peaks"])
    if taken <= 0 or found is None:
        return None
    least, bound, kinds = found
    print(f"linear attention roofline: least {least:.6f} s of {taken:.6f} s "
          f"taken, bound by {bound}, calls {kinds}", flush=True)
    return 100.0 * least / taken
