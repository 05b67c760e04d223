"""The scalar-decay delta-rule kernels' share of their roofline: the least
time the chip could take for the calls found in the trace
(``kernels/gdn_cost.py``: ``q``, ``k``, ``v``, ``g``, ``beta``, ``o`` and
their gradients across HBM once and the recurrence's own products with the
state, against ``peaks.json``) over the device time those calls took. A
chunked form's products inside a chunk, its triangular solve, the second
parts of its float32 operands, the kept states and the inverses are in the
time and not in the count."""

from benchmark.layer_metrics import _gdn

NAME, UNIT, LAYER = "gdn_roofline_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    taken = trace.op_seconds(_gdn.is_gdn)
    found = _gdn.roofline_seconds(trace, host["peaks"])
    if taken <= 0 or found is None:
        return None
    least, bound, kinds, operations, nbytes = found
    print(f"gated delta rule roofline: least {least:.6f} s of {taken:.6f} s "
          f"taken, {operations:.4g} operations, {nbytes:.4g} bytes, bound by "
          f"{bound}, calls {kinds}", flush=True)
    return 100.0 * least / taken
