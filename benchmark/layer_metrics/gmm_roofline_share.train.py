"""The grouped matmuls' share of their roofline: the least time the chip
could take for the calls found in the trace (``kernels/gmm_cost.py``: the
rows routed, every operand and result across HBM once, against
``peaks.json``) over the device time those calls took. The rows a layout
pads in are in the time and not in the count."""

from benchmark.layer_metrics import _moe

NAME, UNIT, LAYER = "gmm_roofline_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    taken = trace.op_seconds(_moe.is_gmm)
    found = _moe.gmm_roofline_seconds(trace, host["peaks"], cell)
    if taken <= 0 or found is None:
        return None
    least, bound, names = found
    print(f"grouped matmul roofline: least {least:.6f} s of {taken:.6f} s "
          f"taken, bound by {bound}, calls {names}", flush=True)
    return 100.0 * least / taken
