"""The flash kernels' share of their roofline: the least time the chip could
take for the calls found in the trace (``kernels/flash_cost.py``, the larger
of operations over peak FLOP/s and bytes over peak bandwidth) over the
device time those calls took. The bound is printed beside it."""

from benchmark.layer_metrics import _flash

NAME, UNIT, LAYER = "flash_roofline_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    taken = _flash.seconds(trace)
    found = _flash.roofline_seconds(trace, host["peaks"])
    if taken <= 0 or found is None:
        return None
    least, bound, kinds = found
    print(f"flash roofline: least {least:.6f} s of {taken:.6f} s taken, "
          f"bound by {bound}, calls {kinds} (causal, as the program calls "
          f"them)", flush=True)
    return 100.0 * least / taken
