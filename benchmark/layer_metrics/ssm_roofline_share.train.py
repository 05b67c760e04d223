"""The selective-scan kernels' share of their roofline: the least time the
chip could take for the calls found in the trace (``kernels/ssm_cost.py``:
the operation's own operands and results across HBM once and a stated count
of operations a state element, against ``peaks.json``) over the device time
those calls took. Chunk-boundary states and whatever else the implementation
moves are in the time and not in the count."""

from benchmark.layer_metrics import _ssm

NAME, UNIT, LAYER = "ssm_roofline_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    taken = trace.op_seconds(_ssm.is_ssm)
    found = _ssm.roofline_seconds(trace, host["peaks"])
    if taken <= 0 or found is None:
        return None
    least, bound, kinds = found
    print(f"selective scan roofline: least {least:.6f} s of {taken:.6f} s "
          f"taken, bound by {bound}, calls {kinds}", flush=True)
    return 100.0 * least / taken
