"""The state-space-dual kernels' share of their roofline: the least time the
chip could take for the calls found in the trace (``kernels/ssd_cost.py``:
``u``, ``B``, ``C``, ``Delta``, ``y`` and their gradients across HBM once and
the recurrence's own products with the state, against ``peaks.json``) over
the device time those calls took. A chunked form's products inside a chunk,
the second parts of its float32 operands and the kept states are in the time
and not in the count."""

from benchmark.layer_metrics import _ssd

NAME, UNIT, LAYER = "ssd_roofline_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    taken = trace.op_seconds(_ssd.is_ssd)
    found = _ssd.roofline_seconds(trace, host["peaks"])
    if taken <= 0 or found is None:
        return None
    least, bound, kinds, operations, nbytes = found
    print(f"state-space rule roofline: least {least:.6f} s of {taken:.6f} s "
          f"taken, {operations:.4g} operations, {nbytes:.4g} bytes, bound by "
          f"{bound}, calls {kinds}", flush=True)
    return 100.0 * least / taken
