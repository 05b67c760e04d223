"""The latent-attention kernels' share of their roofline: the least time the
chip could take for the calls found in the trace (``kernels/mla_cost.py``:
the two matmuls a live pair of the forward and the five of the backward at
the heads' two widths, each operand and result across HBM once, the shared
rotary key once a batch row, against ``peaks.json``) over the device time
those calls took. Each kernel is costed by the shapes in its own event."""

from benchmark.layer_metrics import _mla

NAME, UNIT, LAYER = "mla_roofline_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    taken = trace.op_seconds(_mla.is_mla)
    found = _mla.roofline_seconds(trace, host["peaks"])
    if taken <= 0 or found is None:
        return None
    least, bound, kinds, operations, nbytes = found
    print(f"latent attention roofline: least {least:.6f} s of {taken:.6f} s "
          f"taken, {operations:.4g} operations, {nbytes:.4g} bytes, bound by "
          f"{bound}, calls {kinds}", flush=True)
    return 100.0 * least / taken
