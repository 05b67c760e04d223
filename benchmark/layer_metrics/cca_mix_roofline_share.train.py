"""The compressed attention's mixing kernels' share of their roofline: the
least time the chip could take for the calls found in the trace
(``kernels/cca_mix_cost.py``: a head's two ``[D, D]`` products a row forward
and twice that backward, the latents, the result, the cotangent and the
latents' gradient across HBM once, the weights and their sums once a head,
against ``peaks.json``) over the device time those calls took. Each kernel is
costed by the shapes in its own event."""

from benchmark.layer_metrics import _cca

NAME, UNIT, LAYER = "cca_mix_roofline_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    taken = trace.op_seconds(_cca.is_cca_mix)
    found = _cca.roofline_seconds(trace, host["peaks"])
    if taken <= 0 or found is None:
        return None
    least, bound, kinds, operations, nbytes = found
    print(f"cca mixing roofline: least {least:.6f} s of {taken:.6f} s "
          f"taken, {operations:.4g} operations, {nbytes:.4g} bytes, bound by "
          f"{bound}, calls {kinds}", flush=True)
    return 100.0 * least / taken
