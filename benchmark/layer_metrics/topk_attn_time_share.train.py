"""Share of the traced window the device spent in the sparse layer's block
top-k attention: the choice (scores over the compressed keys, their softmax,
the pooling onto blocks, the top-k and its sort: XLA operations, found by the
arrays only the choice has) and the kernels that visit the chosen blocks
(``tepdist_topk_attn_fwd`` in the forward walk and again in the backward
walk's recomputation, ``tepdist_topk_attn_bwd``), mean over the chips used.
Seconds of the choice and of the kernels are printed apart."""

from benchmark.layer_metrics import _sala

NAME, UNIT, LAYER = "topk_attn_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    kernels = trace.op_seconds(_sala.is_topk_kernel)
    choice = _sala.choice_matcher(cell)
    if kernels <= 0 or choice is None:
        return None
    ops = trace.ops(choice)
    chosen = sum(s for _, s, _ in ops)
    top = sorted(((s, text[:90]) for text, s, _ in ops), reverse=True)[:5]
    print(f"block top-k attention: kernels {kernels:.6f} s, the choice "
          f"{chosen:.6f} s in {len(ops)} operations, longest {top}",
          flush=True)
    return 100.0 * (kernels + chosen) / trace.window_s
