"""The flash attention kernels' share of their roofline in a cell that runs
several kinds of them (window and global layers, fewer key/value heads than
query heads): each event costed by the window and the key/value heads in
its own name (``kernels/window_flash_cost.py`` against ``peaks.json``):
operations of the pairs inside the mask only. Their time, which costs
nothing, is ``attn_time_share.train``'s; the seconds by kind are printed
here."""

from benchmark.layer_metrics import _window_flash

NAME, UNIT, LAYER = "attn_mixed_roofline_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    ops = trace.ops(_window_flash.is_attention)
    taken = sum(s for _, s, _ in ops)
    found = _window_flash.roofline_seconds(trace, host["peaks"])
    if taken <= 0 or found is None:
        return None
    least, bound, kinds = found
    by_kind = {}
    for text, s, _ in ops:
        label = _window_flash.call_cost(text)[0]
        by_kind[label] = by_kind.get(label, 0.0) + s
    print("attention kernels by kind (s): " + ", ".join(
        f"{k} {v:.6f}" for k, v in sorted(by_kind.items())), flush=True)
    print(f"attention roofline (by each kernel's own window): least "
          f"{least:.6f} s of {taken:.6f} s taken, bound by {bound}, calls "
          f"{kinds}", flush=True)
    return 100.0 * least / taken
