"""Share of the traced window the device spent in the routed expert layer:
router, sort and index arithmetic, gathers into and out of the layout,
grouped matmuls and the gated activation; forward, remat and backward
(``_moe.py`` says how they are found). The seconds by part are printed."""

from benchmark.layer_metrics import _moe

NAME, UNIT, LAYER = "moe_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    match = _moe.moe_matcher(cell)
    if match is None:
        return None
    ops = trace.ops(match)
    seconds = sum(s for _, s, _ in ops)
    if seconds <= 0:
        return None
    gmm = sum(s for text, s, _ in ops if _moe.is_gmm(text))
    top = sorted(((s, text[:90]) for text, s, _ in ops
                  if not _moe.is_gmm(text)), reverse=True)[:5]
    print(f"expert layer: {seconds:.6f} s of a {trace.window_s:.6f} s "
          f"window, grouped matmuls {gmm:.6f} s, the rest "
          f"{seconds - gmm:.6f} s; largest of the rest: "
          + "; ".join(f"{s:.4f} s {t}" for s, t in top), flush=True)
    return 100.0 * seconds / trace.window_s
