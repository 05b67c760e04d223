"""Share of the traced window the device spent in the routed expert layer
of a chip that holds a share of the experts: router (scores, top-k, the
count of choices), sorts and index arithmetic, gathers into and out of the
layout, grouped matmuls and the gated activation; forward, remat and
backward. Not the shared expert, which is a dense MLP.

Found as ``_moe.py`` finds OLMoE's layer, by the arrays only this layer
has, but the layout's row count is **read from the trace**: the row operand
of the ``tepdist_gmm_*`` events themselves (a held share's layout has as
many rows as its worst case needs, which the cell's files do not say), with
the sorts' length beside it, one entry an assignment (``[S*k]``), and the
router's ``[S, E]`` / ``[S, k]`` / ``[S, k, E]`` at the router's published
width. A program without grouped-matmul kernels gives nothing to read."""

from benchmark.layer_metrics import _moe
from benchmark.layer_metrics._flash import _SHAPE

NAME, UNIT, LAYER = "moe_held_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def layout_rows(trace) -> set:
    """The row counts of the layouts the grouped matmuls ran over: the
    leading dimension of each kernel event's two-dimensional operands."""
    rows = set()
    for text, _, _ in trace.ops(_moe.is_gmm):
        for _, dims in _SHAPE.findall(text.partition(" custom-call(")[2]):
            if dims.count(",") == 1:
                rows.add(int(dims.split(",")[0]))
    return rows


def read(trace, host, cell):
    c, t = cell.config, cell.traffic
    rows = layout_rows(trace)
    if not rows or "num_experts_per_tok" not in c:
        return None
    S = int(t["batch"]) // int(t.get("num_micro_batches") or 1) \
        * int(t["seq"])
    k = int(c["num_experts_per_tok"])
    E = int(c.get("router_num_experts", c["num_experts"]))
    tile = int(c.get("program", {}).get("moe_tile_m", 1))
    sorted_len = {max(m, S * k + int(c["num_experts"]) * tile) for m in rows}
    marks = [f"[{S * k}]", f"[{S * k},1]", f"[{S},{E}]", f"[{S},{k}]",
             f"[{S},{k},{E}]"]
    for m in rows | sorted_len:
        marks += [f"[{m},", f"[{m}]"]

    def match(text: str) -> bool:
        return _moe.is_gmm(text) or any(m in text for m in marks)

    ops = trace.ops(match)
    seconds = sum(s for _, s, _ in ops)
    if seconds <= 0:
        return None
    gmm = sum(s for text, s, _ in ops if _moe.is_gmm(text))
    top = sorted(((s, text[:90]) for text, s, _ in ops
                  if not _moe.is_gmm(text)), reverse=True)[:5]
    print(f"held expert layer (layout rows {sorted(rows)}): {seconds:.6f} s "
          f"of a {trace.window_s:.6f} s window, grouped matmuls {gmm:.6f} "
          f"s, the rest {seconds - gmm:.6f} s; largest of the rest: "
          + "; ".join(f"{s:.4f} s {t}" for s, t in top), flush=True)
    return 100.0 * seconds / trace.window_s
