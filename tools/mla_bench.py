"""Time the latent-attention kernels alone, on the chip.

Heads, length, the three head widths and tiles in; device microseconds a call
of the forward (``tepdist_mla_fwd``) and of the backward pass (one kernel,
``tepdist_mla_dkv``, since PR 47; the dQ and the dK/dV kernel of an older
copy, each and summed) out, from one ``jax.profiler`` trace a tiling and copy
reduced by ``benchmark/trace_reduce.py``, each kernel beside its roofline
time as the benchmark costs it (``benchmark/layer_metrics/_mla.py``,
``benchmark/kernels/mla_cost.py``, ``benchmark/peaks.json``: a call this
tool cannot read is one the benchmark's readers cannot read either) and the
backward pass beside the **whole** pass's (``backward_dq`` +
``backward_dkv`` operations, every operand and result across HBM once), and
the relative L2 distance of ``o`` and the five gradients from dense float32
attention of the same inputs (the plain form: the shared rotary key joined
to every head's keys, an explicit mask, a block of queries at a time so that
no ``[T, T]`` array is held).

No benchmark cell runs this; it is for work on the kernels. There is no CPU
fallback: without a TPU it exits 2. ``--impl`` times another copy of
``mla_attention.py`` (the parent commit's, say) in the same process, so that
two versions are read on one chip in one call, and says whether its ``o``
and five gradients are the first copy's bit for bit.

Run: chiprun -- python tools/mla_bench.py [--heads 1,16,16384]
     [--widths 128,64,128] [--dtype bf16] [--block 512,256,512x1024]
     [--impl old=path/to/mla_attention.py]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

QUERY_BLOCK = 512
NAMES = ("o", "dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv")


def whole_backward(heads, widths, dtype_bytes: int) -> dict:
    """Operations and bytes of the whole backward pass: both halves'
    matmuls, the dK/dV half's traffic and the two dQ results."""
    from benchmark.kernels import mla_cost
    B, H, T = heads
    dq, dkv = (getattr(mla_cost, kind)(heads, widths, dtype_bytes)
               for kind in ("backward_dq", "backward_dkv"))
    return {"ops": dq["ops"] + dkv["ops"],
            "bytes": dkv["bytes"]
            + B * H * T * (widths[0] + widths[1]) * dtype_bytes}


def dense_reference(operands, do, scale: float):
    """o and the five gradients of plain float32 causal attention, every
    matmul at the highest precision, a head and a block of queries at a
    time."""
    import jax
    import jax.numpy as jnp

    def head(qn, qr, kn, kr, v):                 # one head: [T, .]
        T = qn.shape[0]
        qb = min(QUERY_BLOCK, T)
        q = jnp.concatenate([qn, qr], axis=-1)
        k = jnp.concatenate([kn, kr], axis=-1)
        keys = jnp.arange(T)

        @jax.checkpoint
        def block(args):
            start, qs = args
            s = jnp.matmul(qs, k.T, precision="highest") * scale
            seen = (start + jnp.arange(qb))[:, None] >= keys[None, :]
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.matmul(p, v, precision="highest")

        o = jax.lax.map(block, (jnp.arange(0, T, qb),
                                q.reshape(T // qb, qb, -1)))
        return o.reshape(T, -1)

    def attend(qn, qr, kn, kr, v):               # [B, H, T, .]; kr [B, 1, ..]
        B, H = qn.shape[:2]
        kr = jnp.broadcast_to(kr, (B, H) + kr.shape[2:])
        flat = [x.reshape((B * H,) + x.shape[2:])
                for x in (qn, qr, kn, kr, v)]
        o = jax.lax.map(lambda xs: head(*xs), tuple(flat))
        return o.reshape(B, H, *o.shape[1:])

    operands = [x.astype(jnp.float32) for x in operands]
    o, vjp = jax.vjp(attend, *operands)
    return (o,) + vjp(do.astype(jnp.float32))


def time_tiling(label, module, block_q, block_k, operands, do, scale, want,
                args, peaks, trace_root, first):
    """One traced window of ``args.iters`` gradient calls (each runs the
    forward and the backward pass once) of one copy at one tiling.
    ``first``: the first copy's record and results at this tiling, or
    None."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce
    from benchmark.kernels import mla_cost
    from benchmark.layer_metrics import _mla
    from benchmark.lib import tracing
    from tools.flash_bench import rel_l2

    heads = operands[0].shape[:3]
    widths = tuple(operands[i].shape[-1] for i in (0, 1, 4))

    @jax.jit
    def fwd_bwd(operands, do):
        o, vjp = jax.vjp(lambda *xs: module.mla_attention(
            *xs, causal=True, scale=scale, block_q=block_q, block_k=block_k,
            interpret=False), *operands)
        return (o,) + vjp(do)

    got = jax.block_until_ready(fwd_bwd(operands, do))   # compiles
    errors = want and {n: rel_l2(g, w) for n, g, w in zip(NAMES, got, want)}

    path = os.path.join(trace_root, f"{label}_{block_q}x{block_k}")
    tracing.discard(path)
    jax.profiler.start_trace(path)
    for _ in range(args.iters):
        out = fwd_bwd(operands, do)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    summary = tracing.reduce_trace(path)
    tracing.discard(path)

    record = {"impl": label, "block_q": block_q, "block_k": block_k,
              "heads": args.heads, "widths": args.widths,
              "dtype": args.dtype, "iters": args.iters,
              "rel_l2_vs_dense_f32": errors, "kernels": {}}
    passes = {}
    for text, secs, calls in summary.ops(_mla.is_mla):
        found = _mla.call_cost(text)
        if found is None:
            record["kernels"]["unparsed"] = text[:200]
            continue
        kind, cost = found
        least = mla_cost.roofline_seconds(cost, peaks)
        record["kernels"][kind] = {
            "calls": calls, "us_per_call": 1e6 * secs / calls,
            "results": text.partition(" custom-call(")[0].count("["),
            "ops": cost["ops"], "bytes": cost["bytes"],
            "roofline_us": 1e6 * least["seconds"], "bound": least["bound"],
            "roofline_share_pct": 100.0 * least["seconds"] * calls / secs,
            "name": trace_reduce.short_name(text)}
        which = "forward" if kind == "forward" else "backward"
        passes[which] = passes.get(which, 0.0) + secs / calls
    itemsize = jnp.dtype(do.dtype).itemsize
    whole = {"forward": mla_cost.forward(heads, widths, itemsize),
             "backward": whole_backward(heads, widths, itemsize)}
    for which, secs in passes.items():
        least = mla_cost.roofline_seconds(whole[which], peaks)
        record[which] = {
            "us_per_call": 1e6 * secs, "ops": whole[which]["ops"],
            "roofline_us": 1e6 * least["seconds"], "bound": least["bound"],
            "roofline_share_pct": 100.0 * least["seconds"] / secs}
    if first is not None:
        record["same_bits_as_first"] = {
            n: bool(jnp.array_equal(g, f))
            for n, g, f in zip(NAMES, got, first[1])}
        record["first_over_this"] = {
            which: first[0][which]["us_per_call"]
            / record[which]["us_per_call"]
            for which in passes if which in first[0]}
    # Everything else the gradient call runs on the device (``delta``, the
    # sum of the shared key's gradient over the heads, layout copies).
    others = sorted(summary.ops(lambda t: not _mla.is_mla(t)),
                    key=lambda op: -op[1])
    record["other_device_us_per_iter"] = \
        1e6 * sum(s for _, s, _ in others) / args.iters
    record["other_ops"] = [[text[:160], 1e6 * s / args.iters]
                           for text, s, _ in others[:4]]
    return record, got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--heads", default="1,16,16384", help="B,H,T")
    ap.add_argument("--widths", default="128,64,128", help="Dn,Dr,Dv")
    ap.add_argument("--dtype", default="bf16", choices=("bf16", "f32"))
    ap.add_argument("--block", default="512",
                    help="tilings to time, each BQ or BQxBK (comma list)")
    ap.add_argument("--scale", type=float, default=None,
                    help="default: sarvam-105b's (Dn+Dr)^-0.5 * 1.3689^2")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--check", type=int, default=1,
                    help="0 skips the dense float32 reference")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", action="append", default=[],
                    metavar="LABEL=FILE",
                    help="another mla_attention.py to time beside the "
                         "checkout's own and compare with it bit for bit "
                         "(repeatable)")
    ap.add_argument("--out", default=None, help="also write the records "
                    "as JSON lines to this file")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.lib import device
    from tools.flash_bench import load_impl

    devices = device.own_chips(1)
    peaks = device.peaks_for(devices[0].device_kind,
                             os.path.join(ROOT, "benchmark"))
    B, H, T = (int(x) for x in args.heads.split(","))
    Dn, Dr, Dv = (int(x) for x in args.widths.split(","))
    scale = args.scale or (Dn + Dr) ** -0.5 * (0.1 * math.log(40) + 1) ** 2
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[args.dtype]
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    shapes = ((B, H, T, Dn), (B, H, T, Dr), (B, H, T, Dn), (B, 1, T, Dr),
              (B, H, T, Dv), (B, H, T, Dv))
    *operands, do = (jax.random.normal(k, s, jnp.float32).astype(dtype)
                     for k, s in zip(keys, shapes))
    want = jax.block_until_ready(jax.jit(dense_reference, static_argnums=2)(
        operands, do, scale)) if args.check else None
    trace_root = os.path.join(ROOT, ".bench_trace", "mla_bench")
    impls = [("tree", os.path.join(ROOT, "tepdist_tpu", "ops", "pallas",
                                   "mla_attention.py"))]
    impls += [tuple(item.split("=", 1)) for item in args.impl]
    modules = [(label, load_impl(label, path)) for label, path in impls]
    for item in args.block.split(","):
        bq, _, bk = item.partition("x")
        first = None
        for label, module in modules:
            try:
                record, got = time_tiling(
                    label, module, int(bq), int(bk or bq), tuple(operands),
                    do, scale, want, args, peaks, trace_root, first)
                first = (record, got) if first is None else first
                del got
            except Exception as e:  # noqa: BLE001 — one refused variant
                # must not cost the call that times the others
                record = {"impl": label, "block": item,
                          "error": repr(e)[:2000]}
            record["device"] = devices[0].device_kind
            line = json.dumps(record)
            print(line, flush=True)
            if args.out:
                os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                            exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
