"""Time the flash attention kernels alone, on the chip.

Shape, dtype and tiles in; device microseconds a call of the forward, the dQ
and the dK/dV kernel out, from one ``jax.profiler`` trace reduced by
``benchmark/trace_reduce.py``, each beside its roofline time
(``benchmark/kernels/flash_cost.py``, ``benchmark/peaks.json``), and the
relative L2 distance of o, dq, dk, dv from dense float32 attention of the
same inputs. The kernels are told apart as the benchmark tells them apart
(``benchmark/layer_metrics/_flash.py``), so a call this tool cannot read is a
call the benchmark's flash metrics cannot read either.

No benchmark cell runs this; it is for work on the kernels. There is no CPU
fallback: without a TPU it exits 2. ``--impl`` times another copy of
``flash_attention.py`` (the parent commit's, say) in the same process, so
that two versions are read on one chip in one call.

Run: chiprun -- python tools/flash_bench.py [--shape 3,25,1024,64]
     [--dtype bf16] [--block 512] [--impl old=path/to/flash_attention.py]
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

def load_impl(label: str, path: str):
    spec = importlib.util.spec_from_file_location("flash_impl_" + label, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def dense_reference(q, k, v, do, causal: bool):
    """o, dq, dk, dv of plain float32 attention, every matmul at the
    highest precision."""
    import jax
    import jax.numpy as jnp

    def attend(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest")
        s = s / jnp.sqrt(jnp.float32(q.shape[-1]))
        if causal:
            T = q.shape[2]
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")

    q, k, v, do = (x.astype(jnp.float32) for x in (q, k, v, do))
    o, vjp = jax.vjp(attend, q, k, v)
    return (o,) + vjp(do)


def rel_l2(got, want) -> float:
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def time_impl(label, module, args, peaks, trace_root):
    """One traced window of ``args.iters`` gradient calls (each runs the
    forward, the dQ and the dK/dV kernel once)."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce
    from benchmark.kernels import flash_cost
    from benchmark.layer_metrics import _flash
    from benchmark.lib import tracing

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[args.dtype]
    shape = tuple(int(x) for x in args.shape.split(","))
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    q, k, v, do = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                   for kk in keys)
    causal = bool(args.causal)
    block_q, block_k = args.block_q or args.block, args.block_k or args.block

    def attend(q, k, v):
        return module.flash_attention(q, k, v, causal=causal, block_q=block_q,
                                      block_k=block_k, interpret=False)

    @jax.jit
    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(attend, q, k, v)
        return (o,) + vjp(do)

    got = jax.block_until_ready(fwd_bwd(q, k, v, do))   # compiles
    errors = None
    if args.check:
        want = dense_reference(q, k, v, do, causal)
        errors = {n: rel_l2(g, w)
                  for n, g, w in zip(("o", "dq", "dk", "dv"), got, want)}
        del want

    path = os.path.join(trace_root, label)
    tracing.discard(path)
    jax.profiler.start_trace(path)
    for _ in range(args.iters):
        out = fwd_bwd(q, k, v, do)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    summary = tracing.reduce_trace(path)
    tracing.discard(path)

    record = {"impl": label, "shape": list(shape), "dtype": args.dtype,
              "causal": causal, "block_q": block_q, "block_k": block_k,
              "iters": args.iters,
              "rel_l2_vs_dense_f32": errors, "kernels": {}}
    for text, secs, calls in summary.ops(_flash.is_flash):
        parsed = _flash.parse(text)
        if parsed is None:
            record["kernels"]["unparsed"] = text[:200]
            continue
        kind, (bh, t, d), dtype_bytes = parsed
        cost = getattr(flash_cost, kind)((1, bh, t, d), dtype_bytes, causal)
        least = flash_cost.roofline_seconds(cost, peaks)
        record["kernels"][kind] = {
            "calls": calls, "us_per_call": 1e6 * secs / calls,
            "roofline_us": 1e6 * least["seconds"], "bound": least["bound"],
            "roofline_share_pct": 100.0 * least["seconds"] * calls / secs,
            "name": trace_reduce.short_name(text)}
    # Everything else the gradient call runs on the device (the XLA fusion
    # that makes ``delta``, layout copies): kernel-alone time hides it.
    others = sorted(summary.ops(lambda t: not _flash.is_flash(t)),
                    key=lambda op: -op[1])
    record["other_device_us_per_iter"] = \
        1e6 * sum(s for _, s, _ in others) / args.iters
    record["other_ops"] = [[text[:160], 1e6 * s / args.iters]
                           for text, s, _ in others[:4]]
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="3,25,1024,64", help="B,H,T,D")
    ap.add_argument("--dtype", default="bf16", choices=("bf16", "f32"))
    ap.add_argument("--block", type=int, default=None,
                    help="both tiles (default: the module's own choice)")
    ap.add_argument("--block-q", type=int, default=None)
    ap.add_argument("--block-k", type=int, default=None)
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--check", type=int, default=1,
                    help="0 skips the dense float32 reference, whose "
                         "[B, H, T, T] scores do not fit the chip at long T")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", action="append", default=[],
                    metavar="LABEL=FILE",
                    help="another flash_attention.py to time beside the "
                         "checkout's own (repeatable)")
    ap.add_argument("--only-impl", action="store_true",
                    help="skip the checkout's own kernels")
    ap.add_argument("--out", default=None, help="also write the records "
                    "as JSON lines to this file")
    args = ap.parse_args(argv)

    from benchmark.lib import device

    devices = device.own_chips(1)
    peaks = device.peaks_for(devices[0].device_kind,
                             os.path.join(ROOT, "benchmark"))
    impls = [] if args.only_impl else [
        ("tree", os.path.join(ROOT, "tepdist_tpu", "ops", "pallas",
                              "flash_attention.py"))]
    for item in args.impl:
        label, _, path = item.partition("=")
        impls.append((label, path))
    trace_root = os.path.join(ROOT, ".bench_trace", "flash_bench")
    for label, path in impls:
        try:
            record = time_impl(label, load_impl(label, path), args, peaks,
                               trace_root)
        except Exception as e:  # noqa: BLE001 — one refused variant must
            # not cost the call that times the others
            record = {"impl": label, "error": repr(e)[:2000]}
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
