"""Time the compressed attention's mixing kernels alone, on the chip.

Batch, length, heads and blocks in; device microseconds a call of
``tepdist_cca_mix_fwd`` and of ``tepdist_cca_mix_bwd``
(``tepdist_tpu/ops/pallas/cca_mix.py``) out, from one ``jax.profiler`` trace
a block size reduced by ``benchmark/trace_reduce.py``, each kernel beside its
roofline time as the benchmark costs it (``benchmark/layer_metrics/_cca.py``,
``benchmark/kernels/cca_mix_cost.py``, ``benchmark/peaks.json``: a call this
tool cannot read is one the benchmark's readers cannot read either), the
``jax.numpy`` form (``cca_mix.reference``) timed beside them the same way,
and the relative L2 distance of ``q``, ``k`` and the six gradients from that
form computed in float32 at the highest matmul precision.

No benchmark cell runs this; it is for work on the kernels. There is no CPU
fallback: without a TPU it exits 2.

Run: chiprun -- python tools/cca_bench.py [--shape 1,8192] [--heads 8,2,128]
     [--dtype bf16] [--block-t 512,1024,2048] [--iters 10]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NAMES = ("q", "k", "dq0", "dk0", "dw1", "db1", "dw2", "db2")


def make_operands(B, T, H, Hkv, D, dtype, seed):
    """The six operands at the model's scales (latents of unit order, taps
    normal(0.5), matrices normal(0.02)) and the two cotangents."""
    import jax
    import jax.numpy as jnp
    N, f32 = H + Hkv, jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    return (jax.random.normal(ks[0], (B, T, H * D), f32).astype(dtype),
            jax.random.normal(ks[1], (B, T, Hkv * D), f32).astype(dtype),
            0.5 * jax.random.normal(ks[2], (2, N * D), f32),
            0.1 * jax.random.normal(ks[3], (N * D,), f32),
            (0.02 * jax.random.normal(ks[4], (2, N, D, D), f32)).astype(
                dtype),
            0.1 * jax.random.normal(ks[5], (N * D,), f32)), (
        jax.random.normal(ks[6], (B, H, T, D), f32).astype(dtype),
        jax.random.normal(ks[7], (B, Hkv, T, D), f32).astype(dtype))


def fwd_bwd(fn):
    import jax

    @jax.jit
    def run(operands, cts):
        out, vjp = jax.vjp(fn, *operands)
        return tuple(out) + vjp(cts)
    return run


def traced(run, operands, cts, iters, path):
    """The trace summary of ``iters`` calls of ``run``."""
    import jax

    from benchmark.lib import tracing
    tracing.discard(path)
    jax.profiler.start_trace(path)
    for _ in range(iters):
        out = run(operands, cts)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    summary = tracing.reduce_trace(path)
    tracing.discard(path)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="1,8192", help="B,T")
    ap.add_argument("--heads", default="8,2,128", help="H,Hkv,D")
    ap.add_argument("--dtype", default="bf16", choices=("bf16", "f32"))
    ap.add_argument("--block-t", default="512,1024,2048",
                    help="rows a grid step to time (comma list)")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="also write the records "
                    "as JSON lines to this file")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce
    from benchmark.kernels import cca_mix_cost
    from benchmark.layer_metrics import _cca
    from benchmark.lib import device
    from tepdist_tpu.ops.pallas import cca_mix
    from tools.flash_bench import rel_l2

    devices = device.own_chips(1)
    peaks = device.peaks_for(devices[0].device_kind,
                             os.path.join(ROOT, "benchmark"))
    B, T = (int(x) for x in args.shape.split(","))
    H, Hkv, D = (int(x) for x in args.heads.split(","))
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[args.dtype]
    operands, cts = make_operands(B, T, H, Hkv, D, dtype, args.seed)
    itemsize = jnp.dtype(dtype).itemsize
    costs = {kind: getattr(cca_mix_cost, kind)(B * T, H + Hkv, D, itemsize)
             for kind in ("forward", "backward")}
    least = {kind: cca_mix_cost.roofline_seconds(cost, peaks)
             for kind, cost in costs.items()}
    trace_root = os.path.join(ROOT, ".bench_trace", "cca_bench")

    def emit(record):
        record["device"] = devices[0].device_kind
        line = json.dumps(record)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")

    # The yardstick: the jax.numpy form in float32, matmuls at the highest
    # precision; and the same form as a bf16 layer would run it, timed.
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(fwd_bwd(cca_mix.reference)(
            tuple(x.astype(jnp.float32) for x in operands),
            tuple(x.astype(jnp.float32) for x in cts)))
    plain = fwd_bwd(cca_mix.reference)
    got = jax.block_until_ready(plain(operands, cts))
    summary = traced(plain, operands, cts, args.iters,
                     os.path.join(trace_root, "reference"))
    device_us = 1e6 * sum(s for _, s, _ in summary.ops(lambda t: True)) \
        / args.iters
    roofline_us = 1e6 * sum(r["seconds"] for r in least.values())
    emit({"impl": "jax.numpy", "shape": args.shape, "heads": args.heads,
          "dtype": args.dtype, "iters": args.iters,
          "device_us_per_fwd_and_bwd": device_us,
          "roofline_us": roofline_us,
          "roofline_share_pct": 100.0 * roofline_us / device_us,
          "rel_l2_vs_f32": {n: rel_l2(g, w)
                            for n, g, w in zip(NAMES, got, want)},
          "longest_ops": [[text[:120], 1e6 * s / args.iters] for text, s, _
                          in sorted(summary.ops(lambda t: True),
                                    key=lambda op: -op[1])[:5]]})

    for block_t in (int(x) for x in args.block_t.split(",")):
        try:
            run = fwd_bwd(lambda *ops: cca_mix.cca_mix(
                *ops, block_t=block_t, interpret=False))
            got = jax.block_until_ready(run(operands, cts))   # compiles
            summary = traced(run, operands, cts, args.iters,
                             os.path.join(trace_root, f"bt{block_t}"))
            record = {"impl": "kernels", "block_t": block_t,
                      "shape": args.shape, "heads": args.heads,
                      "dtype": args.dtype, "iters": args.iters,
                      "rel_l2_vs_f32": {n: rel_l2(g, w) for n, g, w
                                        in zip(NAMES, got, want)},
                      "kernels": {}}
            for text, secs, calls in summary.ops(_cca.is_cca_mix):
                found = _cca.call_cost(text)
                if found is None:
                    record["kernels"]["unparsed"] = text[:200]
                    continue
                kind, cost = found
                assert cost == costs[kind], (cost, costs[kind])
                record["kernels"][kind] = {
                    "calls": calls, "us_per_call": 1e6 * secs / calls,
                    "ops": cost["ops"], "bytes": cost["bytes"],
                    "roofline_us": 1e6 * least[kind]["seconds"],
                    "bound": least[kind]["bound"],
                    "roofline_share_pct":
                        100.0 * least[kind]["seconds"] * calls / secs,
                    "name": trace_reduce.short_name(text)}
            others = sorted(summary.ops(lambda t: not _cca.is_cca_mix(t)),
                            key=lambda op: -op[1])
            record["other_device_us_per_iter"] = \
                1e6 * sum(s for _, s, _ in others) / args.iters
            record["other_ops"] = [[text[:120], 1e6 * s / args.iters]
                                   for text, s, _ in others[:3]]
        except Exception as e:  # noqa: BLE001 — one refused block size must
            # not cost the call that times the others
            record = {"impl": "kernels", "block_t": block_t,
                      "error": repr(e)[:2000]}
        emit(record)
    return 0


if __name__ == "__main__":
    sys.exit(main())
