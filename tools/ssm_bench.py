"""Time the selective-scan kernels alone, on the chip.

Shape and dtype in; for each channel block and chunk asked for, device
microseconds a call of the forward kernel (without and with what the backward
needs saved) and of the backward kernel out, from one ``jax.profiler`` trace
a variant reduced by ``benchmark/trace_reduce.py``, each beside its roofline
time (``benchmark/kernels/ssm_cost.py``, ``benchmark/peaks.json``), and the
relative L2 distance of the output and the seven gradients from the
sequential float32 scan of ``benchmark/reference/jamba.py`` on the same
inputs (``benchmark/kernels/ssm_check.py``, which the Jamba cell's builder
also holds the kernel to). The kernels are found as the benchmark finds them
(``benchmark/layer_metrics/_ssm.py``), so a call this tool cannot read is a
call the benchmark's scan metrics cannot read either.

No benchmark cell runs this; it is for work on the kernels. There is no CPU
fallback: without a TPU it exits 2. A variant the compiler refuses is
reported and the others still run.

Run: chiprun -- python tools/ssm_bench.py [--shape 1,8192,5120] [--states 16]
     [--dtype bf16] [--block-d 256,512,1024] [--chunk 64,128]
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def time_variant(block_d: int, chunk: int, inputs, want, args, peaks,
                 trace_root):
    import jax

    from benchmark import trace_reduce
    from benchmark.kernels import ssm_check, ssm_cost
    from benchmark.layer_metrics import _ssm
    from benchmark.lib import tracing
    from tepdist_tpu.ops.pallas.selective_scan import selective_scan

    *operands, do = inputs

    def scan(*a):
        return selective_scan(*a, chunk=chunk, block_d=block_d,
                              interpret=False)

    @jax.jit
    def fwd_bwd(*a):
        out, vjp = jax.vjp(scan, *a)
        return (out,) + vjp(do)

    plain = jax.jit(scan)
    got = jax.block_until_ready(fwd_bwd(*operands))         # compiles
    jax.block_until_ready(plain(*operands))
    record = {"block_d": block_d, "chunk": chunk,
              "shape": list(operands[0].shape), "dtype": args.dtype,
              "iters": args.iters, "kernels": {}}
    if want is not None:
        record["rel_l2_vs_sequential_f32"] = {
            n: ssm_check.rel_l2(g, w)
            for n, g, w in zip(ssm_check.NAMES, got, want)}
    del got

    def traced(label, run):
        path = os.path.join(trace_root, f"{label}-{block_d}-{chunk}")
        tracing.discard(path)
        jax.profiler.start_trace(path)
        for _ in range(args.iters):
            out = run(*operands)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        summary = tracing.reduce_trace(path)
        tracing.discard(path)
        return summary

    for label, run in (("forward_alone", plain), ("grad", fwd_bwd)):
        summary = traced(label, run)
        for text, secs, calls in summary.ops(_ssm.is_ssm):
            parsed = _ssm.parse(text)
            if parsed is None:
                record["kernels"]["unparsed"] = text[:200]
                continue
            kind, batch, T, Di, N, act, delta = parsed
            least = ssm_cost.roofline_seconds(
                getattr(ssm_cost, kind)(batch * T, Di, N, act, delta), peaks)
            name = kind if label == "grad" else label
            record["kernels"]["forward_saving" if name == "forward"
                              else name] = {
                "calls": calls, "us_per_call": 1e6 * secs / calls,
                "roofline_us": 1e6 * least["seconds"],
                "bound": least["bound"],
                "roofline_share_pct": 100.0 * least["seconds"] * calls / secs,
                "name": trace_reduce.short_name(text)}
        if label == "grad":
            others = sorted(summary.ops(lambda t: not _ssm.is_ssm(t)),
                            key=lambda op: -op[1])
            record["other_device_us_per_iter"] = \
                1e6 * sum(s for _, s, _ in others) / args.iters
            record["other_ops"] = [[text[:160], 1e6 * s / args.iters]
                                   for text, s, _ in others[:4]]
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="1,8192,5120", help="batch,T,Di")
    ap.add_argument("--states", type=int, default=16)
    ap.add_argument("--dtype", default="bf16", choices=("bf16", "f32"))
    ap.add_argument("--block-d", default="1024", help="channel blocks, a "
                    "comma between them")
    ap.add_argument("--chunk", default="64", help="chunks, likewise")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--check", type=int, default=1,
                    help="0 skips the sequential float32 scan")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="also write the records "
                    "as JSON lines to this file")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.kernels import ssm_check
    from benchmark.lib import device

    devices = device.own_chips(1)
    peaks = device.peaks_for(devices[0].device_kind,
                             os.path.join(ROOT, "benchmark"))
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[args.dtype]
    shape = tuple(int(x) for x in args.shape.split(","))
    inputs = ssm_check.make_inputs(shape, args.states, dtype, args.seed)
    want = ssm_check.out_and_gradients(ssm_check.sequential, inputs) \
        if args.check else None
    trace_root = os.path.join(ROOT, ".bench_trace", "ssm_bench")
    for block_d, chunk in itertools.product(
            (int(x) for x in args.block_d.split(",")),
            (int(x) for x in args.chunk.split(","))):
        try:
            record = time_variant(block_d, chunk, inputs, want, args, peaks,
                                  trace_root)
        except Exception as e:  # noqa: BLE001 — one refused variant must
            # not cost the call that times the others
            record = {"block_d": block_d, "chunk": chunk,
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
