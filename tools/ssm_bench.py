"""Time the selective-scan and the causal-conv kernels alone, on the chip.

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

After the scan's rows, for each time and channel block asked for, the conv
before the scan (``ops/pallas/causal_conv.py``: ``tepdist_conv_fwd`` /
``tepdist_conv_bwd``) at the same ``[batch, T, Di]``: device microseconds a
call, each kernel's share of its HBM bound (``u`` in and ``c`` out; ``u``,
``dc`` in and ``du`` out), the whole of the ``jax.numpy`` form's forward and
gradient beside them, and the distance of the output and the three gradients
from that form.

No benchmark cell runs this; it is for work on the kernels. There is no CPU
fallback: without a TPU it exits 2. A variant the compiler refuses is
reported and the others still run.

Run: chiprun -- python tools/ssm_bench.py [--shape 1,8192,5120] [--states 16]
     [--dtype bf16] [--block-d 256,512,1024] [--chunk 64,128]
     [--conv-block-t 1024,2048] [--conv-block-d 256,512]
"""

from __future__ import annotations

import argparse
import functools
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


CONV_NAMES = ("c", "du", "dw", "db")


def time_conv(variants, shape, dtype, args, peaks, trace_root):
    """One record a ``(block_t, block_d)`` of ``variants``, and first one of
    the ``jax.numpy`` form."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce
    from benchmark.kernels import ssm_check
    from benchmark.kernels.flash_cost import roofline_seconds
    from benchmark.lib import tracing
    from tepdist_tpu.ops.pallas import causal_conv as conv

    def is_conv(text):
        return "tepdist_conv_" in trace_reduce.short_name(text)

    Bn, T, Di = shape
    ks = jax.random.split(jax.random.PRNGKey(args.seed % 2 ** 31), 4)
    u, dc = (jax.random.normal(k, shape, jnp.float32).astype(dtype)
             for k in ks[:2])
    w = (0.5 * jax.random.normal(ks[2], (4, Di), jnp.float32)).astype(dtype)
    b = (0.1 * jax.random.normal(ks[3], (Di,), jnp.float32)).astype(dtype)
    size = jnp.dtype(dtype).itemsize
    least = {"tepdist_conv_fwd": roofline_seconds(
        {"ops": conv.FWD_FLOPS * u.size, "bytes": 2.0 * u.size * size},
        peaks), "tepdist_conv_bwd": roofline_seconds(
        {"ops": conv.BWD_FLOPS * u.size, "bytes": 3.0 * u.size * size},
        peaks)}

    def both(fn):
        @jax.jit
        def fwd_bwd(u, w, b):
            out, vjp = jax.vjp(fn, u, w, b)
            return (out,) + vjp(dc)
        return jax.jit(fn), fwd_bwd

    def traced(label, run):
        path = os.path.join(trace_root, label)
        tracing.discard(path)
        jax.profiler.start_trace(path)
        for _ in range(args.iters):
            out = run(u, w, b)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        summary = tracing.reduce_trace(path)
        tracing.discard(path)
        return summary

    plain, grad = both(conv.reference)
    want = jax.block_until_ready(grad(u, w, b))
    jax.block_until_ready(plain(u, w, b))
    yield {"conv": "jax.numpy", "shape": list(shape), "dtype": args.dtype,
           "forward_us": 1e6 * traced("conv-ref-f", plain).op_seconds(
               lambda t: True) / args.iters,
           "forward_and_gradient_us": 1e6 * traced(
               "conv-ref-g", grad).op_seconds(lambda t: True) / args.iters}
    for block_t, block_d in variants:
        record = {"conv": "kernels", "block_t": block_t, "block_d": block_d,
                  "shape": list(shape), "dtype": args.dtype, "kernels": {}}
        try:
            plain, grad = both(functools.partial(
                conv.causal_conv, block_t=block_t, block_d=block_d,
                interpret=False))
            got = jax.block_until_ready(grad(u, w, b))
            jax.block_until_ready(plain(u, w, b))
            record["rel_l2_vs_jax_numpy"] = {
                n: ssm_check.rel_l2(g, x)
                for n, g, x in zip(CONV_NAMES, got, want)}
            del got
            for label, run in (("forward_alone", plain), ("grad", grad)):
                summary = traced(f"conv-{label}-{block_t}-{block_d}", run)
                for text, secs, calls in summary.ops(is_conv):
                    name = next(n for n in least
                                if n in trace_reduce.short_name(text))
                    record["kernels"][
                        label if label == "forward_alone" else name] = {
                        "calls": calls, "us_per_call": 1e6 * secs / calls,
                        "hbm_bound_us": 1e6 * least[name]["seconds"],
                        "hbm_bound_share_pct":
                            100.0 * least[name]["seconds"] * calls / secs}
                if label == "grad":
                    record["other_device_us_per_iter"] = 1e6 * \
                        summary.op_seconds(
                            lambda t: not is_conv(t)) / args.iters
        except Exception as e:  # noqa: BLE001 — as for the scan's variants
            record["error"] = repr(e)[:2000]
        yield record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="1,8192,5120", help="batch,T,Di")
    ap.add_argument("--states", type=int, default=16)
    ap.add_argument("--dtype", default="bf16", choices=("bf16", "f32"))
    ap.add_argument("--block-d", default="1024", help="channel blocks, a "
                    "comma between them")
    ap.add_argument("--chunk", default="64", help="chunks, likewise")
    ap.add_argument("--conv-block-t", default="2048", help="the conv's time "
                    "blocks, likewise; none skips the conv")
    ap.add_argument("--conv-block-d", default="256", help="and its channel "
                    "blocks")
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
    trace_root = os.path.join(ROOT, ".bench_trace", "ssm_bench")

    def ints(text):
        return [int(x) for x in text.split(",") if x and x != "none"]

    def scans():
        variants = list(itertools.product(ints(args.block_d),
                                          ints(args.chunk)))
        if not variants:
            return
        inputs = ssm_check.make_inputs(shape, args.states, dtype, args.seed)
        want = ssm_check.out_and_gradients(ssm_check.sequential, inputs) \
            if args.check else None
        for block_d, chunk in variants:
            try:
                yield time_variant(block_d, chunk, inputs, want, args, peaks,
                                   trace_root)
            except Exception as e:  # noqa: BLE001 — one refused variant
                # must not cost the call that times the others
                yield {"block_d": block_d, "chunk": chunk,
                       "error": repr(e)[:2000]}

    convs = list(itertools.product(ints(args.conv_block_t),
                                   ints(args.conv_block_d)))
    for record in itertools.chain(scans(), time_conv(
            convs, shape, dtype, args, peaks, trace_root) if convs else ()):
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
