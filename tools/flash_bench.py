"""Time the flash attention kernels alone, on the chip.

Shapes, dtype and tiles in; device microseconds a call of the forward and of
the backward pass out (one kernel since PR 46; the dQ and the dK/dV kernel
of an older copy, each and summed), from one ``jax.profiler`` trace a shape
and copy reduced by ``benchmark/trace_reduce.py``, each beside its roofline
time (``benchmark/kernels/flash_cost.py``: ``forward`` and ``backward``, the
whole pass's five matmuls a pair; under a window or with fewer key/value
heads ``window_flash_cost.py``'s pairs and bytes; ``benchmark/peaks.json``),
and the relative L2 distance of o, dq, dk, dv from dense float32 attention
of the same inputs. The kernels are found as the benchmark finds them, by
name (``benchmark/layer_metrics/_window_flash.py:NAME``), so a call this
tool cannot read is a call the benchmark's attention metrics cannot read
either.

No benchmark cell runs this; it is for work on the kernels. There is no CPU
fallback: without a TPU it exits 2. ``--impl`` times another copy of
``flash_attention.py`` (the parent commit's, say) in the same process, so
that two versions are read on one chip in one call, and says whether its o,
dq, dk, dv are the first copy's bit for bit.

Run: chiprun -- python tools/flash_bench.py [--shape 3,25,1024,64]
     [--shape 1,32,16384,128/4/1024 --check 0] [--dtype bf16] [--block 512]
     [--impl old=path/to/flash_attention.py]
A shape is B,H,T,D[/key-value heads[/window]]; several ``--shape`` run one
after another.
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
    """The file at ``path`` as a module of its own, known to ``sys.modules``
    (a ``dataclass`` in it looks its module up there)."""
    name = "flash_impl_" + label
    spec = importlib.util.spec_from_file_location(name, path)
    module = sys.modules[name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def dense_reference(q, k, v, do, causal: bool, window=None):
    """o, dq, dk, dv of plain float32 attention, every matmul at the
    highest precision; k and v at their own head count."""
    import jax
    import jax.numpy as jnp

    group = q.shape[1] // k.shape[1]

    def attend(q, k, v):
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest")
        s = s / jnp.sqrt(jnp.float32(q.shape[-1]))
        if causal:
            T = q.shape[2]
            ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
            seen = ahead >= 0
            if window is not None:
                seen &= ahead < window
            s = jnp.where(seen, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")

    q, k, v, do = (x.astype(jnp.float32) for x in (q, k, v, do))
    o, vjp = jax.vjp(attend, q, k, v)
    return (o,) + vjp(do)


def rel_l2(got, want) -> float:
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def parse_shape(text: str):
    """``B,H,T,D[/key-value heads[/window]]`` -> ((B, H, T, D), kv, window)."""
    dims, *more = text.split("/")
    shape = tuple(int(x) for x in dims.split(","))
    kv = int(more[0]) if more and more[0] else shape[1]
    return shape, kv, int(more[1]) if len(more) > 1 else None


def costs(shape, kv, window, dtype_bytes, causal):
    """Operations and bytes of the forward and of the whole backward pass
    (``flash_cost.py``'s; under a window or grouped heads its counts with
    ``window_flash_cost.py``'s pairs and head counts)."""
    from benchmark.kernels import flash_cost, window_flash_cost as wfc
    B, H, T, D = shape
    if window is None and kv == H:
        return {"forward": flash_cost.forward(shape, dtype_bytes, causal),
                "backward": flash_cost.backward(shape, dtype_bytes, causal)}
    parts = [getattr(wfc, kind)(shape, dtype_bytes, causal, window, kv)
             for kind in ("backward_dq", "backward_dkv")]
    q_io, kv_io = B * H * T * D * dtype_bytes, B * kv * T * D * dtype_bytes
    return {"forward": wfc.forward(shape, dtype_bytes, causal, window, kv),
            # q, o (through delta) and dO in, dq out; k, v in, dk, dv out.
            "backward": {"ops": sum(p["ops"] for p in parts),
                         "bytes": 4.0 * q_io + 4.0 * kv_io
                         + 2.0 * B * H * T * 4.0}}


def time_impl(label, module, case, args, peaks, trace_root, first):
    """One traced window of ``args.iters`` gradient calls of one shape (each
    runs the forward and the backward pass once). ``first``: the results of
    the first copy timed at this shape, or None."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce
    from benchmark.kernels import flash_cost
    from benchmark.layer_metrics import _window_flash
    from benchmark.lib import tracing

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[args.dtype]
    shape, kv, window = case
    kv_shape = (shape[0], kv) + shape[2:]
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    q, k, v, do = (jax.random.normal(kk, s, jnp.float32).astype(dtype)
                   for kk, s in zip(keys, (shape, kv_shape, kv_shape, shape)))
    causal = bool(args.causal)
    block_q, block_k = args.block_q or args.block, args.block_k or args.block

    def attend(q, k, v):
        return module.flash_attention(q, k, v, causal=causal, block_q=block_q,
                                      block_k=block_k, interpret=False,
                                      window=window)

    @jax.jit
    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(attend, q, k, v)
        return (o,) + vjp(do)

    got = jax.block_until_ready(fwd_bwd(q, k, v, do))   # compiles
    names = ("o", "dq", "dk", "dv")
    errors = None
    if args.check:
        want = dense_reference(q, k, v, do, causal, window)
        errors = {n: rel_l2(g, w) for n, g, w in zip(names, got, want)}
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

    record = {"impl": label, "shape": list(shape), "kv_heads": kv,
              "window": window, "dtype": args.dtype,
              "causal": causal, "block_q": block_q, "block_k": block_k,
              "iters": args.iters,
              "rel_l2_vs_dense_f32": errors, "kernels": {}}
    if first is not None:
        record["same_bits_as_first"] = {
            n: bool(jnp.array_equal(g, f))
            for n, g, f in zip(names, got, first)}
    cost = costs(shape, kv, window, jnp.dtype(dtype).itemsize, causal)
    passes = {}
    for text, secs, calls in summary.ops(_window_flash.is_attention):
        which = _window_flash.NAME.search(
            trace_reduce.short_name(text)).group(1)
        results = text.partition(" custom-call(")[0].count("[")
        record["kernels"][which] = {
            "calls": calls, "us_per_call": 1e6 * secs / calls,
            "results": results, "name": trace_reduce.short_name(text)}
        kind = "forward" if which == "fwd" else "backward"
        passes[kind] = passes.get(kind, 0.0) + secs / calls
    for kind, secs in passes.items():
        least = flash_cost.roofline_seconds(cost[kind], peaks)
        record[kind] = {
            "us_per_call": 1e6 * secs,
            "roofline_us": 1e6 * least["seconds"], "bound": least["bound"],
            "roofline_share_pct": 100.0 * least["seconds"] / secs}
    # Everything else the gradient call runs on the device (the XLA fusion
    # that makes ``delta``, the sum over a group's heads, layout copies):
    # kernel-alone time hides it.
    others = sorted(
        summary.ops(lambda t: not _window_flash.is_attention(t)),
        key=lambda op: -op[1])
    record["other_device_us_per_iter"] = \
        1e6 * sum(s for _, s, _ in others) / args.iters
    record["other_ops"] = [[text[:160], 1e6 * s / args.iters]
                           for text, s, _ in others[:4]]
    return record, got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append", default=[],
                    metavar="B,H,T,D[/KV[/WINDOW]]",
                    help="repeatable; default 3,25,1024,64")
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
                         "checkout's own and compare with it bit for bit "
                         "(repeatable)")
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
    modules = [(label, load_impl(label, path)) for label, path in impls]
    for case in map(parse_shape, args.shape or ["3,25,1024,64"]):
        first = None
        for label, module in modules:
            try:
                record, got = time_impl(label, module, case, args, peaks,
                                        trace_root, first)
                first = got if first is None else first
                del got
            except Exception as e:  # noqa: BLE001 — one refused variant
                # must not cost the call that times the others
                record = {"impl": label, "shape": list(case[0]),
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
