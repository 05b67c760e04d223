"""Time the scalar-decay delta-rule kernels alone, on the chip.

``ops/pallas/gdn_attention.py`` at the Qwen3-Next cell's ``[1, 8192, 16 | 32
x 128]`` (``q, k`` 16 key heads, ``v`` 32 value heads of 128) in bf16 with
float32 log decays and ``beta`` ``[1, 8192, 32]``: device microseconds a call
of ``tepdist_gdn_fwd`` (under differentiation it also writes the states
before every chunk and the chunks' inverses, which the backward kernel reads)
and of ``tepdist_gdn_bwd``, for each chunk asked for, from one
``jax.profiler`` trace a variant reduced by ``benchmark/trace_reduce.py``,
each beside its roofline time (``benchmark/kernels/gdn_cost.py``); **the
same operands through ``tepdist_kda_*``** (``ops/pallas/kda_attention.py``
with ``g`` broadcast over a head's 128 channels and ``q, k`` repeated to 32
heads: what says the two rules are one), timed beside; and the relative L2
distance of the output and the five gradients (asked for in float32) from
the token-by-token float32 recurrence of ``benchmark/reference/qwen3_next.py``,
from the chunked ``jax.numpy`` form (``gdn_attention.chunked``) and from that
broadcast call. ``--state-dtype bf16`` reads the same with the carried state
rounded to bf16 (a control: what a narrower carry costs). ``--impl`` times
another copy of ``gdn_attention.py`` in the same process as
``tools/kda_bench.py`` does (a file, or a directory that holds it, a
``_delta_rule.py`` or both: ``kda_bench.load_impl``), prints its six
distances from the recurrence too and says whether its output and five
gradients are the checkout's bit for bit.

Operands as a Gated-DeltaNet layer hands them over: ``q`` and ``k`` unit L2
norm a key head, ``q`` over ``sqrt(K)``, ``v`` a unit-variance projection,
``g = -exp(A) softplus(.)`` over the initialisation's range (``A = log U(1,
16)`` a value head, the softplus ``exp(U(log 1e-3, log 1e-1))`` a token and
head, times ``--decay-scale``), ``beta`` a sigmoid of a unit normal.

The kernels are found as the benchmark finds them
(``benchmark/layer_metrics/_gdn.py``, ``_kda.py``). No benchmark cell runs
this; there is no CPU fallback: without a TPU it exits 2.

Run: chiprun -- python tools/gdn_bench.py [--tokens 8192] [--chunk 64,128]
     [--state-dtype f32] [--decay-scale 1] [--check 1] [--kda 1]
     [--impl parent=path/to/gdn_attention.py | a directory that holds it]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NAMES = ("out", "dq", "dk", "dv", "dg", "dbeta")


def make_inputs(T: int, Hk: int, Hv: int, K: int, dtype, seed: int,
                decay_scale=1.0):
    """``q, k`` ``[1, T, Hk * K]``, ``v, do`` ``[1, T, Hv * K]``, ``g, beta``
    float32 ``[1, T, Hv]``, in the order ``q, k, v, g, beta, do``."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed % 2 ** 31), 7)
    f32 = jnp.float32

    def unit(k):
        x = jax.random.normal(k, (1, T, Hk, K), f32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))

    def wide(k):
        return jax.random.normal(k, (1, T, Hv * K), f32).astype(dtype)

    rate = jax.random.uniform(ks[3], (Hv,), f32, 1.0, 16.0)
    step = jnp.exp(jax.random.uniform(ks[4], (1, T, Hv), f32,
                                      jnp.log(1e-3), jnp.log(1e-1)))
    return ((unit(ks[0]) * K ** -0.5).reshape(1, T, Hk * K).astype(dtype),
            unit(ks[1]).reshape(1, T, Hk * K).astype(dtype), wide(ks[2]),
            -decay_scale * rate * step,
            jax.nn.sigmoid(jax.random.normal(ks[5], (1, T, Hv), f32)),
            wide(ks[6]))


def broadcast(kda_attention, Hk: int, chunk: int):
    """``(q, k, v, g, beta) -> o`` through the per-channel kernels: ``q, k``
    repeated to the value heads, ``g`` spread over a head's channels."""
    import jax.numpy as jnp

    def call(q, k, v, g, beta, **how):
        B, T, _ = q.shape
        Hv = beta.shape[2]
        K = v.shape[2] // Hv

        def repeated(x):
            return jnp.repeat(x.reshape(B, T, Hk, K), Hv // Hk,
                              axis=2).reshape(B, T, Hv * K)

        return kda_attention(repeated(q), repeated(k), v,
                             jnp.repeat(g, K, axis=-1), beta, chunk=chunk,
                             **how)
    return call


def _kernels(summary, is_mine, parse, cost, roofline, peaks):
    from benchmark import trace_reduce
    out = {}
    for text, secs, calls in summary.ops(is_mine):
        parsed = parse(text)
        name = trace_reduce.short_name(text)
        if parsed is None:
            out[name] = {"unparsed": text[:300]}
            continue
        least = roofline(cost(parsed), peaks)
        out[name] = {"calls": calls, "us_per_call": 1e6 * secs / calls,
                     "roofline_us": 1e6 * least["seconds"],
                     "bound": least["bound"],
                     "roofline_share_pct":
                         100.0 * least["seconds"] * calls / secs}
    return out


def variants(args, peaks, trace_root, impls):
    """``impls``: ``(label, module)`` of each copy of ``gdn_attention.py``
    to time, the checkout's own first."""
    import jax
    import jax.numpy as jnp

    from benchmark.kernels.ssm_check import rel_l2
    from benchmark.reference import qwen3_next as ref
    from tools.kda_bench import _out_and_gradients

    Hk, Hv, K, T = args.key_heads, args.value_heads, args.head_dim, \
        args.tokens
    inputs = make_inputs(T, Hk, Hv, K, jnp.bfloat16, args.seed,
                         args.decay_scale)
    want = chunked = None
    if args.check:
        gdn = impls[0][1]
        with jax.default_matmul_precision("highest"):
            want = _out_and_gradients(
                lambda q, k, v, g, b: ref.recurrence(
                    q[0].reshape(T, Hk, K), k[0].reshape(T, Hk, K),
                    v[0].reshape(T, Hv, K), g[0], b[0]).reshape(
                        1, T, Hv * K), inputs)
            chunked = _out_and_gradients(
                lambda *a: gdn.chunked(*a, chunk=64), inputs)
        yield {"what": "chunked jax.numpy form against the recurrence",
               "rel_l2": {n: rel_l2(c, w)
                          for n, c, w in zip(NAMES, chunked, want)}}
    for chunk in (int(c) for c in args.chunk.split(",")):
        first = None
        for label, module in impls:
            record, got = _time_impl(label, module, chunk, inputs, want,
                                     chunked, first, args, peaks, trace_root)
            first = got if first is None else first
            yield record


def _grad_of(fn):
    import jax

    @jax.jit
    def grad(q, k, v, g, beta, do):
        out, vjp = jax.vjp(fn, q, k, v, g, beta)
        return (out,) + vjp(do)
    return grad


def _time_impl(label, gdn, chunk, inputs, want, chunked, first, args, peaks,
               trace_root):
    """One copy's kernels at one chunk: ``(record, the output and five
    gradients of its differentiated call)``. ``want``, ``chunked``: the two
    float32 references' results, or None; ``first``: the results of the
    first copy timed at this chunk, or None (this copy is the first: the
    broadcast call of ``tepdist_kda_*`` is timed beside it)."""
    import jax
    import jax.numpy as jnp

    from benchmark.kernels import gdn_cost, kda_cost
    from benchmark.kernels.ssm_check import rel_l2
    from benchmark.layer_metrics import _gdn, _kda
    from tepdist_tpu.ops.pallas.kda_attention import kda_attention
    from tools.sala_bench import _traced

    record = {"what": "gdn", "impl": label, "chunk": chunk,
              "tokens": args.tokens, "key_heads": args.key_heads,
              "value_heads": args.value_heads,
              "state_dtype": args.state_dtype, "iters": args.iters,
              "decay_scale": args.decay_scale}
    got = None
    try:
        if want is not None:
            how = dict(chunk=chunk, out_dtype=jnp.float32, state_dtype={
                "f32": None, "bf16": jnp.bfloat16}[args.state_dtype])
            alone = jax.block_until_ready(jax.jit(
                lambda *x: (gdn.forward(*x[:5], **how),)
                + gdn.backward(*x, **how))(*inputs))
            record["rel_l2_vs_recurrence_f32"] = {
                n: rel_l2(a, w) for n, a, w in zip(NAMES, alone, want)}
            record["rel_l2_vs_chunked_f32"] = {
                n: rel_l2(a, w) for n, a, w in zip(NAMES, alone, chunked)}
        grad = _grad_of(lambda *a: gdn.gdn_attention(*a, chunk=chunk))
        got = jax.block_until_ready(grad(*inputs))
        if first is not None:
            record["same_bits_as_first"] = {
                n: bool(jnp.array_equal(a, f))
                for n, a, f in zip(NAMES, got, first)}
        summary = _traced(f"gdn-{label}-{chunk}", lambda: grad(*inputs),
                          args.iters, trace_root)
        record["kernels"] = _kernels(
            summary, _gdn.is_gdn, _gdn.parse, _gdn.call_cost,
            gdn_cost.roofline_seconds, peaks)
        record["other_device_us_per_iter"] = 1e6 * sum(
            s for _, s, _ in summary.ops(
                lambda t: not _gdn.is_gdn(t))) / args.iters
        if args.kda and first is None:
            wide = _grad_of(broadcast(kda_attention, args.key_heads, chunk))
            through = jax.block_until_ready(wide(*inputs))
            record["rel_l2_vs_broadcast_kda_bf16"] = {
                n: rel_l2(a, w) for n, a, w in zip(NAMES, got, through)}
            summary = _traced(f"kda-{chunk}", lambda: wide(*inputs),
                              args.iters, trace_root)
            record["broadcast_kda_kernels"] = _kernels(
                summary, _kda.is_kda, _kda.parse, _kda.call_cost,
                kda_cost.roofline_seconds, peaks)
            record["broadcast_other_device_us_per_iter"] = 1e6 * sum(
                s for _, s, _ in summary.ops(
                    lambda t: not _kda.is_kda(t))) / args.iters
    except Exception as e:  # noqa: BLE001 — one refused variant must not
        # cost the call that times the others
        record["error"] = repr(e)[:2000]
    return record, got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--key-heads", type=int, default=16)
    ap.add_argument("--value-heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--chunk", default="64", help="chunks, a comma between "
                    "them")
    ap.add_argument("--state-dtype", default="f32", choices=("f32", "bf16"))
    ap.add_argument("--decay-scale", type=float, default=1.0,
                    help="multiplies every log decay")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--check", type=int, default=1,
                    help="0 skips the float32 references")
    ap.add_argument("--kda", type=int, default=1,
                    help="0 skips the broadcast call of tepdist_kda_*")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", action="append", default=[],
                    metavar="LABEL=PATH",
                    help="another gdn_attention.py, or a directory that "
                         "holds one or a _delta_rule.py or both, to time "
                         "beside the checkout's own and compare with it bit "
                         "for bit (repeatable)")
    ap.add_argument("--out", default=None, help="also write the records "
                    "as JSON lines to this file")
    args = ap.parse_args(argv)

    from benchmark.lib import device
    from tools.kda_bench import load_impl

    devices = device.own_chips(1)
    peaks = device.peaks_for(devices[0].device_kind,
                             os.path.join(ROOT, "benchmark"))
    trace_root = os.path.join(ROOT, ".bench_trace", "gdn_bench")
    impls = [("tree", os.path.join(ROOT, "tepdist_tpu", "ops", "pallas"))]
    impls += [tuple(item.partition("=")[::2]) for item in args.impl]
    impls = [(label, load_impl(label, path, "gdn_attention.py"))
             for label, path in impls]
    for record in variants(args, peaks, trace_root, impls):
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
