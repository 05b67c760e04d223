"""Time the Kimi Delta Attention kernels alone, on the chip.

``ops/pallas/kda_attention.py`` at the Kimi Linear cell's ``[1, 8192, 32 x
128]`` in bf16 with float32 log decays and ``beta``: device microseconds a
call of the forward kernel (under differentiation it also writes the states
before every chunk and the chunks' inverses, which the backward kernel
reads) and of the backward kernel, for each chunk asked for, from one
``jax.profiler`` trace a variant reduced by ``benchmark/trace_reduce.py``,
each beside its roofline time (``benchmark/kernels/kda_cost.py``); the
relative L2 distance of the output and the five gradients (asked for in
float32) from the token-by-token float32 recurrence of
``benchmark/reference/kimi_linear.py`` and from the chunked ``jax.numpy`` form
(``kda_attention.chunked``, which is also timed, forward and backward, as
what the kernels replace); ``--state-dtype bf16`` reads the same with the
carried state rounded to bf16 (a control: what a narrower carry costs).
``--impl`` times another copy of ``kda_attention.py`` in the same process,
beside the checkout's own (a parent's), prints its six distances from the
recurrence too and says whether its output and five gradients are the
checkout's bit for bit. A ``_delta_rule.py`` beside the copy is the copy's
(:func:`load_impl`), so ``--impl parent=dir/`` times a parent's kernel file
on the parent's inverse, and a directory that holds a ``_delta_rule.py``
alone the checkout's kernel file on that one (PR 55: the lockstep with four
passes a product beside the three).

Operands as a KDA layer hands them over: ``q`` and ``k`` unit L2 norm a
head, ``q`` over ``sqrt(K)``, ``v`` a unit-variance projection, ``g = -exp(A)
softplus(.)`` over the initialisation's range (``A = log U(1, 16)`` a head,
the softplus ``exp(U(log 1e-3, log 1e-1))`` a channel and token, times
``--decay-scale``), ``beta`` a sigmoid of a unit normal.

The kernels are found as the benchmark finds them
(``benchmark/layer_metrics/_kda.py``). No benchmark cell runs this; there is
no CPU fallback: without a TPU it exits 2.

Run: chiprun -- python tools/kda_bench.py [--tokens 8192] [--chunk 64,128]
     [--state-dtype f32] [--decay-scale 1] [--check 1]
     [--impl parent=path/to/kda_attention.py | a directory that holds it]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NAMES = ("out", "dq", "dk", "dv", "dg", "dbeta")


def load_impl(label: str, path: str, kernel: str = "kda_attention.py"):
    """A copy of the kernel file ``kernel`` as a module of its own: ``path``
    the file or a directory that holds it (one without it: the checkout's
    file). A ``_delta_rule.py`` in that directory stands in the copy's
    imports where the checkout's would."""
    import importlib

    from tools.flash_bench import load_impl as load_file
    shared = "tepdist_tpu.ops.pallas._delta_rule"
    mine = importlib.import_module(shared)
    folder = path if os.path.isdir(path) else os.path.dirname(path)
    file = os.path.join(path, kernel) if os.path.isdir(path) else path
    if not os.path.exists(file):
        file = os.path.join(os.path.dirname(mine.__file__), kernel)
    beside = os.path.join(folder, "_delta_rule.py")
    try:
        if os.path.exists(beside) \
                and not os.path.samefile(beside, mine.__file__):
            sys.modules[shared] = load_file(label + "_delta_rule", beside)
        return load_file(label + "_" + kernel[:-3], file)
    finally:
        sys.modules[shared] = mine


def make_inputs(T: int, H: int, K: int, dtype, seed: int, decay_scale=1.0):
    """``q, k, v, g, beta, do``: ``[1, T, H * K]`` but ``beta`` ``[1, T,
    H]``."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed % 2 ** 31), 7)
    f32 = jnp.float32

    def unit(k):
        x = jax.random.normal(k, (1, T, H, K), f32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))

    def flat(x):
        return x.reshape(1, T, H * K)

    rate = jax.random.uniform(ks[3], (H, 1), f32, 1.0, 16.0)
    step = jnp.exp(jax.random.uniform(ks[4], (1, T, H, K), f32,
                                      jnp.log(1e-3), jnp.log(1e-1)))
    return (flat(unit(ks[0]) * K ** -0.5).astype(dtype),
            flat(unit(ks[1])).astype(dtype),
            flat(jax.random.normal(ks[2], (1, T, H, K), f32)).astype(dtype),
            flat(-decay_scale * rate * step),
            jax.nn.sigmoid(jax.random.normal(ks[5], (1, T, H), f32)),
            flat(jax.random.normal(ks[6], (1, T, H, K), f32)).astype(dtype))


def _out_and_gradients(fn, inputs):
    """``fn(q, k, v, g, beta) -> o`` and its five gradients under ``do``,
    everything float32."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(*x):
        out, vjp = jax.vjp(fn, *x[:5])
        return (out,) + vjp(x[5])
    return jax.block_until_ready(run(*(x.astype(jnp.float32)
                                       for x in inputs)))


def variants(args, peaks, trace_root, impls):
    """``impls``: ``(label, module)`` of each copy of ``kda_attention.py`` to
    time, the checkout's own first."""
    import jax
    import jax.numpy as jnp

    from benchmark.kernels.ssm_check import rel_l2
    from benchmark.reference import kimi_linear as ref
    from tools.sala_bench import _traced

    H, K, T = args.heads, args.head_dim, args.tokens
    inputs = make_inputs(T, H, K, jnp.bfloat16, args.seed, args.decay_scale)
    want = chunked = None
    kda = impls[0][1]
    if args.check:
        def heads(x):
            return x[0].reshape(T, H, -1)

        with jax.default_matmul_precision("highest"):
            want = _out_and_gradients(
                lambda q, k, v, g, b: ref.recurrence(
                    heads(q), heads(k), heads(v), heads(g), b[0]).reshape(
                        1, T, H * K), inputs)
            chunked = _out_and_gradients(
                lambda *a: kda.chunked(*a, chunk=64), inputs)
        yield {"what": "chunked jax.numpy form against the recurrence",
               "rel_l2": {n: rel_l2(c, w)
                          for n, c, w in zip(NAMES, chunked, want)}}

        @jax.jit
        def plain(q, k, v, g, beta, do):
            out, vjp = jax.vjp(lambda *a: kda.chunked(*a, chunk=64),
                               q, k, v, g, beta)
            return (out,) + vjp(do)
        try:
            summary = _traced("chunked", lambda: plain(*inputs), args.iters,
                              trace_root)
            yield {"what": "chunked jax.numpy form, forward and backward",
                   "device_us_per_iter": 1e6 * sum(
                       s for _, s, _ in summary.ops(lambda t: True))
                   / args.iters}
        except Exception as e:  # noqa: BLE001 — the kernels are still timed
            yield {"what": "chunked jax.numpy form, forward and backward",
                   "error": repr(e)[:2000]}
    for chunk in (int(c) for c in args.chunk.split(",")):
        first = None
        for label, module in impls:
            record, got = _time_impl(label, module, chunk, inputs, want,
                                     chunked, first, args, peaks, trace_root)
            first = got if first is None else first
            yield record


def _time_impl(label, kda, chunk, inputs, want, chunked, first, args, peaks,
               trace_root):
    """One copy's kernels at one chunk: ``(record, the output and five
    gradients of its differentiated call)``. ``want``, ``chunked``: the two
    float32 references' results, or None; ``first``: the results of the
    first copy timed at this chunk, or None."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce
    from benchmark.kernels import kda_cost
    from benchmark.kernels.ssm_check import rel_l2
    from benchmark.layer_metrics import _kda
    from benchmark.layer_metrics._sala import _operands
    from tools.sala_bench import _traced

    record = {"what": "kda", "impl": label, "chunk": chunk,
              "tokens": args.tokens, "heads": args.heads,
              "state_dtype": args.state_dtype, "iters": args.iters,
              "decay_scale": args.decay_scale}
    got = None
    try:
        if want is not None:
            how = dict(chunk=chunk, out_dtype=jnp.float32, state_dtype={
                "f32": None, "bf16": jnp.bfloat16}[args.state_dtype])
            alone = jax.block_until_ready(jax.jit(
                lambda *x: (kda.forward(*x[:5], **how),)
                + kda.backward(*x, **how))(*inputs))
            record["rel_l2_vs_recurrence_f32"] = {
                n: rel_l2(a, w) for n, a, w in zip(NAMES, alone, want)}
            record["rel_l2_vs_chunked_f32"] = {
                n: rel_l2(a, w) for n, a, w in zip(NAMES, alone, chunked)}

        @jax.jit
        def grad(q, k, v, g, beta, do):
            out, vjp = jax.vjp(lambda *a: kda.kda_attention(
                *a, chunk=chunk), q, k, v, g, beta)
            return (out,) + vjp(do)

        got = jax.block_until_ready(grad(*inputs))
        if first is not None:
            record["same_bits_as_first"] = {
                n: bool(jnp.array_equal(a, f))
                for n, a, f in zip(NAMES, got, first)}
        summary = _traced(f"kda-{label}-{chunk}", lambda: grad(*inputs),
                          args.iters, trace_root)
        kernels = {}
        for text, secs, calls in summary.ops(_kda.is_kda):
            parsed = _kda.parse(text)
            name = trace_reduce.short_name(text)
            if parsed is None:
                kernels[name] = {"unparsed": text[:300]}
                continue
            least = kda_cost.roofline_seconds(_kda.call_cost(parsed), peaks)
            # Operands and results say which kernel of the two halves this
            # is: a forward of 2 results writes the states alone, of 3 the
            # inverses too; a backward of 8 operands reads them.
            kernels[name] = {
                "calls": calls, "us_per_call": 1e6 * secs / calls,
                "results": text.partition(" custom-call(")[0].count("["),
                "operands": len(_operands(text)),
                "roofline_us": 1e6 * least["seconds"],
                "bound": least["bound"],
                "roofline_share_pct":
                    100.0 * least["seconds"] * calls / secs}
        record["kernels"] = kernels
        record["other_device_us_per_iter"] = 1e6 * sum(
            s for _, s, _ in summary.ops(
                lambda t: not _kda.is_kda(t))) / args.iters
    except Exception as e:  # noqa: BLE001 — one refused variant must not
        # cost the call that times the others
        record["error"] = repr(e)[:2000]
    return record, got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--chunk", default="64", help="chunks, a comma between "
                    "them")
    ap.add_argument("--state-dtype", default="f32", choices=("f32", "bf16"))
    ap.add_argument("--decay-scale", type=float, default=1.0,
                    help="multiplies every log decay (3: down to -4.8 a "
                    "token)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--check", type=int, default=1,
                    help="0 skips the float32 references")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", action="append", default=[],
                    metavar="LABEL=PATH",
                    help="another kda_attention.py, or a directory that "
                         "holds one or a _delta_rule.py or both, to time "
                         "beside the checkout's own and compare with it bit "
                         "for bit (repeatable)")
    ap.add_argument("--out", default=None, help="also write the records "
                    "as JSON lines to this file")
    args = ap.parse_args(argv)

    from benchmark.lib import device
    devices = device.own_chips(1)
    peaks = device.peaks_for(devices[0].device_kind,
                             os.path.join(ROOT, "benchmark"))
    trace_root = os.path.join(ROOT, ".bench_trace", "kda_bench")
    impls = [("tree", os.path.join(ROOT, "tepdist_tpu", "ops", "pallas",
                                   "kda_attention.py"))]
    impls += [tuple(item.partition("=")[::2]) for item in args.impl]
    impls = [(label, load_impl(label, path)) for label, path in impls]
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
