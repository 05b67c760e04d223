"""Time the state-space-dual kernels alone, on the chip.

``ops/pallas/ssd_attention.py`` at the Nemotron-3-Nano cell's ``[1, 8192, 64
x 64]`` (``u`` 64 heads of 64 channels, ``B, C`` 8 groups of 128 states) in
bf16 with float32 steps ``Delta`` ``[1, 8192, 64]``: device microseconds a
call of ``tepdist_ssd_fwd`` (under differentiation it also writes the states
before every chunk, which the backward kernel reads) and of
``tepdist_ssd_bwd``, for each chunk asked for, from one ``jax.profiler`` trace
a chunk reduced by ``benchmark/trace_reduce.py``, each beside its roofline
time (``benchmark/kernels/ssd_cost.py``); the chunked ``jax.numpy`` form
(``ssd_attention.chunked``) timed beside them; and the relative L2 distance
of the output and the six gradients (asked for in float32) from the
token-by-token float32 recurrence of ``benchmark/reference/nemotron_h.py``
and from that chunked form. ``--state-dtype bf16`` reads the same with the
carried state rounded to bf16 (a control: what a narrower carry costs).

Operands as a Mamba-2 layer hands them over: ``u``, ``B``, ``C`` a silu of a
unit normal (what the conv leaves), ``Delta`` a softplus over the
initialisation's range (``exp(U(log 1e-3, log 1e-1))`` a token and head,
times ``--step-scale``), ``A = -(1 .. H)``, ``D`` = 1.

The kernels are found as the benchmark finds them
(``benchmark/layer_metrics/_ssd.py``). No benchmark cell runs this; there is
no CPU fallback: without a TPU it exits 2.

Run: chiprun -- python tools/ssd_bench.py [--tokens 8192] [--chunk 128,256]
     [--state-dtype f32] [--step-scale 1] [--check 1] [--plain 1]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NAMES = ("out", "du", "dB", "dC", "dDelta", "dA", "dD")


def make_inputs(T: int, H: int, P: int, G: int, N: int, dtype, seed: int,
                step_scale=1.0):
    """``u, dy`` ``[1, T, H * P]``, ``B, C`` ``[1, T, G * N]``, ``Delta``
    float32 ``[1, T, H]``, ``A, D`` float32 ``[H]``, in the order ``u, B, C,
    Delta, A, D, dy``."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed % 2 ** 31), 6)
    f32 = jnp.float32

    def conv_out(k, width):
        return jax.nn.silu(jax.random.normal(k, (1, T, width), f32)).astype(
            dtype)

    step = jnp.exp(jax.random.uniform(ks[3], (1, T, H), f32, jnp.log(1e-3),
                                      jnp.log(1e-1)))
    return (conv_out(ks[0], H * P), conv_out(ks[1], G * N),
            conv_out(ks[2], G * N), step_scale * step,
            -jnp.arange(1, H + 1, dtype=f32),
            1.0 + 0.1 * jax.random.normal(ks[4], (H,), f32),
            jax.random.normal(ks[5], (1, T, H * P), f32).astype(dtype))


def recurrence(ref, groups: int):
    """``(u, B, C, Delta, A, D) -> y`` by the reference's token-by-token
    recurrence over a batch."""
    import jax.numpy as jnp

    def call(u, Bm, Cm, delta, A, D):
        T, H = u.shape[1], delta.shape[2]
        return jnp.stack([ref.recurrence(
            u1.reshape(T, H, -1), b1.reshape(T, groups, -1),
            c1.reshape(T, groups, -1), d1, A, D).reshape(T, -1)
            for u1, b1, c1, d1 in zip(u, Bm, Cm, delta)])
    return call


def out_and_gradients(fn, inputs, dtype=None):
    """``fn(u, B, C, Delta, A, D) -> y`` and its six gradients under ``dy``
    (``dtype``: the operands cast to it first)."""
    import jax

    @jax.jit
    def run(*x):
        out, vjp = jax.vjp(fn, *x[:6])
        return (out,) + vjp(x[6])
    if dtype is not None:
        inputs = tuple(x.astype(dtype) for x in inputs)
    return jax.block_until_ready(run(*inputs))


def variants(args, peaks, trace_root):
    import jax
    import jax.numpy as jnp

    from benchmark.kernels import ssd_cost
    from benchmark.kernels.ssm_check import rel_l2
    from benchmark.layer_metrics import _ssd
    from benchmark.reference import nemotron_h as ref
    from tepdist_tpu.ops.pallas import ssd_attention as ssd
    from tools.gdn_bench import _kernels
    from tools.sala_bench import _traced

    H, P, G, N, T = args.heads, args.head_dim, args.groups, args.states, \
        args.tokens
    inputs = make_inputs(T, H, P, G, N, jnp.bfloat16, args.seed,
                         args.step_scale)
    want = plain = None
    if args.check:
        with jax.default_matmul_precision("highest"):
            want = out_and_gradients(recurrence(ref, G), inputs, jnp.float32)
            plain = out_and_gradients(
                lambda *a: ssd.chunked(*a, groups=G, chunk=128), inputs,
                jnp.float32)
        yield {"what": "chunked jax.numpy form against the recurrence",
               "rel_l2": {n: rel_l2(c, w)
                          for n, c, w in zip(NAMES, plain, want)}}
    for chunk in (int(c) for c in args.chunk.split(",")):
        record = {"what": "ssd", "chunk": chunk, "tokens": T, "heads": H,
                  "head_dim": P, "groups": G, "states": N,
                  "state_dtype": args.state_dtype, "iters": args.iters,
                  "step_scale": args.step_scale}
        try:
            if want is not None:
                how = dict(groups=G, chunk=chunk, out_dtype=jnp.float32,
                           state_dtype={"f32": None, "bf16": jnp.bfloat16}[
                               args.state_dtype])
                alone = jax.block_until_ready(jax.jit(
                    lambda *x: (ssd.forward(*x[:6], **how),)
                    + ssd.backward(*x, **how))(*inputs))
                record["rel_l2_vs_recurrence_f32"] = {
                    n: rel_l2(a, w) for n, a, w in zip(NAMES, alone, want)}
                record["rel_l2_vs_chunked_f32"] = {
                    n: rel_l2(a, w) for n, a, w in zip(NAMES, alone, plain)}

            def both(fn):
                @jax.jit
                def run(*x):
                    out, vjp = jax.vjp(fn, *x[:6])
                    return (out,) + vjp(x[6])
                return run

            grad = both(lambda *a: ssd.ssd_attention(*a, groups=G,
                                                     chunk=chunk))
            summary = _traced(f"ssd-{chunk}", lambda: grad(*inputs),
                              args.iters, trace_root)
            record["kernels"] = _kernels(
                summary, _ssd.is_ssd, _ssd.parse, _ssd.call_cost,
                ssd_cost.roofline_seconds, peaks)
            record["other_device_us_per_iter"] = 1e6 * sum(
                s for _, s, _ in summary.ops(
                    lambda t: not _ssd.is_ssd(t))) / args.iters
            if args.plain:
                form = both(lambda *a: ssd.chunked(*a, groups=G,
                                                   chunk=chunk))
                summary = _traced(f"ssd-plain-{chunk}",
                                  lambda: form(*inputs), args.iters,
                                  trace_root)
                record["chunked_form_device_us_per_iter"] = \
                    1e6 * summary.busy_s / args.iters
        except Exception as e:  # noqa: BLE001 — one refused chunk must not
            # cost the call that times the others
            record["error"] = repr(e)[:2000]
        yield record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--states", type=int, default=128)
    ap.add_argument("--chunk", default="128", help="chunks, a comma between "
                    "them")
    ap.add_argument("--state-dtype", default="f32", choices=("f32", "bf16"))
    ap.add_argument("--step-scale", type=float, default=1.0,
                    help="multiplies every step Delta")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--check", type=int, default=1,
                    help="0 skips the float32 references")
    ap.add_argument("--plain", type=int, default=1,
                    help="0 skips timing the chunked jax.numpy form")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="also write the records "
                    "as JSON lines to this file")
    args = ap.parse_args(argv)

    from benchmark.lib import device

    devices = device.own_chips(1)
    peaks = device.peaks_for(devices[0].device_kind,
                             os.path.join(ROOT, "benchmark"))
    trace_root = os.path.join(ROOT, ".bench_trace", "ssd_bench")
    for record in variants(args, peaks, trace_root):
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
