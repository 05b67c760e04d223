"""Time MiniCPM-SALA's two mixing mechanisms alone, on the chip.

``--what lightning``: the decayed linear-attention kernels
(``ops/pallas/lightning_attention.py``) at the cell's ``[1, 32768, 32 x
128]``: device microseconds a call of the forward and of the two backward
kernels for each chunk asked for, from one ``jax.profiler`` trace a variant
reduced by ``benchmark/trace_reduce.py``, each beside its roofline time
(``benchmark/kernels/lightning_cost.py``), and the relative L2 distance of
the output and the three gradients (asked for in float32) from the sequential
float32 recurrence of ``benchmark/reference/minicpm_sala.py``
(``benchmark/kernels/lightning_check.py``, which the cell's builder also
holds the kernels to); ``--state-dtype bf16`` reads the same with the
carried state rounded to bf16 (the check's control).

``--what topk``: the sparse layer's choice (``select_blocks``: XLA) and the
kernels over the chosen blocks (``ops/pallas/block_topk_attention.py``) at
``[1, 32768, 32 heads over 2 x 128]``, geometry 64 / 32 / 16, top 64: device
microseconds of the choice's operations and of the forward and backward
kernels beside their roofline times (``kernels/topk_attn_cost.py``), and the
distance of the output and the three gradients from the reference's masked
float32 attention handed the same sets.

The kernels are found as the benchmark finds them
(``benchmark/layer_metrics/_sala.py``). No benchmark cell runs this; there
is no CPU fallback: without a TPU it exits 2.

Run: chiprun -- python tools/sala_bench.py [--what lightning,topk]
     [--tokens 32768] [--chunk 128,256,512] [--state-dtype f32]
     [--keys-a-trip 256,512,1024]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _traced(label, run, iters, trace_root):
    import jax

    from benchmark.lib import tracing
    path = os.path.join(trace_root, label)
    tracing.discard(path)
    jax.block_until_ready(run())                            # compiles
    jax.profiler.start_trace(path)
    for _ in range(iters):
        out = run()
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    summary = tracing.reduce_trace(path)
    tracing.discard(path)
    return summary


def _kernel_lines(summary, match, parse, cost_of, module, peaks, iters):
    from benchmark import trace_reduce
    out = {}
    for text, secs, calls in summary.ops(match):
        parsed = parse(text)
        name = trace_reduce.short_name(text)
        if parsed is None:
            out[name] = {"unparsed": text[:300]}
            continue
        least = module.roofline_seconds(cost_of(parsed), peaks)
        out[name] = {"calls": calls, "us_per_call": 1e6 * secs / calls,
                     "roofline_us": 1e6 * least["seconds"],
                     "bound": least["bound"],
                     "roofline_share_pct":
                         100.0 * least["seconds"] * calls / secs}
    return out


def lightning(args, peaks, trace_root):
    import jax
    import jax.numpy as jnp

    from benchmark.kernels import lightning_check, lightning_cost
    from benchmark.layer_metrics import _sala
    from benchmark.reference import minicpm_sala as ref
    from tepdist_tpu.ops.pallas import lightning_attention as la

    H, D, T = args.heads, args.head_dim, args.tokens
    hp = ref.Hyper(H, 2, H, (ref.LIGHTNING,))
    lam = ref.decays(hp, args.layer)
    inputs = lightning_check.make_inputs((1, T, H, D), jnp.bfloat16,
                                         args.seed)
    want = lightning_check.sequential_out_and_gradients(inputs, lam) \
        if args.check else None
    state = {"f32": None, "bf16": jnp.bfloat16}[args.state_dtype]
    q, k, v, do = (x.reshape(1, T, H * D) for x in inputs)
    ld = jnp.log(lam)
    for chunk in (int(c) for c in args.chunk.split(",")):
        record = {"what": "lightning", "chunk": chunk, "tokens": T,
                  "heads": H, "state_dtype": args.state_dtype,
                  "iters": args.iters}
        try:
            if want is not None:
                def kernels(q, k, v, ld, do, chunk=chunk):
                    how = dict(chunk=chunk, interpret=False,
                               out_dtype=jnp.float32, state_dtype=state)
                    return (la.forward(q, k, v, ld, **how),) \
                        + la.backward(q, k, v, ld, do, **how)
                record["rel_l2_vs_sequential_f32"] = \
                    lightning_check.against_sequential(kernels, inputs, lam,
                                                       want)

            @jax.jit
            def grad(q, k, v, ld, do, chunk=chunk):
                out, vjp = jax.vjp(lambda *a: la.lightning_attention(
                    *a, ld, chunk=chunk, interpret=False), q, k, v)
                return (out,) + vjp(do)

            summary = _traced(f"lightning-{chunk}",
                              lambda: grad(q, k, v, ld, do), args.iters,
                              trace_root)

            def cost_of(parsed):
                kind, tokens, heads, dim, act = parsed
                cost = getattr(lightning_cost, kind)(tokens, heads, dim, act)
                return {n: x / 2 for n, x in cost.items()} \
                    if kind == "backward" else cost

            record["kernels"] = _kernel_lines(
                summary, _sala.is_lightning, _sala.parse_lightning, cost_of,
                lightning_cost, peaks, args.iters)
            others = sorted(summary.ops(lambda t: not _sala.is_lightning(t)),
                            key=lambda op: -op[1])
            record["other_device_us_per_iter"] = \
                1e6 * sum(s for _, s, _ in others) / args.iters
        except Exception as e:  # noqa: BLE001 — one refused variant must
            # not cost the call that times the others
            record["error"] = repr(e)[:2000]
        yield record


def topk(args, peaks, trace_root):
    import jax
    import jax.numpy as jnp

    from benchmark.kernels import lightning_check, topk_attn_cost
    from benchmark.layer_metrics import _sala
    from benchmark.reference import minicpm_sala as ref
    from tepdist_tpu.ops.pallas import block_topk_attention as bt

    H, G, D, T = args.heads, args.kv_heads, args.head_dim, args.tokens
    geo = bt.BlockGeometry()
    hp = ref.Hyper(H, G, H, (ref.SPARSE,))
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 4)

    def unit(key, heads):
        x = jax.random.normal(key, (1, T, heads, D), jnp.float32)
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
                ).astype(jnp.bfloat16)

    q, k = unit(ks[0], H), unit(ks[1], G)
    v = jax.random.normal(ks[2], (1, T, G, D), jnp.float32).astype(
        jnp.bfloat16)
    do = jax.random.normal(ks[3], (1, T, H, D), jnp.float32).astype(
        jnp.bfloat16)
    select = jax.jit(lambda q, k: bt.select_blocks(q, k, geo))
    for trip in (int(x) for x in args.keys_a_trip.split(",")):
        bt.KEYS_A_TRIP = trip
        yield _topk_variant(args, peaks, trace_root, select, geo, hp,
                            (q, k, v, do), trip)
        args.check = 0      # the references once; the choice is timed again


def _topk_variant(args, peaks, trace_root, select, geo, hp, inputs, trip):
    import jax
    import jax.numpy as jnp

    from benchmark.kernels import lightning_check, topk_attn_cost
    from benchmark.layer_metrics import _sala
    from benchmark.reference import minicpm_sala as ref
    from tepdist_tpu.ops.pallas import block_topk_attention as bt

    q, k, v, do = inputs
    _, T, H, D = q.shape
    G = k.shape[2]
    record = {"what": "topk", "tokens": T, "heads": H, "kv_heads": G,
              "keys_a_trip": trip, "iters": args.iters}
    try:
        idx = jax.block_until_ready(select(q, k))
        summary = _traced("topk-select", lambda: select(q, k), args.iters,
                          trace_root)
        record["choice_us_per_call"] = \
            1e6 * sum(s for _, s, _ in summary.ops(lambda t: True)) \
            / args.iters
        record["choice_ops"] = [
            [text[:120], 1e6 * s / args.iters] for text, s, _ in sorted(
                summary.ops(lambda t: True), key=lambda op: -op[1])[:6]]

        @jax.jit
        def grad(q, k, v, idx, do):
            out, vjp = jax.vjp(lambda *a: bt.topk_attention(
                *a, idx, geo, interpret=False), q, k, v)
            return (out,) + vjp(do)

        got = jax.block_until_ready(grad(q, k, v, idx, do))
        if args.check:
            f32 = jnp.float32
            sets = jnp.zeros((G, T, T // geo.block_size), bool).at[
                jnp.arange(G)[:, None, None], jnp.arange(T)[None, :, None],
                idx[0]].set(True)

            # The reference's own choice, and how many sets differ from the
            # program's (both from the same bf16 q and k).
            with jax.default_matmul_precision("highest"):
                theirs = jax.jit(lambda q, k: ref.chosen_blocks(
                    q[0].astype(f32).reshape(T, G, H // G, D),
                    k[0].astype(f32), hp))(q, k)
                record["sets_differing_share"] = float(
                    jnp.mean(jnp.any(theirs != sets, axis=-1)))

                @jax.jit
                def masked(q, k, v, do):
                    out, vjp = jax.vjp(
                        lambda q, k, v: ref.masked_attention(
                            q[0].reshape(T, G, H // G, D), k[0], v[0], sets,
                            hp).reshape(1, T, H, D), q, k, v)
                    return (out,) + vjp(do)
                want = jax.block_until_ready(masked(
                    *(x.astype(f32) for x in (q, k, v, do))))
            record["rel_l2_vs_masked_f32"] = {
                n: lightning_check.rel_l2(g, w) for n, g, w in zip(
                    lightning_check.NAMES, got, want)}
        summary = _traced("topk-grad", lambda: grad(q, k, v, idx, do),
                          args.iters, trace_root)

        def cost_of(parsed):
            kind, batch, T_, H_, G_, D_, K, act = parsed
            return getattr(topk_attn_cost, kind)(T_, H_, G_, D_,
                                                 geo.block_size, K, act)

        record["kernels"] = _kernel_lines(
            summary, _sala.is_topk_kernel, _sala.parse_topk, cost_of,
            topk_attn_cost, peaks, args.iters)
        others = sorted(summary.ops(lambda t: not _sala.is_topk_kernel(t)),
                        key=lambda op: -op[1])
        record["other_device_us_per_iter"] = \
            1e6 * sum(s for _, s, _ in others) / args.iters
        record["other_ops"] = [[text[:120], 1e6 * s / args.iters]
                               for text, s, _ in others[:4]]
    except Exception as e:  # noqa: BLE001
        record["error"] = repr(e)[:3000]
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--what", default="lightning,topk")
    ap.add_argument("--tokens", type=int, default=32768)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--layer", type=int, default=1,
                    help="published layer whose decay slopes are used")
    ap.add_argument("--chunk", default="256", help="lightning chunks, a "
                    "comma between them")
    ap.add_argument("--keys-a-trip", default="512", help="top-k kernels' "
                    "key slots a trip, a comma between them")
    ap.add_argument("--state-dtype", default="f32", choices=("f32", "bf16"))
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--check", type=int, default=1,
                    help="0 skips the float32 references")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="also write the records "
                    "as JSON lines to this file")
    args = ap.parse_args(argv)

    from benchmark.lib import device

    devices = device.own_chips(1)
    peaks = device.peaks_for(devices[0].device_kind,
                             os.path.join(ROOT, "benchmark"))
    trace_root = os.path.join(ROOT, ".bench_trace", "sala_bench")
    for what in args.what.split(","):
        for record in {"lightning": lightning, "topk": topk}[what](
                args, peaks, trace_root):
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
