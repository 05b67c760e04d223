"""Time and check the grouped matmul alone, on the chip: the tree's Pallas
kernels (``ops/pallas/grouped_matmul.py``) in their tile-aligned layout.

Rows, K, N and groups in; device microseconds a call of the forward, the
input gradient and the weight gradient out, from one ``jax.profiler`` trace
a form, each beside its roofline time (``benchmark/kernels/gmm_cost.py``,
``benchmark/peaks.json``); pads are in the time and not in the count.

``--sizes`` says how the rows fall into groups: ``balanced`` (a multinomial
over equally likely experts, as a trained router gives them), ``skewed``
(experts likely as 1 / rank^2, the last two empty: what a capacity scheme
would drop from) or a JSON file whose ``layers`` is a list of size lists, one
timed after the other (``tools/olmoe_flips.py --sizes-out`` writes the sizes
the OLMoE cell's router gives a micro batch).

``--check 1`` (the default) holds the compiled kernels to a per-expert
float32 loop on the host (numpy, the same bf16 operands): relative L2 error
of the forward, the input gradient and the weight gradient, the pad rows
read back as zeros, an empty group's weight gradient exactly zero. A result
rounded once to bf16 is within 2**-8 of the float32 one; a form above that
ends the run with 1.

``--stack L`` times the kernels' other form beside each: the weight as one
layer of ``[L, groups, K, N]`` read through the layer's index
(``forward.stack``, ``input_grad.stack``), and the weight gradient added
into an accumulator of that shape where it lies (``weight_grad.into``)
against the weight gradient with XLA's slice, add and update of the
accumulator (``weight_grad.sliced``: what a walk over slices runs a layer).
With ``--check 1`` each is held to its counterpart bit for bit.

``--epilogue 1`` times the calls that carry an epilogue beside what they
replace: ``input_grad.add`` (the addend's buffer the result's) against
``input_grad.xla_add`` (the kernel, then XLA's add of the two results), and
``forward.act`` (a gate's forward, then the forward whose epilogue is
``silu(gate) * product * row_weight``) against ``forward.xla_act`` (two
forwards, then XLA's ``gated``). With ``--check 1`` all four are held to the
host's float32 loop.

No benchmark cell runs this; it is for work on the kernels. No CPU fallback.

Run: chiprun -- python tools/gmm_bench.py [--rows 65536] [--k 2048]
     [--n 1024] [--groups 64] [--sizes balanced] [--tile-m 128,256,512]
     [--block-n 1024] [--check 0] [--stack 0] [--epilogue 0]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CHECK_LIMIT = 2.0 ** -8


def traced_us(fn, args, iters, path, carried=None):
    """Device microseconds a call of jitted ``fn``, and its four longest
    operations. ``carried``: the position of the operand a call's result
    replaces (an accumulator the call was given to write over)."""
    import jax
    from benchmark.lib import tracing
    args = list(args)

    def call():
        out = fn(*args)
        if carried is not None:
            args[carried] = out
        return out

    jax.block_until_ready(call())                       # compiles
    tracing.discard(path)
    jax.profiler.start_trace(path)
    for _ in range(iters):
        out = call()
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    summary = tracing.reduce_trace(path)
    tracing.discard(path)
    ops = sorted(summary.ops(lambda t: True), key=lambda op: -op[1])
    return (1e6 * sum(s for _, s, _ in ops) / iters,
            [[t[:100], round(1e6 * s / iters, 1)] for t, s, _ in ops[:4]])


def size_sets(spec, rows, groups, rng):
    """[(label, rows of each group)] of ``--sizes``."""
    import numpy as np
    if spec == "balanced":
        p = np.full(groups, 1.0 / groups)
    elif spec == "skewed":
        p = rng.permutation(1.0 / np.arange(1, groups + 1) ** 2)
        p[-2:] = 0.0
        p /= p.sum()
    else:
        with open(spec) as f:
            return [(f"layer{i}", np.asarray(s, np.int32))
                    for i, s in enumerate(json.load(f)["layers"])]
    return [(spec, rng.multinomial(rows, p).astype(np.int32))]


def loop_reference(x, dy, w, ids):
    """(x @ w[g], dy @ w[g].T, x.T @ dy per group) by a loop over the
    groups in float32; rows in the order of ``ids`` [R]."""
    import numpy as np
    x, dy, w = (np.asarray(a, np.float32) for a in (x, dy, w))
    out, dx, dw = np.zeros_like(dy), np.zeros_like(x), np.zeros_like(w)
    for g in range(w.shape[0]):
        rows = np.flatnonzero(ids == g)
        out[rows] = x[rows] @ w[g]
        dx[rows] = dy[rows] @ w[g].T
        dw[g] = x[rows].T @ dy[rows]
    return out, dx, dw


def rel_l2(got, want):
    import numpy as np
    got = np.asarray(got, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--k", type=int, default=2048)
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--groups", type=int, default=64)
    ap.add_argument("--sizes", default="balanced")
    ap.add_argument("--tile-m", default="128,256,512")
    ap.add_argument("--block-n", default="1024")
    ap.add_argument("--check", type=int, default=1)
    ap.add_argument("--stack", type=int, default=0,
                    help="layers of a stack to time the other forms on")
    ap.add_argument("--epilogue", type=int, default=0,
                    help="1: time the calls with an addend or an activation")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.kernels import gmm_cost
    from benchmark.lib import device
    from tepdist_tpu.ops import grouped_matmul as layout
    from tepdist_tpu.ops.pallas import grouped_matmul as kernels

    devices = device.own_chips(1)
    peaks = device.peaks_for(devices[0].device_kind,
                             os.path.join(ROOT, "benchmark"))
    K, N, G = args.k, args.n, args.groups
    rng = np.random.default_rng(args.seed)
    trace_root = os.path.join(ROOT, ".bench_trace", "gmm_bench")
    bf16 = jnp.bfloat16
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    w = (jax.random.normal(kw, (G, K, N), jnp.float32) * 0.02).astype(bf16)
    # The epilogue's gate is another product's result.
    w_gate = jnp.flip(w, 0) if args.epilogue else None
    records, sound = [], True
    if args.stack:      # the weight is the middle layer of the stack
        at = args.stack // 2
        layer = jnp.asarray([at], jnp.int32)
        ks, ka = jax.random.split(jax.random.PRNGKey(args.seed + 1))
        stack = (jax.random.normal(ks, (args.stack, G, K, N), jnp.float32)
                 * 0.02).astype(bf16).at[at].set(w)
        acc = jax.random.normal(ka, (args.stack, G, K, N), bf16)

    for label, sizes in size_sets(args.sizes, args.rows, G, rng):
        R = int(sizes.sum())
        least = gmm_cost.roofline_seconds(
            gmm_cost.grouped_matmul(R, K, N, G), peaks)
        # One assignment a row ("tokens" with k = 1), in a seeded order.
        ids = rng.permutation(np.repeat(np.arange(G), sizes)).astype(np.int32)
        x = jax.random.normal(kx, (R, K), jnp.float32).astype(bf16)
        dy = jax.random.normal(ky, (R, N), jnp.float32).astype(bf16)
        row_weight = jax.random.uniform(ky, (R, 1), jnp.float32, 0.1, 1.0)
        want = loop_reference(x, dy, w, ids) if args.check else None
        for tm in (int(t) for t in args.tile_m.split(",")):
            r = layout.route(jnp.asarray(ids)[:, None], G, tm)
            xp = layout.dispatch(x, r.row_token, r.dest)
            dyp = layout.dispatch(dy, r.row_token, r.dest)
            rwp = layout.dispatch_values(row_weight, r)
            tg, nt = r.tile_group, r.n_tiles
            for bn in (int(b) for b in args.block_n.split(",")):
                def fwd(x, w, *layer, **kw):
                    return kernels.gmm(x, w, tg, nt, *layer, tile_m=tm,
                                       block_n=bn, interpret=False, **kw)

                def dx(dy, w, *layer, **kw):
                    return fwd(dy, w, *layer, transpose_rhs=True,
                               name="tepdist_gmm_dx", **kw)

                forms = {
                    "forward": jax.jit(fwd),
                    "input_grad": jax.jit(dx),
                    "weight_grad": jax.jit(lambda x, dy: kernels.tgmm(
                        x, dy, tg, nt, G, tile_m=tm, block_n=bn,
                        interpret=False)),
                }
                operands = {"forward": (xp, w), "input_grad": (dyp, w),
                            "weight_grad": (xp, dyp)}
                carried = {}
                if args.stack:
                    def sliced(x, dy, acc, dw=forms["weight_grad"]):
                        one = jax.lax.dynamic_index_in_dim(
                            acc, layer[0], keepdims=False)
                        return jax.lax.dynamic_update_index_in_dim(
                            acc, one + dw(x, dy), layer[0], 0)

                    forms.update({
                        "forward.stack": jax.jit(
                            lambda x, s: fwd(x, s, layer)),
                        "input_grad.stack": jax.jit(
                            lambda dy, s: dx(dy, s, layer)),
                        "weight_grad.into": jax.jit(
                            lambda x, dy, acc: kernels.tgmm(
                                x, dy, tg, nt, G, acc, layer, tile_m=tm,
                                block_n=bn, interpret=False),
                            donate_argnums=2),
                        "weight_grad.sliced": jax.jit(sliced,
                                                      donate_argnums=2)})
                    operands.update({
                        "forward.stack": (xp, stack),
                        "input_grad.stack": (dyp, stack),
                        "weight_grad.into": (xp, dyp, jnp.copy(acc)),
                        "weight_grad.sliced": (xp, dyp, jnp.copy(acc))})
                    carried = {"weight_grad.into": 2, "weight_grad.sliced": 2}
                if args.epilogue:
                    forms.update({
                        "input_grad.add": jax.jit(
                            lambda dy, w, first: dx(dy, w, add=first),
                            donate_argnums=2),
                        "input_grad.xla_add": jax.jit(
                            lambda dy, w, first: first + dx(dy, w),
                            donate_argnums=2),
                        "forward.act": jax.jit(lambda x, w, wg, rw: fwd(
                            x, w, act=(fwd(x, wg), rw))),
                        "forward.xla_act": jax.jit(
                            lambda x, w, wg, rw: layout.gated(
                                fwd(x, wg), fwd(x, w), rw))})
                    operands.update({
                        "input_grad.add": (dyp, w, jnp.copy(xp)),
                        "input_grad.xla_add": (dyp, w, jnp.copy(xp)),
                        "forward.act": (xp, w, w_gate, rwp),
                        "forward.xla_act": (xp, w, w_gate, rwp)})
                    carried.update({"input_grad.add": 2,
                                    "input_grad.xla_add": 2})
                rec = {"impl": "pallas", "sizes": label, "rows": R, "K": K,
                       "N": N, "groups": G, "rows_max": int(sizes.max()),
                       "rows_min": int(sizes.min()), "tile_m": tm,
                       "block_n": bn, "rows_in_layout": int(xp.shape[0]),
                       "live_tiles": int(nt[0]),
                       "roofline_us": 1e6 * least["seconds"],
                       "bound": least["bound"],
                       "device": devices[0].device_kind}
                try:
                    if args.check:
                        pad = np.asarray(r.row_token) >= R
                        dest = np.asarray(r.dest)[:, 0]
                        got = {f: np.asarray(forms[f](*operands[f]),
                                             np.float32)
                               for f in ("forward", "input_grad",
                                         "weight_grad")}
                        empty = np.flatnonzero(sizes == 0)
                        rec["check"] = {
                            "rel_l2": {
                                "forward": rel_l2(
                                    got["forward"][dest], want[0]),
                                "input_grad": rel_l2(
                                    got["input_grad"][dest], want[1]),
                                "weight_grad": rel_l2(
                                    got["weight_grad"], want[2])},
                            "pad_rows": int(pad.sum()),
                            "pad_rows_zero": not (
                                got["forward"][pad].any()
                                or got["input_grad"][pad].any()),
                            "empty_groups": len(empty),
                            "empty_groups_dw_zero":
                                not got["weight_grad"][empty].any()}
                        c = rec["check"]
                        c["sound"] = bool(
                            max(c["rel_l2"].values()) < CHECK_LIMIT
                            and c["pad_rows_zero"]
                            and c["empty_groups_dw_zero"])
                        if args.stack:
                            def same(f):
                                return bool(jnp.array_equal(
                                    forms[f](*operands[f]),
                                    forms[f + ".stack"](
                                        *operands[f + ".stack"])))

                            c["stack_bit_for_bit"] = {
                                "forward": same("forward"),
                                "input_grad": same("input_grad"),
                                "weight_grad": bool(jnp.array_equal(
                                    forms["weight_grad.into"](
                                        xp, dyp, jnp.copy(acc)),
                                    forms["weight_grad.sliced"](
                                        xp, dyp, jnp.copy(acc))))}
                            c["sound"] = c["sound"] and all(
                                c["stack_bit_for_bit"].values())
                        if args.epilogue:
                            # The addend: the rows themselves, K wide.
                            first = np.asarray(x, np.float32)
                            gate = loop_reference(x, dy, w_gate, ids)[0]
                            act = gate / (1.0 + np.exp(-gate)) * want[0] \
                                * np.asarray(row_weight)

                            def fresh(f):   # a donated addend is spent
                                if f not in carried:
                                    return operands[f]
                                return (*operands[f][:2], jnp.copy(xp))

                            c["epilogue_rel_l2"] = {
                                f: rel_l2(np.asarray(
                                    forms[f](*fresh(f)))[dest], ref)
                                for f, ref in (
                                    ("input_grad.add", want[1] + first),
                                    ("input_grad.xla_add", want[1] + first),
                                    ("forward.act", act),
                                    ("forward.xla_act", act))}
                            c["sound"] = c["sound"] and max(
                                c["epilogue_rel_l2"].values()) < CHECK_LIMIT
                        sound = sound and c["sound"]
                        del got
                    total = 0.0
                    for f, fn in forms.items():
                        us, ops = traced_us(
                            fn, operands[f], args.iters, os.path.join(
                                trace_root, f"{label}_{tm}_{bn}_{f}"),
                            carried.get(f))
                        rec[f] = {"us_per_call": us, "top_ops": ops,
                                  "roofline_share_pct":     # two products
                                      100.0 * rec["roofline_us"] / us
                                      * (1 + f.endswith("act"))}
                        total += us * ("." not in f)
                    rec["three_forms_us"] = total
                except Exception as e:  # noqa: BLE001 — one refused variant
                    # must not cost the call that times the others
                    rec["error"] = repr(e)[:2000]
                    sound = False
                line = json.dumps(rec)
                print(line, flush=True)
                records.append(line)
            del xp, dyp
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write("\n".join(records) + "\n")
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
