"""Time and check the expert layers' routers alone, on the chip:
``olmoe.router``, ``mellum.router`` and ``afmoe.router``, value and vjp, at
the shapes the cells call them with, and the choice they share
(``ops/pallas/router_choice.py:choose``) beside ``jax.lax.top_k`` and beside
the k-th score by bit search (``block_topk_attention.py:highest`` with a
cumulative count for the ids).

A case is ``router:S,d,E,k`` (``S`` tokens of width ``d``, ``k`` of ``E``
experts). Device microseconds a call out, from one ``jax.profiler`` trace a
form and direction, with the longest operations of each, and from each
compiled form's optimised HLO its ``sort``, ``scatter`` and ``gather``
instructions.

``--impl parent=<a directory that holds another olmoe.py, mellum.py and
afmoe.py>`` times a second copy of the routers in the same process and says
whether its ids are the first copy's and how far its weights and gradients
lie from the first's, as a share of the largest (a weight may differ in its
last place: the compiler fuses the two forms' arithmetic differently; exit 1
where an id differs):
``git archive <parent> tepdist_tpu/models | tar -x -C .chip_scratch/p``, then
``--impl parent=.chip_scratch/p/tepdist_tpu/models/``.

No benchmark cell runs this; it is for work on the function. No CPU
fallback.

Run: chiprun -- python tools/router_bench.py [--impl parent=DIR]
     [--case mellum:8192,2048,512,10 ...] [--alone 1] [--iters 10]
     [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools.flash_bench import load_impl  # noqa: E402
from tools.gmm_bench import traced_us  # noqa: E402

# Qwen3-Next, Mellum2, OLMoE, Kimi Linear, a chunk of sarvam's sequence,
# Trinity and Nemotron-3-Nano.
CASES = ("mellum:8192,2048,512,10", "mellum:16384,2304,64,8",
         "olmoe:8192,2048,64,8", "afmoe:8192,2304,256,8",
         "afmoe:2048,4096,128,8", "afmoe:8192,2048,128,8",
         "afmoe:8192,2688,128,6")
# Of a router's results (weights [S, k], ids [S, k]).
PICK = {"olmoe": lambda out: out[2:], "mellum": lambda out: out,
        "afmoe": lambda out: out[1:]}


def left_in(hlo: str) -> dict:
    """The sorts, scatters and gathers an optimised HLO text holds."""
    return {op: len(re.findall(rf"= \S+ {op}\(", hlo))
            for op in ("sort", "scatter", "gather")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--case", action="append", default=[],
                    help="olmoe|mellum|afmoe:S,d,E,k; repeatable (the "
                    "default: the cells' calls)")
    ap.add_argument("--impl", action="append", default=[],
                    help="label=directory of another olmoe.py, mellum.py "
                    "and afmoe.py")
    ap.add_argument("--alone", type=int, default=1,
                    help="1: the choice alone too, on scores in HBM, beside "
                    "lax.top_k and the bit search")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import device
    from tepdist_tpu.ops.pallas.block_topk_attention import highest
    from tepdist_tpu.ops.pallas.router_choice import choose

    devices = device.own_chips(1)
    impls = [("tree", os.path.join(ROOT, "tepdist_tpu", "models"))]
    impls += [tuple(item.partition("=")[::2]) for item in args.impl]
    trace_root = os.path.join(ROOT, ".bench_trace", "router_bench")
    records, sound = [], True

    def timed(rec, name, fn, operands):
        hlo = fn.lower(*operands).compile().as_text()
        us, ops = traced_us(fn, operands, args.iters,
                            os.path.join(trace_root, name))
        rec[name] = {"us_per_call": us, "top_ops": ops, **left_in(hlo)}
        return fn(*operands)

    for case in args.case or CASES:
        kind, shape = case.split(":")
        S, d, E, k = (int(v) for v in shape.split(","))
        rng = np.random.default_rng(args.seed)
        h = jnp.asarray(rng.standard_normal((S, d), np.float32)
                        ).astype(jnp.bfloat16)
        blk = {"router": jnp.asarray(
            rng.standard_normal((d, E), np.float32) * d ** -0.5
        ).astype(jnp.bfloat16), "router_bias": jnp.asarray(
            rng.standard_normal(E, np.float32) * 0.01)}
        g = jnp.asarray(rng.standard_normal((S, k), np.float32))
        cfg = types.SimpleNamespace(num_experts_per_tok=k, route_scale=2.5)
        rec = {"case": case, "iters": args.iters,
               "scores_bytes": 4 * S * E,
               "device": devices[0].device_kind}
        first = None
        for label, path in impls:
            router = load_impl(f"{label}_{kind}",
                               os.path.join(path, kind + ".py")).router

            def routed(h, w, router=router):
                return PICK[kind](router({**blk, "router": w}, h, cfg))

            got = dict(zip(("weights", "ids"), timed(
                rec, f"{label}.value", jax.jit(routed), (h, blk["router"]))))
            got["d_h"], got["d_router"] = timed(
                rec, f"{label}.vjp", jax.jit(
                    lambda h, w, g, routed=routed: jax.vjp(
                        lambda h, w: routed(h, w)[0], h, w)[1](g)),
                (h, blk["router"], g))
            got = {name: np.asarray(a.astype(jnp.float32))
                   for name, a in got.items()}
            if first is None:
                first = got
                continue
            rec[f"{label}.same_ids"] = bool(
                (got["ids"] == first["ids"]).all())
            rec[f"{label}.apart"] = {
                name: float(np.abs(got[name] - first[name]).max()
                            / np.abs(first[name]).max())
                for name in ("weights", "d_h", "d_router")}
            sound = sound and rec[f"{label}.same_ids"]
        if args.alone:
            scores = jax.nn.softmax(jnp.dot(
                h, blk["router"], preferred_element_type=jnp.float32), -1)

            def searched(x):
                chosen = highest(x, k)
                count = jnp.cumsum(chosen, axis=-1)
                return jnp.sum(count[..., None, :] <= jnp.arange(k)[:, None],
                               axis=-1)

            forms = {"choose": lambda x: choose(x, k),
                     "lax.top_k": lambda x: jax.lax.top_k(x, k),
                     "bit_search": searched}
            ids = {}
            for name, fn in forms.items():
                out = timed(rec, f"alone.{name}", jax.jit(fn), (scores,))
                ids[name] = np.sort(np.asarray(
                    out if name == "bit_search" else out[1]), axis=-1)
            rec["alone.same_sets"] = {
                name: bool((got == ids["lax.top_k"]).all())
                for name, got in ids.items()}
            sound = sound and all(rec["alone.same_sets"].values())
        line = json.dumps(rec)
        print(line, flush=True)
        records.append(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write("\n".join(records) + "\n")
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
