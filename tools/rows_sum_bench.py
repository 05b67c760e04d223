"""Time and check the rows out of an expert layer's layout alone, on the
chip: the tree's row-copy kernel (``ops/pallas/rows_sum.py``,
``tepdist_rows_sum``) beside the ``k`` XLA gathers it stands in for
(``ops/grouped_matmul.py:_sum_of_rows``).

Layout rows ``M``, width ``d``, tokens ``S``, choices a token ``k`` and the
live share in; device microseconds a call out, from one ``jax.profiler``
trace a form: the XLA gathers, the kernels as the layer calls them (the one
that lays ``y``'s live rows out a row a tile run, ``tepdist_rows_tiled``,
then the sum) and the sum alone on a source already in that form, each
with its longest operations, nanoseconds a live row beside what HBM needs
for the row's bytes (``benchmark/peaks.json``), and whether the kernel's
result is the gathers' bit for bit.

``dest`` is drawn from the seed: each choice is live with probability
``--live`` and names a row under the live bound (every live row once where
they fit), a choice elsewhere names the layout's last row; ``y`` holds zeros
from the bound on, as the grouped-matmul kernels leave it.

No benchmark cell runs this; it is for work on the kernel. No CPU fallback.

Run: chiprun -- python tools/rows_sum_bench.py
     [--shapes "33024,2048,8192,8;53504,2304,16384,8;81920,2048,8192,8"]
     [--live 0.25,1.0] [--dtype bf16] [--tile-m 256]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools.gmm_bench import traced_us  # noqa: E402


def drawn(M, d, S, k, live, tile, dtype, seed):
    """(y [M, d], dest [S, k], bound [1], live choices) of the docstring."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    is_live = rng.random((S, k)) < live
    n_live = int(is_live.sum())
    bound = min(-(-max(n_live, 1) // tile) * tile, M - tile)
    rows = rng.permutation(max(n_live, bound))[:n_live] % bound
    dest = np.full((S, k), M - 1, np.int32)
    dest[is_live] = rows
    y = rng.standard_normal((M, d), np.float32)
    y[bound:] = 0.0
    return (jnp.asarray(y).astype(dtype), jnp.asarray(dest),
            jnp.asarray([bound], jnp.int32), n_live)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="33024,2048,8192,8;"
                    "53504,2304,16384,8;81920,2048,8192,8",
                    help="M,d,S,k of a layout, a semicolon between them "
                    "(the default: the Trinity, Mellum2 and OLMoE cells')")
    ap.add_argument("--live", default="0.25,1.0")
    ap.add_argument("--dtype", default="bf16", choices=("bf16", "f32"))
    ap.add_argument("--tile-m", type=int, default=256)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import device
    from tepdist_tpu.ops import grouped_matmul as layout
    from tepdist_tpu.ops.pallas import rows_sum as kernel

    devices = device.own_chips(1)
    peaks = device.peaks_for(devices[0].device_kind,
                             os.path.join(ROOT, "benchmark"))
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[args.dtype]
    trace_root = os.path.join(ROOT, ".bench_trace", "rows_sum_bench")
    records, sound = [], True

    for shape in args.shapes.split(";"):
        M, d, S, k = (int(v) for v in shape.split(","))
        row_bytes = d * jnp.dtype(dtype).itemsize
        for live in (float(v) for v in args.live.split(",")):
            y, dest, bound, n_live = drawn(M, d, S, k, live, args.tile_m,
                                           dtype, args.seed)
            tiled = jax.jit(lambda y, bound: kernel.rows_tiled(
                y, bound, interpret=False))(y, bound)
            rec = {"M": M, "d": d, "S": S, "k": k, "dtype": args.dtype,
                   "live_share": n_live / (S * k), "live_rows": n_live,
                   "bound": int(bound[0]), "hbm_ns_a_row":
                       1e9 * row_bytes / peaks["hbm_bytes_per_s"],
                   "device": devices[0].device_kind}
            forms = {
                "xla_gathers": (jax.jit(layout._sum_of_rows), (y, dest)),
                "kernel": (jax.jit(
                    lambda y, dest, bound: kernel.rows_sum(
                        y, dest, bound, interpret=False)), (y, dest, bound)),
                "kernel_alone": (jax.jit(
                    lambda t, dest, bound: kernel.rows_sum_tiled(
                        t, dest, bound, d=d, interpret=False)),
                    (tiled, dest, bound))}
            want = None
            for name, (fn, operands) in forms.items():
                try:
                    got = np.asarray(fn(*operands).astype(jnp.float32))
                    if want is None:
                        want = got
                    same = bool((got.view(np.uint32)
                                 == want.view(np.uint32)).all())
                    us, ops = traced_us(fn, operands, args.iters,
                                        os.path.join(trace_root, name))
                    rec[name] = {"us_per_call": us, "top_ops": ops,
                                 "ns_a_live_row": 1e3 * us / max(n_live, 1),
                                 "ns_a_choice": 1e3 * us / (S * k),
                                 "bit_for_bit": same}
                    sound = sound and same
                except Exception as e:  # noqa: BLE001 — one refused form
                    # must not cost the call that times the others
                    rec[name] = {"error": repr(e)[:2000]}
                    sound = False
            line = json.dumps(rec)
            print(line, flush=True)
            records.append(line)
            del y, tiled
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write("\n".join(records) + "\n")
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
