"""Time the hyper-connection maps alone, on the chip:
``models/layers.py:hyper_maps`` + ``hyper_read`` (a sub-layer's input from the
stream) and ``hyper_write`` (the stream after it), value and vjp, each alone
under ``jit`` at the Xing cell's ``[2, 4096, 4 x 3584]`` in bf16, where
nothing of a block can fuse across them.

Device microseconds a call, from one ``jax.profiler`` trace a form and
direction with the longest operations of each, beside the least bytes any
form must move (``benchmark/kernels/mhc_cost.py``) over HBM's peak
(``benchmark/peaks.json``): the share says how far the ``jax.numpy`` form
stands from the chip's bandwidth, and whether the maps are worth a kernel.
(PR 61 timed the Sinkhorn loop at 1, 4 and 20 rounds a trip: 1,066, 1,074
and 1,169 us a read, so the loop has no such knob.)

No benchmark cell runs this; it is for work on the functions. No CPU
fallback.

Run: chiprun -- python tools/mhc_bench.py [--shape 2,4096,4,3584]
     [--rounds 20] [--iters 5] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools.gmm_bench import traced_us  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="2,4096,4,3584",
                    help="B,T,lanes,hidden")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.kernels import mhc_cost
    from benchmark.lib import device
    from tepdist_tpu.models import layers

    devices = device.own_chips(1)
    peaks = device.peaks_for(devices[0].device_kind,
                             os.path.join(ROOT, "benchmark"))
    B, T, n, d = (int(v) for v in args.shape.split(","))
    wide = n * n + 2 * n
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    bf16 = jnp.bfloat16
    x = jax.random.normal(ks[0], (B, T, n * d), bf16)
    phi = (jax.random.normal(ks[1], (n * d, wide)) * 0.02 / n ** 0.5
           ).astype(bf16)
    b = jax.random.normal(ks[2], (wide,)) + jnp.concatenate(
        [jnp.zeros((2 * n,)), 2.0 * jnp.eye(n).reshape(-1)])
    alpha = jnp.ones((3,))
    f = jax.random.normal(ks[3], (B, T, d), bf16)
    dy = jax.random.normal(ks[4], (B, T, d), bf16)
    dx = jax.random.normal(ks[5], (B, T, n * d), bf16)
    trace_root = os.path.join(ROOT, ".bench_trace", "mhc_bench")

    def maps_of(x, phi, b, alpha):
        return layers.hyper_maps(x, phi, b, alpha, rounds=args.rounds,
                                 eps=1e-6)

    def read(x, phi, b, alpha):
        return layers.hyper_read(x, maps_of(x, phi, b, alpha))

    maps = jax.jit(maps_of)(x, phi, b, alpha)
    forms = {
        "read": (jax.jit(read), (x, phi, b, alpha),
                 mhc_cost.read(B * T, n, d)),
        "read.vjp": (jax.jit(lambda x, phi, b, alpha, dy: jax.vjp(
            read, x, phi, b, alpha)[1](dy)), (x, phi, b, alpha, dy),
            mhc_cost.backward(mhc_cost.read(B * T, n, d))),
        "write": (jax.jit(layers.hyper_write), (x, maps, f),
                  mhc_cost.write(B * T, n, d)),
        "write.vjp": (jax.jit(lambda x, maps, f, dx: jax.vjp(
            layers.hyper_write, x, maps, f)[1](dx)), (x, maps, f, dx),
            mhc_cost.backward(mhc_cost.write(B * T, n, d)))}
    rec = {"shape": args.shape, "rounds": args.rounds, "iters": args.iters,
           "device": devices[0].device_kind}
    for name, (fn, operands, cost) in forms.items():
        us, ops = traced_us(fn, operands, args.iters, os.path.join(
            trace_root, name))
        hbm_us = 1e6 * cost["bytes"] / peaks["hbm_bytes_per_s"]
        rec[name] = {"us_per_call": us, "least_bytes": cost["bytes"],
                     "hbm_us": hbm_us, "hbm_share": hbm_us / us,
                     "top_ops": ops}
    rec["sub_layer"] = {
        way: {"us": sum(rec[k]["us_per_call"] for k in keys),
              "hbm_share": sum(rec[k]["hbm_us"] for k in keys)
              / sum(rec[k]["us_per_call"] for k in keys)}
        for way, keys in (("forward", ("read", "write")),
                          ("backward", ("read.vjp", "write.vjp")))}
    line = json.dumps(rec)
    print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as fh:
            fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
