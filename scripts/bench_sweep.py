"""One-off throughput probe for a GPT-2 config on the current backend.

Usage: python scripts/bench_sweep.py --config 1.5B --batch 8 --micro 8 \
          --attn flash --remat --opt adamw_bf16 --steps 10
Prints tokens/s/chip over windows ended by ``block_until_ready``; the peak
for the MFU is looked up by ``device_kind`` and an unknown kind is an
error."""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="117M")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--attn", default="einsum")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--scan", action="store_true",
                    help="scan-over-layers stacked-param form")
    ap.add_argument("--opt", default="adamw",
                    choices=["adamw", "adamw_bf16", "adafactor"])
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    import optax

    from tepdist_tpu.models import gpt2
    from tepdist_tpu.train import plan_training

    cfg = dataclasses.replace(gpt2.CONFIGS[args.config], attn=args.attn,
                              remat=args.remat)
    if args.scan:
        params = gpt2.stacked_init_params(cfg, jax.random.PRNGKey(0))
        loss = lambda p, t: gpt2.loss_fn_stacked(p, t, cfg)
    else:
        params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
        loss = lambda p, t: gpt2.loss_fn(p, t, cfg)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    tokens = gpt2.fake_batch(cfg, args.batch, args.seq)
    if args.opt == "adamw":
        tx = optax.adamw(1e-4, b1=0.9, b2=0.95, weight_decay=0.01)
    elif args.opt == "adamw_bf16":
        from tepdist_tpu.optim import adamw_bf16
        tx = adamw_bf16(1e-4, b1=0.9, b2=0.95, weight_decay=0.01)
    else:
        tx = optax.adafactor(1e-3)

    t0 = time.perf_counter()
    plan = plan_training(loss, tx, params, tokens,
                         num_micro_batches=args.micro)
    t_plan = time.perf_counter() - t0
    print(f"planner: {t_plan:.1f}s  params={n_params/1e6:.0f}M", flush=True)

    t0 = time.perf_counter()
    loss = plan.step(tokens)
    print(f"compile+step0: {time.perf_counter()-t0:.1f}s loss={loss:.4f}",
          flush=True)
    loss = plan.step(tokens)  # steady state

    # Async stepping (the bench.py pattern): drive the jitted step_fn
    # directly, thread state without host sync, one barrier per window.
    step_fn = plan._step_fn
    state = plan._state
    batch = [jax.device_put(v, s) for v, s in
             zip(jax.tree_util.tree_leaves((tokens,)),
                 plan._batch_shardings)]
    n_state = len(state)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(args.steps):
            outs = step_fn(*state, *batch)
            state = list(outs[1:1 + n_state])
        jax.block_until_ready(outs)
        dt = time.perf_counter() - t0
        loss = float(outs[0])
        best = dt if best is None else min(best, dt)
    n_dev = len(jax.devices())
    tps = args.batch * args.seq * args.steps / best / n_dev
    flops = 6 * n_params * args.batch * args.seq * args.steps
    from tepdist_tpu.parallel.performance_utils import (
        chip_spec_for_device_kind,
    )
    peak = chip_spec_for_device_kind(
        jax.devices()[0].device_kind).bf16_tflops * 1e12
    mfu = flops / best / n_dev / peak
    print(f"RESULT config={args.config} attn={args.attn} remat={args.remat} "
          f"opt={args.opt} batch={args.batch} micro={args.micro} "
          f"seq={args.seq}: {tps:,.0f} tok/s/chip  param-MFU={mfu:.1%} "
          f"loss={loss:.4f}", flush=True)


if __name__ == "__main__":
    main()
