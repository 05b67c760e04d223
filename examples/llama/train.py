"""llama-family auto-parallel training driver (surplus over the reference's
four example families; model: tepdist_tpu/models/llama.py — RMSNorm/SwiGLU/
RoPE/GQA, optional pallas flash attention).

Plans automatically over all visible devices like examples/GPT2/main.py.
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.abspath(_os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "..", "..")))

import argparse
import dataclasses
import time

import jax
import optax


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="test",
                        help="config name (test/1B/7B)")
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument("--attn", default="einsum",
                        choices=["einsum", "flash"])
    parser.add_argument("--mode", default="cost", choices=["cost", "rule"])
    parser.add_argument("--data", default="",
                        help="packed token file (default: random tokens)")
    args = parser.parse_args()

    from tepdist_tpu.core.compile_cache import configure_compile_cache
    from tepdist_tpu.core.mesh import MeshTopology
    from tepdist_tpu.models import llama
    from tepdist_tpu.parallel.auto_parallel import auto_parallel

    configure_compile_cache()

    cfg = dataclasses.replace(llama.CONFIGS[args.config], attn=args.attn)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"llama {args.config}: {n/1e6:.0f}M params, attn={cfg.attn}")

    if args.data:
        from tepdist_tpu.data import TokenDataset
        ds = TokenDataset(args.data)
        batches = ds.batches(args.batch, args.seq, seed=0)
        tokens = next(batches)
    else:
        batches = None
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.seq + 1), 0,
            cfg.vocab_size)

    tx = optax.adamw(1e-4, b1=0.9, b2=0.95, weight_decay=0.01)
    opt_state = tx.init(params)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, cfg))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    topo = MeshTopology([("data", len(jax.devices()))])
    n_state = len(jax.tree_util.tree_leaves((params, opt_state)))
    t0 = time.perf_counter()
    plan = auto_parallel(train_step, topo, params, opt_state, tokens,
                         mode=args.mode,
                         state_alias={1 + k: k for k in range(n_state)})
    step = plan.executable()
    print(f"planned in {time.perf_counter()-t0:.2f}s over {topo}")

    flat, _ = jax.tree_util.tree_flatten(((params, opt_state, tokens), {}))
    flat = [jax.device_put(v, s)
            for v, s in zip(flat, plan.input_shardings())]
    outs = step(*flat)
    _ = float(jax.device_get(outs[0]))
    n_state_out = len(outs) - 1
    for i in range(args.steps):
        t0 = time.perf_counter()
        flat = list(outs[1:]) + flat[n_state_out:]
        if batches is not None:
            flat[-1] = jax.device_put(next(batches),
                                      plan.input_shardings()[-1])
        outs = step(*flat)
        loss = float(jax.device_get(outs[0]))
        dt = time.perf_counter() - t0
        print(f"step {i}: loss={loss:.4f} ({dt*1e3:.1f} ms, "
              f"{args.batch*args.seq/dt:.0f} tok/s)")


if __name__ == "__main__":
    main()
