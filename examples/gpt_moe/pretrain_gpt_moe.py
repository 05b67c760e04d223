"""GPT-MoE expert-parallel training.

Reference parity: examples/gpt_moe/pretrain_gpt_moe.py — top-2 gated
GShard-style MoE whose dispatch/combine einsums become ICI all-to-alls when
the expert dim is sharded over the 'expert' mesh axis."""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.abspath(_os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "..", "..")))

import argparse
import time

import jax
import optax


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="base-8e")
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=256)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--expert_parallel", type=int, default=0,
                        help="devices on the expert axis (0 = all)")
    args = parser.parse_args()

    from tepdist_tpu.core.compile_cache import configure_compile_cache
    from tepdist_tpu.core.dist_spec import DimStrategy
    from tepdist_tpu.core.mesh import MeshTopology
    from tepdist_tpu.models import gpt2, gpt_moe
    from tepdist_tpu.parallel.auto_parallel import auto_parallel

    configure_compile_cache()

    cfg = gpt_moe.CONFIGS[args.config]
    params = gpt_moe.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg.base, args.batch, args.seq)
    tx = optax.adamw(1e-4)
    opt_state = tx.init(params)

    n = len(jax.devices())
    ep = args.expert_parallel or min(n, cfg.num_experts)
    dp = n // ep
    topo = MeshTopology([("data", dp), ("expert", ep)])

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: gpt_moe.loss_fn(p, tokens, cfg))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    # Annotate expert weights onto the expert axis (planner pins).
    leaves = jax.tree_util.tree_leaves(params)
    annotations = {}
    for i, leaf in enumerate(leaves):
        if leaf.ndim == 3 and leaf.shape[0] == cfg.num_experts:
            annotations[i] = {"expert": DimStrategy.split_on(0, ep)}
    n_state = len(jax.tree_util.tree_leaves((params, opt_state)))
    plan = auto_parallel(train_step, topo, params, opt_state, tokens,
                         annotations=annotations,
                         state_alias={1 + k: k for k in range(n_state)})
    step = plan.executable()
    print(f"planned over {topo}; {len(annotations)} expert weights pinned")
    flat, _ = jax.tree_util.tree_flatten(((params, opt_state, tokens), {}))
    flat = [jax.device_put(v, s)
            for v, s in zip(flat, plan.input_shardings())]
    outs = step(*flat)
    _ = float(jax.device_get(outs[0]))
    for i in range(args.steps):
        t0 = time.perf_counter()
        flat = list(outs[1:]) + flat[len(outs) - 1:]
        outs = step(*flat)
        loss = float(jax.device_get(outs[0]))
        print(f"step {i}: loss={loss:.4f} "
              f"({time.perf_counter()-t0:.3f}s)")


if __name__ == "__main__":
    main()
