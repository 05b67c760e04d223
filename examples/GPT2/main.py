"""GPT-2 auto-parallel training driver.

Reference parity: examples/GPT2/main.py with the {117M,345M,1.5B,175B}.json
configs and fake-input benchmark mode (FAKE_INPUT). Plans automatically over
all visible devices: DP/TP via the cost planner, optional pipeline stages
via --num_stages (PIPELINE par type), gradient accumulation via
--num_micro_batches.
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.abspath(_os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "..", "..")))

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import optax


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="117M",
                        help="config name or path to json")
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=512)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--num_stages", type=int, default=0)
    parser.add_argument("--pipeline", choices=["taskgraph", "collective"],
                        default="taskgraph",
                        help="taskgraph: 1F1B multi-program runtime; "
                             "collective: single-jit shard_map+ppermute")
    parser.add_argument("--num_micro_batches", type=int, default=1)
    parser.add_argument("--mode", default="cost", choices=["cost", "rule"])
    parser.add_argument("--data", default="",
                        help="path to a packed token file "
                             "(tepdist_tpu.data.pack_token_file); default "
                             "is fake input (reference FAKE_INPUT mode)")
    args = parser.parse_args()

    from tepdist_tpu.core.compile_cache import configure_compile_cache
    from tepdist_tpu.core.mesh import MeshTopology
    from tepdist_tpu.models import gpt2
    from tepdist_tpu.parallel.auto_parallel import auto_parallel

    configure_compile_cache()

    if os.path.exists(args.config):
        with open(args.config) as f:
            raw = json.load(f)
        cfg = gpt2.GPT2Config(
            vocab_size=raw.get("n_vocab", 50257),
            n_ctx=raw.get("n_ctx", 1024),
            n_embd=raw["n_embd"],
            n_layer=raw["n_layer"],
            n_head=raw["n_head"],
        )
    else:
        cfg = gpt2.CONFIGS[args.config]
    print(f"GPT-2 {args.config}: ~{gpt2.num_params(cfg)/1e6:.0f}M params")

    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    if args.data:
        from tepdist_tpu.data import TokenDataset
        dataset = TokenDataset(args.data)
        batches = dataset.batches(args.batch, args.seq, seed=0)
        tokens = next(batches)
        print(f"dataset: {len(dataset):,} tokens from {args.data}")
    else:
        batches = None
        tokens = gpt2.fake_batch(cfg, args.batch, args.seq)
    tx = optax.adamw(1e-4, b1=0.9, b2=0.95, weight_decay=0.01)

    if args.num_stages > 1 and args.pipeline == "collective":
        import numpy as np
        from jax.sharding import Mesh

        S = args.num_stages
        if len(jax.devices()) < S:
            raise SystemExit(f"--num_stages {S} needs {S} devices, "
                             f"have {len(jax.devices())}")
        mesh = Mesh(np.array(jax.devices()[:S]), axis_names=("stage",))
        embed, stacked = gpt2.shard_stacked_for_stages(params, cfg, mesh)
        state = (embed, stacked)
        opt = tx.init(state)
        M = args.num_micro_batches if args.num_micro_batches > 0 else 2
        if args.batch % M:
            raise SystemExit(f"--batch {args.batch} not divisible by "
                             f"--num_micro_batches {M}")

        @jax.jit
        def cstep(state, opt, tokens):
            def loss(state):
                e, b = state
                return gpt2.pipelined_loss_fn(e, b, tokens, cfg, mesh, M)
            l, g = jax.value_and_grad(loss)(state)
            u, opt = tx.update(g, opt, state)
            return l, optax.apply_updates(state, u), opt

        l, state, opt = cstep(state, opt, tokens)
        print(f"collective pipeline: S={S} M={M} compile+step0 "
              f"loss={float(l):.4f}")
        for i in range(args.steps):
            t0 = time.perf_counter()
            if batches is not None:
                tokens = next(batches)
            l, state, opt = cstep(state, opt, tokens)
            l = float(l)
            print(f"step {i}: loss={l:.4f} "
                  f"({(time.perf_counter()-t0)*1e3:.1f} ms)")
        return

    opt_state = tx.init(params)
    if args.num_stages > 1:
        from tepdist_tpu.parallel.pipeline import plan_pipeline
        from tepdist_tpu.runtime.executor import PipelineExecutable

        prog = plan_pipeline(
            lambda p, t: gpt2.loss_fn(p, t, cfg),
            args.num_stages, max(args.num_micro_batches, 2), params, tokens)
        exe = PipelineExecutable(prog, optimizer=tx)
        exe.load_variables(params)
        print(f"pipeline: stages={args.num_stages} "
              f"flops={['%.2e' % f for f in prog.stage_flops()]}")
        for i in range(args.steps):
            t0 = time.perf_counter()
            if batches is not None:
                tokens = next(batches)
            loss = exe.step(tokens)
            dt = time.perf_counter() - t0
            print(f"step {i}: loss={loss:.4f} ({dt*1e3:.1f} ms)")
        return

    n = len(jax.devices())
    topo = MeshTopology([("data", n)])

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: gpt2.loss_fn(p, tokens, cfg))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

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
    _ = float(jax.device_get(outs[0]))  # compile + warm
    n_state_out = len(outs) - 1
    token_sharding = plan.input_shardings()[-1]
    prefetch = None
    if batches is not None:
        from tepdist_tpu.data import DevicePrefetcher
        prefetch = DevicePrefetcher(batches, shardings=token_sharding)
    for i in range(args.steps):
        t0 = time.perf_counter()
        flat = list(outs[1:]) + flat[n_state_out:]
        if prefetch is not None:
            flat[-1] = next(prefetch)
        outs = step(*flat)
        loss = float(jax.device_get(outs[0]))
        dt = time.perf_counter() - t0
        tput = args.batch * args.seq / dt
        print(f"step {i}: loss={loss:.4f} ({dt*1e3:.1f} ms, "
              f"{tput:.0f} tok/s)")


if __name__ == "__main__":
    main()
