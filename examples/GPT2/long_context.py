"""Long-context GPT-2 training with sequence parallelism.

First-class capability absent in the reference (SURVEY §5.7): the sequence
axis is sharded over a 'seq' mesh axis; attention runs as ring attention
(ppermute + online-softmax merge over ICI) or Ulysses (head<->sequence
all-to-alls). Per-device activation memory scales 1/P with sequence length.
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.abspath(_os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "..", "..")))

import argparse
import time

import jax
import numpy as np
import optax


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="test")
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--seq", type=int, default=2048)
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument("--impl", choices=["ring", "ulysses"],
                        default="ring")
    args = parser.parse_args()

    from jax.sharding import Mesh
    from tepdist_tpu.core.compile_cache import configure_compile_cache
    from tepdist_tpu.models import gpt2
    from tepdist_tpu.ops.ring_attention import ring_attention
    from tepdist_tpu.ops.ulysses import ulysses_attention

    configure_compile_cache()

    cfg = gpt2.CONFIGS[args.config]
    devices = jax.devices()
    if args.impl == "ulysses":
        # Ulysses needs heads % mesh == 0: use the largest valid divisor.
        n = len(devices)
        while cfg.n_head % n:
            n -= 1
        devices = devices[:n]
    mesh = Mesh(np.array(devices), axis_names=("seq",))
    print(f"sequence mesh: {len(devices)} devices, seq len {args.seq}")

    if args.impl == "ring":
        def attn_impl(q, k, v):
            return ring_attention(q, k, v, mesh, causal=True)
    else:
        def attn_impl(q, k, v):
            return ulysses_attention(q, k, v, mesh, causal=True)

    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    seq = min(args.seq, cfg.n_ctx)
    tokens = gpt2.fake_batch(cfg, args.batch, seq)
    tx = optax.adamw(1e-4)
    opt = tx.init(params)

    @jax.jit
    def step(p, o, t):
        l, g = jax.value_and_grad(
            lambda p: gpt2.loss_fn(p, t, cfg, attn_impl=attn_impl))(p)
        u, o = tx.update(g, o, p)
        return l, optax.apply_updates(p, u), o

    l, params, opt = step(params, opt, tokens)  # compile
    print(f"compile + step 0: loss={float(l):.4f}")
    for i in range(args.steps):
        t0 = time.perf_counter()
        l, params, opt = step(params, opt, tokens)
        l = float(l)
        print(f"step {i+1}: loss={l:.4f} ({time.perf_counter()-t0:.3f}s)")


if __name__ == "__main__":
    main()
