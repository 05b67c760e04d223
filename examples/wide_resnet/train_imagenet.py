"""WideResNet data-parallel benchmark with fake input.

Reference parity: examples/wide_resnet/train_imagenet.py (model_type 0-6,
fake-data benchmark only — reference README: "only for benchmark ... fake
data")."""

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
    parser.add_argument("--model_type", type=int, default=0)
    parser.add_argument("--batch", type=int, default=32)
    parser.add_argument("--image_size", type=int, default=224)
    parser.add_argument("--steps", type=int, default=10)
    args = parser.parse_args()

    from tepdist_tpu.core.compile_cache import configure_compile_cache
    from tepdist_tpu.core.mesh import MeshTopology
    from tepdist_tpu.models import wide_resnet as wrn
    from tepdist_tpu.parallel.auto_parallel import auto_parallel

    configure_compile_cache()

    cfg = wrn.CONFIGS[args.model_type]
    params = wrn.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params)
                   if x is not None)
    print(f"WRN model_type={args.model_type}: {n_params/1e6:.0f}M params")
    images, labels = wrn.fake_batch(cfg, args.batch, args.image_size)
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def train_step(params, opt_state, images, labels):
        loss, grads = jax.value_and_grad(
            lambda p: wrn.loss_fn(p, images, labels, cfg))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    n = len(jax.devices())
    n_state = len(jax.tree_util.tree_leaves((params, opt_state)))
    plan = auto_parallel(train_step, MeshTopology([("data", n)]),
                         params, opt_state, images, labels,
                         state_alias={1 + k: k for k in range(n_state)})
    step = plan.executable()
    flat, _ = jax.tree_util.tree_flatten(
        ((params, opt_state, images, labels), {}))
    flat = [jax.device_put(v, s)
            for v, s in zip(flat, plan.input_shardings())]
    outs = step(*flat)
    _ = float(jax.device_get(outs[0]))
    for i in range(args.steps):
        t0 = time.perf_counter()
        flat = list(outs[1:]) + flat[len(outs) - 1:]
        outs = step(*flat)
        loss = float(jax.device_get(outs[0]))
        dt = time.perf_counter() - t0
        print(f"step {i}: loss={loss:.4f} "
              f"({args.batch/dt:.1f} images/s)")


if __name__ == "__main__":
    main()
