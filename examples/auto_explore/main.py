"""Fully-automatic planning example: no topology, the SERVICE decides.

The defining TePDist behavior (reference: exploration mode inside
BuildExecutionPlan — service/parallel/auto_parallel.cc:236 invoked from
service_rt.cc:218-308): the client ships a loss and an optimizer spec
with NO mesh axes; the server enumerates SPMD meshes, sequence-parallel
meshes, and pipeline stage cuts, prices them with the Evaluator, compiles
the winner (pipeline winners run the task-graph runtime server-side), and
returns the ranked candidate table.

Run (spawns a local server):
    python examples/auto_explore/main.py --steps 5

Force the pipeline-winning regime (emulates a DCN-bound, memory-tight
cluster) with --regime pipeline.
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.abspath(_os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "..", "..")))

import argparse
import os
import signal

import jax
import jax.numpy as jnp
import optax


def main() -> None:
    parser = argparse.ArgumentParser("auto_explore")
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument("--regime", choices=["auto", "pipeline"],
                        default="auto",
                        help="'pipeline' sets cost-model knobs emulating a "
                             "DCN-bound memory-tight cluster so the stage "
                             "cut wins the exploration")
    args = parser.parse_args()

    from tepdist_tpu.client.session import TepdistSession
    from tepdist_tpu.optim import optimizer_spec
    from tepdist_tpu.rpc.client import TepdistClient
    from tepdist_tpu.rpc.local_server import (
        pin_client_to_cpu,
        server_platform,
        spawn_local_server,
    )

    # One process per chip: the client stays on the CPU, the server it
    # spawns is told to own the chip(s).
    pin_client_to_cpu()
    extra_env = {}
    if args.regime == "pipeline":
        extra_env = {"HBM_GB": "0.01", "ICI_BANDWIDTH": "0.05",
                     "COMM_OVERLAP": "0.0"}
    platform = server_platform()
    if platform == "cpu" and "XLA_FLAGS" not in os.environ:
        # A CPU rehearsal needs several devices for the search to have
        # layouts to choose from.
        extra_env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc, port = spawn_local_server(platform, extra_env)

    c = TepdistClient(f"127.0.0.1:{port}")
    c.wait_ready(60)
    c.close()

    depth, width, batch = 8, 512, 16

    def loss_fn(params, x, y):
        h = x
        for i in range(depth):
            h = jax.nn.relu(h @ params[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    k = jax.random.PRNGKey(0)
    scale = (2.0 / width) ** 0.5
    params = {f"w{i}": jax.random.normal(
        jax.random.fold_in(k, i), (width, width)) * scale
        for i in range(depth)}
    x = jax.random.normal(jax.random.fold_in(k, 100), (batch, width))
    y = jax.random.normal(jax.random.fold_in(k, 101), (batch, width))

    try:
        sess = TepdistSession(f"127.0.0.1:{port}")   # NO mesh_axes
        summary = sess.compile_training(
            loss_fn, optax.sgd(0.01), params, x, y,
            num_micro_batches=4,
            optimizer_spec=optimizer_spec("sgd", learning_rate=0.01))
        explored = summary.get("explored", {})
        print(f"winner: {explored.get('winner')}  "
              f"(plan kind: {summary.get('kind', 'spmd')}, "
              f"axes: {summary.get('axes')})")
        print(f"{'kind':>9} {'config':<28} {'duration_s':>12} "
              f"{'mem_ok':>6}")
        for c in explored.get("candidates", [])[:10]:
            mark = " <== winner" if c["winner"] else ""
            print(f"{c['kind']:>9} {c['config']:<28} "
                  f"{c['duration_s']:>12.4e} "
                  f"{str(c['memory_feasible']):>6}{mark}")
        for i in range(args.steps):
            print(f"step {i}: loss = {sess.run(x, y):.6f}")
        sess.close()
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()


if __name__ == "__main__":
    main()
