"""Smoke test: 1-hidden-layer MLP trained through the client/server path.

Reference parity: examples/smoke_testing/simple.py (loss printed per step;
client runs without accelerators — the server owns the devices). Set
SERVER_IP/SERVER_PORT to use a running server, or run with --local to spawn
one on this machine.
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.abspath(_os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "..", "..")))

import argparse
import signal

import jax
import jax.numpy as jnp
import optax


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--local", action="store_true",
                        help="spawn a local server")
    parser.add_argument("--steps", type=int, default=10)
    args = parser.parse_args()

    from tepdist_tpu.client.session import TepdistSession
    from tepdist_tpu.models import mlp
    from tepdist_tpu.rpc.local_server import (
        pin_client_to_cpu,
        server_platform,
        spawn_local_server,
    )

    # One process per chip: the client stays on the CPU, the server it
    # spawns is told to own the chip.
    pin_client_to_cpu()
    proc = None
    address = None
    if args.local:
        proc, port = spawn_local_server(server_platform())
        address = f"127.0.0.1:{port}"

    k = jax.random.PRNGKey(0)
    params = mlp.init_mlp(k, din=32, dh=64, dout=8)
    x = jax.random.normal(k, (256, 32))
    y = jnp.ones((256, 8))
    tx = optax.sgd(0.1)

    def step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(mlp.mlp_loss)(params, x, y)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    sess = TepdistSession(address)
    sess.client.wait_ready()
    info = sess.client.ping()
    print(f"server: {info['n_devices']} {info['platform']} devices")
    summary = sess.compile_train_step(step, params, tx.init(params), x, y)
    print(f"plan: {summary}")
    for i in range(args.steps):
        loss = sess.run(x, y)
        print(f"step {i}: loss = {loss:.6f}")
    sess.close()
    if proc is not None:
        proc.send_signal(signal.SIGKILL)
        proc.wait()


if __name__ == "__main__":
    main()
