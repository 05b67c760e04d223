"""Collective pipeline across PROCESSES: the stage axis spans a 2-process
jax.distributed fleet, so ppermute stage hops cross the inter-process
transport (the DCN analogue) inside one XLA program — single-program
multi-host pipeline parallelism."""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


WORKER = textwrap.dedent("""
    import os, sys
    proc_id = int(sys.argv[1]); port = sys.argv[2]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=2,
                               process_id=proc_id)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from tepdist_tpu.ops.collective_pipeline import (
        collective_pipeline, sequential_reference)

    devs = jax.devices()
    assert len(devs) == 4  # 2 local x 2 processes
    mesh = Mesh(np.array(devs), axis_names=("stage",))

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    k = jax.random.PRNGKey(0)
    stacked = {"w": jax.random.normal(k, (4, 16, 16)) * 0.5}
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 16))
    sh = NamedSharding(mesh, P("stage"))
    stacked_sharded = {"w": jax.device_put(stacked["w"], sh)}

    pipelined = jax.jit(collective_pipeline(stage_fn, mesh))
    out = pipelined(stacked_sharded, x)
    ref = sequential_reference(stage_fn, stacked, x)
    got = np.asarray(jax.device_get(out))
    exp = np.asarray(jax.device_get(ref))
    np.testing.assert_allclose(got, exp, rtol=1e-5, atol=1e-6)
    print(f"[p{proc_id}] multihost pipeline ok; max diff "
          f"{np.abs(got - exp).max():.2e}", flush=True)
""")


def test_collective_pipeline_across_processes(tmp_path):
    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + ":" + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen([sys.executable, str(script), str(i),
                               str(port)],
                              env=env, cwd=root, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT)
             for i in range(2)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        outs.append(out.decode())
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        assert "multihost pipeline ok" in out
