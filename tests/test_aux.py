"""Auxiliary component tests: liveness optimizer, run_jaxpr tool, async
session, planner scalability."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_liveness_optimizer_duplicates_broadcasts():
    from tepdist_tpu.graph.jaxpr_graph import trace_graph
    from tepdist_tpu.parallel.liveness import optimize_liveness
    from jax.extend.core import jaxpr_as_fun
    from jax.extend import core as jexcore

    def f(x):
        ones = jnp.ones((256, 256))  # broadcast with far-apart consumers
        a = x + ones
        for _ in range(40):
            a = jnp.tanh(a @ jnp.eye(256) * 0.1 + 0.5)
        return (a + ones).sum()

    x = jnp.zeros((256, 256))
    graph, _, _ = trace_graph(f, x)
    opt = optimize_liveness(graph, min_range=16, min_bytes=1024)
    # Equation count grew (duplication happened) OR graph unchanged if the
    # tracer already sunk the broadcasts; either way numerics must hold.
    out_ref = jaxpr_as_fun(graph.closed)(x)
    out_opt = jaxpr_as_fun(
        jexcore.ClosedJaxpr(opt.jaxpr, opt.closed.consts))(x)
    np.testing.assert_allclose(np.asarray(out_ref[0]),
                               np.asarray(out_opt[0]), rtol=1e-6)
    if len(opt.nodes) > len(graph.nodes):
        # At least one broadcast duplicated.
        n_bcast_ref = sum(1 for n in graph.nodes
                          if n.prim == "broadcast_in_dim")
        n_bcast_opt = sum(1 for n in opt.nodes
                          if n.prim == "broadcast_in_dim")
        assert n_bcast_opt > n_bcast_ref


def test_run_jaxpr_tool(tmp_path):
    from tepdist_tpu.rpc.jaxpr_serde import serialize_closed_jaxpr

    def f(x, w):
        return jax.nn.relu(x @ w).sum()

    closed = jax.make_jaxpr(f)(jnp.zeros((4, 8)), jnp.zeros((8, 2)))
    path = tmp_path / "mod.bin"
    path.write_bytes(serialize_closed_jaxpr(closed))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "run_jaxpr.py"),
         str(path), "--platform", "cpu"],
        capture_output=True, text=True, env=env, cwd=root, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "out[0]" in out.stdout and "finite=True" in out.stdout


def test_planner_scales_to_345m():
    # Reference claim: planner handles tens of thousands of instructions.
    # GPT-2 345M grad graph (~6k nodes) must plan in bounded time.
    import time

    from tepdist_tpu.core.mesh import MeshTopology
    from tepdist_tpu.graph.jaxpr_graph import trace_graph
    from tepdist_tpu.models import gpt2
    from tepdist_tpu.parallel.auto_parallel import plan_axes

    cfg = gpt2.CONFIGS["345M"]
    params = jax.eval_shape(lambda k: gpt2.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((8, 513), jnp.int32)

    def loss(p, t):
        return gpt2.loss_fn(p, t, cfg)

    graph, _, _ = trace_graph(jax.value_and_grad(loss), params, tokens)
    assert len(graph.nodes) > 3000
    t0 = time.time()
    strategies = plan_axes(graph, MeshTopology([("data", 8)]))
    dt = time.time() - t0
    assert dt < 60, f"planner too slow: {dt:.1f}s"
    assert strategies[0].ilp_status in ("ilp", "greedy")


def test_gpt2_example_json_config(tmp_path):
    """examples/GPT2/main.py accepts reference-style json configs."""
    import json
    import subprocess

    cfg = {"n_vocab": 256, "n_ctx": 64, "n_embd": 64, "n_layer": 2,
           "n_head": 4, "input": "fake_input"}
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(cfg))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "examples/GPT2/main.py", "--config", str(path),
         "--batch", "8", "--seq", "32", "--steps", "1"],
        capture_output=True, text=True, env=env, cwd=root, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "loss=" in out.stdout
