"""Server-side fully-automatic planning (VERDICT r4 #1).

Reference parity: a client ships its module and the SERVICE runs the
exploration — enumerating SPMD / seq / pipeline-stage proposals, planning
each, keeping the Evaluator-minimal one — inside BuildExecutionPlan
(reference: service/parallel/auto_parallel.cc:236 RunExplorationlMode,
invoked from service/service_rt.cc:218-308). A ``session.compile_training``
caller with NO topology gets the fully automatic plan, not DP-by-default.
"""

import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tepdist_tpu.client.session import TepdistSession
from tepdist_tpu.optim import optimizer_spec
from tepdist_tpu.rpc.client import TepdistClient


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_server(extra_env=None, task_index=0):
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["TEPDIST_CKPT_DIR"] = tempfile.mkdtemp(prefix="tepdist_ckpt_")
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "tepdist_tpu.rpc.server",
         "--port", str(port), "--platform", "cpu",
         "--task_index", str(task_index)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    client = TepdistClient(f"127.0.0.1:{port}")
    try:
        client.wait_ready(timeout=60.0)
    finally:
        client.close()
    return port, proc


def _kill(proc):
    proc.send_signal(signal.SIGKILL)
    proc.wait()


def _mlp(depth=2, width=64, batch=64):
    def loss_fn(params, x, y):
        h = x
        for i in range(depth):
            h = jax.nn.relu(h @ params[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    k = jax.random.PRNGKey(0)
    # He init keeps deep relu chains variance-stable (a depth-8 chain at
    # scale 0.1 explodes within 2 SGD steps and the test would compare
    # diverging float noise).
    scale = (2.0 / width) ** 0.5
    params = {f"w{i}": jax.random.normal(
        jax.random.fold_in(k, i), (width, width)) * scale
        for i in range(depth)}
    x = jax.random.normal(jax.random.fold_in(k, 100), (batch, width))
    y = jax.random.normal(jax.random.fold_in(k, 101), (batch, width))
    return loss_fn, params, x, y


def _local_sgd_trajectory(loss_fn, params, x, y, lr, steps):
    tx = optax.sgd(lr)
    p, s = params, tx.init(params)
    out = []
    for _ in range(steps):
        l, g = jax.value_and_grad(loss_fn)(p, x, y)
        u, s = tx.update(g, s, p)
        p = optax.apply_updates(p, u)
        out.append(float(l))
    return out, p


def test_no_topology_session_gets_explored_plan():
    """The VERDICT 'done' bar: compile_training with NO mesh_axes on an
    8-device server runs the server-side exploration — the summary lists
    the explored candidates with costs, and the RPC trajectory matches
    the in-process plan_training numerics exactly."""
    loss_fn, params, x, y = _mlp()
    port, proc = _spawn_server()
    try:
        sess = TepdistSession(f"127.0.0.1:{port}", mesh_axes=())
        summary = sess.compile_training(
            loss_fn, optax.sgd(0.1), params, x, y,
            optimizer_spec=optimizer_spec("sgd", learning_rate=0.1))
        assert "explored" in summary, summary
        cands = summary["explored"]["candidates"]
        assert len(cands) > 1
        assert any(c["winner"] for c in cands)
        assert {"duration_s", "kind", "config"} <= set(cands[0])
        rpc_losses = [sess.run(x, y) for _ in range(3)]
        sess.close()
    finally:
        _kill(proc)

    # Reference BEFORE plan_training: the in-process plan DONATES the
    # caller's param buffers (documented ownership transfer).
    ref_losses, _ = _local_sgd_trajectory(loss_fn, params, x, y, 0.1, 3)

    # In-process explore path: same candidate space, same winner, same
    # numerics (full-batch SGD at M=1 is exact either way).
    from tepdist_tpu.train import plan_training

    plan = plan_training(loss_fn, optax.sgd(0.1), params, x, y,
                         num_micro_batches=1, explore=True)
    local_losses = [plan.step(x, y) for _ in range(3)]
    np.testing.assert_allclose(rpc_losses, local_losses, rtol=1e-5)
    np.testing.assert_allclose(rpc_losses, ref_losses, rtol=1e-5)


# The comm-dominated / memory-tight regime (emulates a DCN-bound cluster
# whose per-device memory cannot replicate the model): pipeline stage
# cuts win the exploration argmin.
_PIPELINE_ENV = {"HBM_GB": "0.01", "ICI_BANDWIDTH": "0.05",
                 "COMM_OVERLAP": "0.0"}


def test_pipeline_winner_executes_over_rpc():
    """When the stage cut wins, BuildExecutionPlan materializes the
    task-graph pipeline runtime behind the plan handle — the no-topology
    client trains through it transparently and can fetch state back."""
    loss_fn, params, x, y = _mlp(depth=8, width=512, batch=16)
    port, proc = _spawn_server(_PIPELINE_ENV)
    try:
        sess = TepdistSession(f"127.0.0.1:{port}", mesh_axes=())
        summary = sess.compile_training(
            loss_fn, optax.sgd(0.01), params, x, y,
            num_micro_batches=4,
            optimizer_spec=optimizer_spec("sgd", learning_rate=0.01))
        assert summary.get("kind") == "pipeline", summary
        assert summary["num_stages"] >= 2
        assert "explored" in summary
        rpc_losses = [sess.run(x, y) for _ in range(3)]
        fetched_params = sess.params()
        sess.close()
    finally:
        _kill(proc)

    # GA over equal micro batches of a mean loss == the full-batch
    # gradient, so the pipelined trajectory matches plain SGD.
    ref_losses, ref_params = _local_sgd_trajectory(
        loss_fn, params, x, y, 0.01, 3)
    np.testing.assert_allclose(rpc_losses, ref_losses, rtol=1e-4)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(fetched_params[k]), np.asarray(ref_params[k]),
            rtol=1e-4, atol=1e-6)


def test_explicit_mesh_axes_skip_exploration():
    """A session WITH a topology keeps the old contract: no exploration,
    the given mesh is planned directly."""
    loss_fn, params, x, y = _mlp()
    port, proc = _spawn_server()
    try:
        sess = TepdistSession(f"127.0.0.1:{port}",
                              mesh_axes=[("data", 8)])
        summary = sess.compile_training(
            loss_fn, optax.sgd(0.1), params, x, y)
        assert "explored" not in summary
        assert summary["axes"] == [["data", 8]]
        losses = [sess.run(x, y) for _ in range(2)]
        sess.close()
    finally:
        _kill(proc)
    ref_losses, _ = _local_sgd_trajectory(loss_fn, params, x, y, 0.1, 2)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)


def test_pipeline_winner_dispatches_over_worker_fleet():
    """When the master has a registered worker cluster (InitMeshTopology)
    and the exploration picks a pipeline stage cut, BuildExecutionPlan
    dispatches the winner over the FLEET (DistributedPipelineSession:
    per-worker stage modules, raw-data activation hops) — the reference's
    service-compiled pipeline driving its workers
    (virtual_client.cc:776). The no-topology client trains through the
    master transparently."""
    from tepdist_tpu.rpc import protocol

    loss_fn, params, x, y = _mlp(depth=8, width=512, batch=16)
    ckpt_dir = tempfile.mkdtemp(prefix="tepdist_fleet_ckpt_")
    fleet_env = dict(_PIPELINE_ENV, TEPDIST_CKPT_DIR=ckpt_dir)
    m_port, m_proc = _spawn_server(fleet_env, task_index=0)
    s_port, s_proc = _spawn_server(fleet_env, task_index=1)
    try:
        # Register the 2-worker cluster on the MASTER (worker 0 = the
        # master itself, reached over loopback).
        mc = TepdistClient(f"127.0.0.1:{m_port}")
        mc.stub.call("InitMeshTopology", protocol.pack({
            "cluster_spec": {"workers": [
                {"ip": "127.0.0.1", "port": m_port, "device_ids": [0],
                 "task_index": 0},
                {"ip": "127.0.0.1", "port": s_port, "device_ids": [0],
                 "task_index": 1},
            ]}}))
        mc.close()
        sess = TepdistSession(f"127.0.0.1:{m_port}", mesh_axes=())
        summary = sess.compile_training(
            loss_fn, optax.sgd(0.01), params, x, y,
            num_micro_batches=4,
            optimizer_spec=optimizer_spec("sgd", learning_rate=0.01))
        assert summary.get("kind") == "pipeline", summary
        assert summary.get("fleet_workers") == 2, summary
        rpc_losses = [sess.run(x, y) for _ in range(3)]
        fetched_params = sess.params()
        # Fleet checkpoints fan out over the workers (per-worker shards
        # + per-stage optimizer slots): save, advance, restore, and the
        # post-restore trajectory must REPLAY the post-save one.
        sess.save()
        after_save = [sess.run(x, y) for _ in range(2)]
        sess.restore()
        replayed = [sess.run(x, y) for _ in range(2)]
        np.testing.assert_allclose(replayed, after_save, rtol=1e-4)
        sess.close()
    finally:
        _kill(m_proc)
        _kill(s_proc)

    ref_losses, ref_params = _local_sgd_trajectory(
        loss_fn, params, x, y, 0.01, 3)
    np.testing.assert_allclose(rpc_losses, ref_losses, rtol=1e-4)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(fetched_params[k]), np.asarray(ref_params[k]),
            rtol=1e-4, atol=1e-6)


def test_generate_reads_live_pipeline_weights():
    """compile_generate AFTER a pipeline-winner training: the generate
    plan is a read-only SPMD plan — it must see the pipeline runtime's
    LIVE weights (the sync-before-read invariant), not the store's
    initial copies, and stepping the training plan afterwards still
    works (read-only plans do not retire the runtime)."""
    loss_fn, params, x, y = _mlp(depth=8, width=512, batch=16)
    port, proc = _spawn_server(_PIPELINE_ENV)
    try:
        sess = TepdistSession(f"127.0.0.1:{port}", mesh_axes=())
        summary = sess.compile_training(
            loss_fn, optax.sgd(0.01), params, x, y,
            num_micro_batches=4,
            optimizer_spec=optimizer_spec("sgd", learning_rate=0.01))
        assert summary.get("kind") == "pipeline", summary
        losses = [sess.run(x, y) for _ in range(3)]

        def fwd(p, xx):
            h = xx
            for i in range(8):
                h = jax.nn.relu(h @ p[f"w{i}"])
            return h

        sess.compile_generate(fwd, params, x)
        out = sess.generate(x)
        # Training continues after the read-only plan compiled.
        more = sess.run(x, y)
        assert more < losses[-1]
        sess.close()
    finally:
        _kill(proc)

    _, ref_params = _local_sgd_trajectory(loss_fn, params, x, y, 0.01, 3)
    ref_out = np.asarray(jax.jit(lambda p, xx: fwd(p, xx))(ref_params, x))
    np.testing.assert_allclose(np.asarray(out), ref_out, rtol=1e-3,
                               atol=1e-5)


def test_explore_without_optimizer_spec_records_exclusions():
    """No optimizer_spec: the server cannot materialize pipeline/seq
    winners, so those kinds are EXCLUDED from the search — and the
    exclusion is recorded in the summary, never silent."""
    loss_fn, params, x, y = _mlp()
    port, proc = _spawn_server()
    try:
        sess = TepdistSession(f"127.0.0.1:{port}", mesh_axes=())
        summary = sess.compile_training(
            loss_fn, optax.sgd(0.1), params, x, y)
        explored = summary["explored"]
        assert set(explored.get("excluded_kinds", [])) == {"seq",
                                                           "pipeline"}
        assert "optimizer_spec" in explored.get("excluded_reason", "")
        losses = [sess.run(x, y) for _ in range(2)]
        assert losses[1] < losses[0]
        sess.close()
    finally:
        _kill(proc)


def test_superseded_pipeline_handle_refuses_steps():
    """A NEW state-writing plan retires the live pipeline runtime; the
    old handle must REFUSE further steps (training through a detached
    runtime would be invisible to every store reader), while the new
    plan trains normally."""
    loss_fn, params, x, y = _mlp(depth=8, width=512, batch=16)
    port, proc = _spawn_server(_PIPELINE_ENV)
    try:
        sess = TepdistSession(f"127.0.0.1:{port}", mesh_axes=())
        summary = sess.compile_training(
            loss_fn, optax.sgd(0.01), params, x, y,
            num_micro_batches=4,
            optimizer_spec=optimizer_spec("sgd", learning_rate=0.01))
        assert summary.get("kind") == "pipeline", summary
        old_handle = sess.handle
        first = sess.run(x, y)

        # Recompile (state-writing) — retires the pipeline runtime (its
        # trained state flushes to the store, then the new compile's
        # OWN initial transfers overwrite it: a fresh training session).
        sess2 = TepdistSession(f"127.0.0.1:{port}", mesh_axes=())
        sess2.compile_training(
            loss_fn, optax.sgd(0.01), params, x, y,
            num_micro_batches=4,
            optimizer_spec=optimizer_spec("sgd", learning_rate=0.01))
        np.testing.assert_allclose(sess2.run(x, y), first, rtol=1e-5)

        import grpc

        with pytest.raises(grpc.RpcError, match="superseded"):
            sess.client.execute_plan(
                old_handle,
                inline_args={8: np.asarray(x), 9: np.asarray(y)})
        sess.close()
        sess2.close()
    finally:
        _kill(proc)
