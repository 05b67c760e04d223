"""Pinned measurement protocol for the two pipeline runtimes.

VERDICT r2 weak #2: the task-graph vs collective-pipeline comparison
drifted between rounds (25 ms r1 vs 492 ms r2 for the same path) because
each round probed ad hoc — different step counts, different micro-batch
shapes, compile sometimes inside the window. This module is the single
source of truth from round 3 on:

  PROTOCOL (both paths, identical):
    - model: GPT-2 "test" config, batch 8 x seq 32, adam(1e-3)
    - parallelism: 2 stages x M=4 micro-batches over the same device list
    - warmup: 2 full steps (compile + steady-state signature), excluded
    - timing: best of 3 windows x 5 steps; every step returns its loss
      to the host, which is the barrier
    - reported: milliseconds per step, a HOST-protocol number on the
      virtual CPU mesh — never a statement about a chip

Run standalone (prints one JSON line) or via ``bench.py`` which records
the result in ``bench_extra.json`` every round. The fabric is the
8-device virtual CPU mesh (tests/conftest.py's env); standalone
invocation pins itself to it before importing jax.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ensure_cpu_mesh() -> None:
    """The protocol's fixed fabric is the 8-device virtual CPU mesh. Pin
    this process to it BEFORE jax is first imported: a standalone run
    neither probes a backend nor re-execs, so it never reaches for a chip
    another process may own."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


STAGES = 2
MICRO = 4
BATCH, SEQ = 8, 32
# Amortization config (VERDICT r3 weak #5: "the gap amortizes at real
# stage granularity" was an untested claim): same protocol, ~32x the
# per-task compute (seq capped by the test config's n_ctx=64).
BATCH_L, SEQ_L = 128, 64
WARMUP_STEPS = 2
WINDOW_STEPS = 5
WINDOWS = 3


def _timed_ms_per_step(step_once) -> float:
    """Best-of-windows protocol. ``step_once()`` must round-trip the loss
    to host (the barrier)."""
    for _ in range(WARMUP_STEPS):
        step_once()
    best = None
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(WINDOW_STEPS):
            step_once()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best / WINDOW_STEPS * 1e3


def bench_task_graph(devices=None, batch=None, seq=None) -> float:
    """Task-graph runtime: plan_training with 2 stages (AOT per-stage
    executables, event-driven 1F1B schedule)."""
    import jax
    import optax

    from tepdist_tpu.models import gpt2
    from tepdist_tpu.train import plan_training

    cfg = gpt2.CONFIGS["test"]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, batch or BATCH, seq or SEQ)
    plan = plan_training(
        lambda p, t: gpt2.loss_fn(p, t, cfg), optax.adam(1e-3), params,
        tokens, num_stages=STAGES, num_micro_batches=MICRO,
        devices=devices)
    return _timed_ms_per_step(lambda: plan.step(tokens))


def bench_collective_pipeline(devices=None, batch=None, seq=None) -> float:
    """Collective pipeline: the whole 1F1B step (fwd+bwd+adam over embed +
    stacked blocks) in ONE jitted program; stage hops are
    collective-permute over the mesh's stage axis."""
    import jax
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from tepdist_tpu.models import gpt2

    devices = list(devices if devices is not None else jax.devices())
    cfg = gpt2.CONFIGS["test"]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, batch or BATCH, seq or SEQ)
    # 2-stage split of the 2-layer test config: one block per stage.
    stage_mesh = Mesh(np.array(devices[:STAGES]), axis_names=("stage",))
    embed, stacked = gpt2.shard_stacked_for_stages(params, cfg, stage_mesh)
    tx = optax.adam(1e-3)
    state = (embed, stacked)
    opt = tx.init(state)

    @jax.jit
    def step(state, opt, tokens):
        def loss(state):
            e, b = state
            return gpt2.pipelined_loss_fn(e, b, tokens, cfg, stage_mesh,
                                          num_micro=MICRO)

        l, g = jax.value_and_grad(loss)(state)
        u, opt = tx.update(g, opt, state)
        return l, optax.apply_updates(state, u), opt

    box = {"state": state, "opt": opt}

    def step_once():
        l, box["state"], box["opt"] = step(box["state"], box["opt"], tokens)
        return float(jax.device_get(l))

    return _timed_ms_per_step(step_once)


def spawn_protocol_fleet(zero: bool = False):
    """Spawn the pinned protocol's worker fleet (one server process per
    stage, 1 device each) and build the DistributedPipelineSession over
    it. Returns (session, tokens, worker_procs); the caller owns
    teardown (SIGKILL the procs). Shared by the fleet benchmark line and
    tools/fleet_overhead_probe.py so both measure the SAME fleet
    configuration.

    ``zero`` tags the program with the ZeRO weight-update modifier
    before the session ships plan_meta, so every worker runs the
    sharded-optimizer apply path (a no-op reshard at 1 device/stage —
    the arm prices the plumbing, not the sharding)."""
    import socket
    import subprocess

    import jax
    import optax

    from tepdist_tpu.core.cluster_spec import ClusterSpec, WorkerSpec
    from tepdist_tpu.models import gpt2
    from tepdist_tpu.parallel.pipeline import plan_pipeline
    from tepdist_tpu.rpc.client import TepdistClient
    from tepdist_tpu.runtime.distributed_executor import (
        DistributedPipelineSession,
    )

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ports, procs = [], []
    for i in range(STAGES):
        port = free_port()
        ports.append(port)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "tepdist_tpu.rpc.server",
             "--port", str(port), "--platform", "cpu",
             "--task_index", str(i)],
            env=env, cwd=root,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
    try:
        for p in ports:
            c = TepdistClient(f"127.0.0.1:{p}")
            c.wait_ready(timeout=60)
            c.close()
        cfg = gpt2.CONFIGS["test"]
        params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
        tokens = gpt2.fake_batch(cfg, BATCH, SEQ)
        prog = plan_pipeline(
            lambda p, t: gpt2.loss_fn(p, t, cfg), STAGES, MICRO, params,
            tokens)
        if zero:
            prog.zero = True
        cluster = ClusterSpec([
            WorkerSpec("127.0.0.1", p, [0], task_index=i)
            for i, p in enumerate(ports)])
        sess = DistributedPipelineSession(prog, cluster,
                                          optimizer=optax.adam(1e-3))
        sess.load_variables(params)
        return sess, tokens, procs
    except Exception:
        import signal
        for pr in procs:
            pr.send_signal(signal.SIGKILL)
            pr.wait()
        raise


# Set by bench_two_worker_fleet when TEPDIST_TRACE=1: path of the merged
# fleet step trace, surfaced in the runtime line by run().
_FLEET_TRACE_PATH = [None]


def bench_two_worker_fleet(wire_dtype: str = "", zero: bool = False) -> float:
    """SAME protocol config over a 2-PROCESS fleet (one server process
    per stage, 1 device each): the multi-worker task-graph path on its
    backend-default transport — host push on the CPU fabric (a "device"
    transfer is itself a socket there), device-direct pulls on TPU
    (VERDICT r3 missing #3 / ask #7; the 1.15x target is TPU-gated).

    ``wire_dtype`` runs the compressed-wire arm: TEPDIST_WIRE_DTYPE is
    set in os.environ BEFORE the fleet spawns (workers inherit it; the
    wire dtype latches at worker/session construction) and in the
    master's ServiceEnv for its dispatch envelopes.

    ``zero`` runs the ZeRO arm: plan_meta ships ``zero=True`` so every
    worker takes the sharded-optimizer apply path."""
    import signal

    from tepdist_tpu.core.service_env import ServiceEnv

    env = ServiceEnv.get()
    prev_env = os.environ.get("TEPDIST_WIRE_DTYPE")
    prev_knob = env.tepdist_wire_dtype
    if wire_dtype:
        os.environ["TEPDIST_WIRE_DTYPE"] = wire_dtype
        env.set("TEPDIST_WIRE_DTYPE", wire_dtype)
    try:
        sess, tokens, procs = spawn_protocol_fleet(zero=zero)
    finally:
        if wire_dtype:
            if prev_env is None:
                os.environ.pop("TEPDIST_WIRE_DTYPE", None)
            else:
                os.environ["TEPDIST_WIRE_DTYPE"] = prev_env
            env.set("TEPDIST_WIRE_DTYPE", prev_knob)
    try:
        ms = _timed_ms_per_step(lambda: sess.step(tokens))
        if os.environ.get("TEPDIST_TRACE"):
            # Workers inherit TEPDIST_TRACE through spawn_protocol_fleet's
            # env copy, so this pulls real spans from every stage server
            # and writes one clock-aligned timeline next to the bench JSON
            # (feed it to tools/trace_summary.py).
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            _FLEET_TRACE_PATH[0] = sess.dump_trace(
                os.path.join(root, "bench_trace.json"))
        sess.close()
        return ms
    finally:
        for pr in procs:
            pr.send_signal(signal.SIGKILL)
            pr.wait()


def bench_dispatch_coalesce() -> dict:
    """Per-verb vs coalesced dispatch on the SAME live fleet: a 2-worker
    in-proc pipeline (4-layer 16x16 MLP, the ledger_report fixture model)
    stepped with TEPDIST_BATCH_DISPATCH off (legacy TransferHostRawData +
    ExecuteRemotePlan per worker) then on (one ExecuteStepSlice per
    worker). The master reads the knob per step, so both windows run on
    one session — identical plan, caches, and workers; only the dispatch
    verb count differs. Returns per-step ms for both plus their ratio
    (``x`` > 1.0 == coalescing is that many times faster)."""
    import jax
    import jax.numpy as jnp
    import optax

    from tepdist_tpu.core.service_env import ServiceEnv
    from tepdist_tpu.parallel.pipeline import plan_pipeline
    from tepdist_tpu.rpc.inproc import (close_inproc_cluster,
                                        make_inproc_cluster)
    from tepdist_tpu.runtime.distributed_executor import (
        DistributedPipelineSession,
    )

    def loss_fn(params, x, y):
        h = x
        for i in range(4):
            h = jnp.tanh(h @ params[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    params = {f"w{i}": jax.random.normal(keys[i], (16, 16)) * 0.3
              for i in range(4)}
    x = jax.random.normal(keys[4], (8, 16))
    y = jax.random.normal(keys[5], (8, 16))

    prog = plan_pipeline(loss_fn, 2, 2, params, x, y)
    cluster, _servicers = make_inproc_cluster(2, jax.devices()[:1])
    env = ServiceEnv.get()
    prev = env.tepdist_batch_dispatch
    try:
        sess = DistributedPipelineSession(prog, cluster,
                                          optimizer=optax.sgd(1e-2))
        sess.load_variables(params)
        env.set("TEPDIST_BATCH_DISPATCH", False)
        per_verb_ms = _timed_ms_per_step(lambda: sess.step(x, y))
        env.set("TEPDIST_BATCH_DISPATCH", True)
        coalesced_ms = _timed_ms_per_step(lambda: sess.step(x, y))
        sess.close()
    finally:
        env.set("TEPDIST_BATCH_DISPATCH", prev)
        close_inproc_cluster(cluster)
    return {
        "per_verb_ms": round(per_verb_ms, 2),
        "coalesced_ms": round(coalesced_ms, 2),
        "x": round(per_verb_ms / coalesced_ms, 4),
    }


def bench_pp_tp_depth() -> float:
    """8-layer GPT-2 at S=4 stages x TP=2/stage over all 8 mesh devices —
    the depth composition line (VERDICT r4 #7)."""
    import dataclasses

    import jax
    import optax

    from tepdist_tpu.models import gpt2
    from tepdist_tpu.parallel.pipeline import plan_pipeline
    from tepdist_tpu.runtime.executor import PipelineExecutable

    devices = jax.devices()
    if len(devices) < 8:
        raise RuntimeError("needs 8 devices")
    cfg = dataclasses.replace(gpt2.CONFIGS["test"], n_layer=8)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    toks = gpt2.fake_batch(cfg, BATCH, 32)
    prog = plan_pipeline(lambda p, t: gpt2.loss_fn(p, t, cfg), 4, MICRO,
                         params, toks)
    exe = PipelineExecutable(prog, devices=devices[:8],
                             optimizer=optax.sgd(0.05), intra_stage_tp=2)
    exe.load_variables(params)
    return _timed_ms_per_step(lambda: exe.step(toks))


def run() -> dict:
    import jax

    # IDENTICAL fabric for both paths: exactly STAGES devices, one per
    # stage (no intra-stage DP on either side).
    devices = jax.devices()[:STAGES]
    task_ms = coll_ms = fleet_ms = None
    err = {}
    try:
        task_ms = bench_task_graph(devices)
    except Exception as e:  # noqa: BLE001
        err["task_graph"] = repr(e)
    try:
        coll_ms = bench_collective_pipeline(devices)
    except Exception as e:  # noqa: BLE001
        err["collective_pipeline"] = repr(e)
    try:
        fleet_ms = bench_two_worker_fleet()
    except Exception as e:  # noqa: BLE001
        err["two_worker_fleet"] = repr(e)
    fleet_c_ms = None
    try:
        fleet_c_ms = bench_two_worker_fleet(wire_dtype="bfloat16")
    except Exception as e:  # noqa: BLE001
        err["two_worker_fleet_compressed"] = repr(e)
    fleet_z_ms = None
    try:
        fleet_z_ms = bench_two_worker_fleet(zero=True)
    except Exception as e:  # noqa: BLE001
        err["two_worker_fleet_zero"] = repr(e)
    task_l = coll_l = None
    try:
        task_l = bench_task_graph(devices, BATCH_L, SEQ_L)
        coll_l = bench_collective_pipeline(devices, BATCH_L, SEQ_L)
    except Exception as e:  # noqa: BLE001
        err["large_config"] = repr(e)
    depth_ms = None
    try:
        depth_ms = bench_pp_tp_depth()
    except Exception as e:  # noqa: BLE001
        err["pp_tp_depth"] = repr(e)
    coalesce = None
    try:
        coalesce = bench_dispatch_coalesce()
    except Exception as e:  # noqa: BLE001
        err["dispatch_coalesce"] = repr(e)
    line = {
        "metric": "runtime_protocol_ms_per_step",
        "protocol": (f"gpt2-test b{BATCH}xs{SEQ}, S={STAGES} M={MICRO}, "
                     f"{STAGES} devices (1/stage), warmup {WARMUP_STEPS}, "
                     f"best of {WINDOWS}x{WINDOW_STEPS} steps, loss "
                     "round-trip barrier"),
        "backend": jax.default_backend(),
        "task_graph_ms": None if task_ms is None else round(task_ms, 2),
        "collective_pipeline_ms":
            None if coll_ms is None else round(coll_ms, 2),
        # Explicitly named (NOT vs_baseline, which repo-wide means
        # value/first-recorded-run): >1.0 == the single-jit collective
        # pipeline is that many times faster than the task-graph runtime.
        "collective_speedup_over_taskgraph":
            None if not (task_ms and coll_ms)
            else round(task_ms / coll_ms, 4),
        "two_worker_fleet_ms":
            None if fleet_ms is None else round(fleet_ms, 2),
        "fleet_transport": ("host_push" if jax.default_backend() == "cpu"
                            else "device_direct"),
        # SAME fleet with TEPDIST_WIRE_DTYPE=bfloat16 on every hop
        # (activations AND dispatch envelopes): the wire-compression arm.
        "two_worker_fleet_compressed_ms":
            None if fleet_c_ms is None else round(fleet_c_ms, 2),
        # >1.0 == the compressed wire beats the fidelity wire per step.
        "wire_compression_speedup":
            None if not (fleet_ms and fleet_c_ms)
            else round(fleet_ms / fleet_c_ms, 4),
        # SAME fleet with the ZeRO weight-update modifier in plan_meta:
        # every worker reshards optimizer state over its intra axis each
        # apply (a no-op placement at 1 device/stage, so any gap over
        # two_worker_fleet_ms is pure plumbing overhead).
        "two_worker_fleet_zero_ms":
            None if fleet_z_ms is None else round(fleet_z_ms, 2),
        # Amortization check (BATCH_L x SEQ_L = b128 x s64, ~32x per-task
        # compute): the per-step dispatch gap should shrink toward 1.0.
        "task_graph_large_ms": None if task_l is None else round(task_l, 2),
        "collective_pipeline_large_ms":
            None if coll_l is None else round(coll_l, 2),
        "collective_speedup_over_taskgraph_large":
            None if not (task_l and coll_l) else round(task_l / coll_l, 4),
        # >1.0 == the 2-process fleet is that many times slower than the
        # single-process task-graph (ask #7 target: <= 1.15).
        "fleet_overhead_vs_taskgraph":
            None if not (task_ms and fleet_ms)
            else round(fleet_ms / task_ms, 4),
        # Canonical short name for the same ratio (ISSUE 11 hot-path
        # target: <= 2.0 on CPU; kept alongside the verbose key so older
        # round comparisons keep working).
        "fleet_overhead_x":
            None if not (task_ms and fleet_ms)
            else round(fleet_ms / task_ms, 4),
        # Per-verb vs ExecuteStepSlice dispatch on one live in-proc fleet
        # (> 1.0 == coalescing wins); sub-keys carry the raw per-step ms.
        "dispatch_coalesce_x": None if coalesce is None else coalesce["x"],
        "dispatch_per_verb_ms":
            None if coalesce is None else coalesce["per_verb_ms"],
        "dispatch_coalesced_ms":
            None if coalesce is None else coalesce["coalesced_ms"],
        # Depth composition (VERDICT r4 #7): 8-layer GPT-2 at S=4 x TP=2
        # through the task-graph runtime over all 8 mesh devices
        # (numerics-exactness asserted in tests/test_pp_tp_depth.py).
        "pp_tp_depth_ms": None if depth_ms is None else round(depth_ms, 2),
    }
    if _FLEET_TRACE_PATH[0]:
        line["fleet_trace"] = _FLEET_TRACE_PATH[0]
    if task_ms is not None and coll_ms is not None:
        best = min(task_ms, coll_ms)
        line["value"] = round(best, 2)
        line["unit"] = "ms/step"
        # Repo convention: vs_baseline > 1.0 == improvement. Lower ms is
        # better, so the ratio is baseline/value.
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from bench import _vs_baseline
        ratio = _vs_baseline("runtime_protocol_ms_per_step", best)
        line["vs_baseline"] = round(1.0 / ratio if ratio else 1.0, 4)
    if err:
        line["errors"] = err
    return line


if __name__ == "__main__":
    _ensure_cpu_mesh()
    print(json.dumps(run()))
