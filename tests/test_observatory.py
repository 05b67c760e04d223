"""Exploration observatory: candidate ledger completeness, typed prune
forensics, report determinism, plan diffing, and the predicted-vs-measured
cost scoreboard (telemetry/observatory.py + tools/plan_explain.py +
tools/plan_diff.py).

The ledger contract under test: every enumerated proposal is either a
priced candidate or a TYPED prune record — nothing silently vanishes —
and a fixed fixture yields a byte-identical canonical report, so
plan_diff of two identical runs is empty while a seeded cost-model
perturbation produces a winner flip with a named driver.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tepdist_tpu.core.service_env import ServiceEnv
from tepdist_tpu.parallel.exploration import explore
from tepdist_tpu.telemetry import observatory


def _mlp(depth=4, width=1024, batch=8):
    """Abstract (ShapeDtypeStruct) MLP: big enough that full replication
    becomes memory-infeasible under a seeded tiny-HBM perturbation."""
    def loss(params, x, y):
        h = x
        for i in range(depth):
            h = jnp.tanh(h @ params[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    params = {f"w{i}": jax.ShapeDtypeStruct((width, width), jnp.float32)
              for i in range(depth)}
    x = jax.ShapeDtypeStruct((batch, width), jnp.float32)
    y = jax.ShapeDtypeStruct((batch, width), jnp.float32)
    return loss, params, x, y


def _explore_report(**env):
    loss, params, x, y = _mlp()
    try:
        if env:
            ServiceEnv.reset({k: v for k, v in env.items()})
        best = explore(loss, params, x, y, n_devices=8, num_micro_batches=2)
    finally:
        if env:
            ServiceEnv.reset()
    return best["report"]


# ---------------------------------------------------------------- ledger


def test_report_completeness_every_proposal_accounted():
    """enumerated == priced candidates + typed prunes, exactly one
    winner, and every prune row carries a type and a reason."""
    rep = _explore_report()
    comp = observatory.completeness(rep)
    assert comp["ok"], comp
    assert comp["unaccounted"] == 0
    assert comp["candidates"] + comp["prunes"] == rep["counts"]["enumerated"]

    winners = [c for c in rep["candidates"] if c.get("winner")]
    assert len(winners) == 1
    for p in rep["prunes"]:
        assert p["kind"] in ("spmd", "seq", "pipeline"), p
        assert p["reason"] in ("enumeration_skip", "planning_exception",
                               "memory_infeasible"), p
        assert p["config"], p
    # Cost decomposition present on every priced candidate.
    for c in rep["candidates"]:
        assert {"compute_s", "coll_s", "bubble_s",
                "total_s"} <= set(c["cost"]), c
    # Report survives a JSON round trip (the RPC/trace persistence path).
    assert observatory.completeness(json.loads(json.dumps(rep)))["ok"]


def test_report_determinism_and_canonical_form():
    """Two explores of the same fixture agree on everything but wall
    time; volatile fields really are excluded from the canonical form."""
    r1, r2 = _explore_report(), _explore_report()
    assert observatory.canonical(r1) == observatory.canonical(r2)
    assert r1["version"] == observatory.REPORT_VERSION
    for vol in ("ts", "phases", "capture_ms"):
        assert vol in r1
        assert vol not in observatory.canonical(r1)
    # Phase spans covered the enumeration stages.
    assert any(k.startswith("spmd") for k in r1["phases"])


def test_report_rationale_and_persistence(tmp_path):
    rep = _explore_report()
    assert rep["winner"]["config"]
    assert rep["rationale"]["deciding_term"] in (
        "compute_s", "coll_s", "bubble_s", "tie", "only_feasible_candidate")
    # TEPDIST_PLAN_REPORT persistence: directory mode names the file by
    # entry point; load() round-trips.
    out = tmp_path / "reports"
    out.mkdir()
    try:
        ServiceEnv.reset({"TEPDIST_PLAN_REPORT": str(out)})
        _explore_report()
    finally:
        ServiceEnv.reset()
    files = list(out.glob("plan_report_*.json"))
    assert files, "TEPDIST_PLAN_REPORT wrote nothing"
    loaded = observatory.ExplorationReport.load(str(files[0]))
    assert observatory.canonical(loaded) == observatory.canonical(rep)


# ------------------------------------------------------- prune forensics


def test_prune_typing_and_uniform_failure_warning():
    """A bug-class exception (TypeError) pruning EVERY proposal of a kind
    is surfaced as a WARN in the report; an expected infeasibility
    (ValueError) is not flagged as a suspect bug."""
    with observatory.capture("unit") as col:
        for i in range(3):
            observatory.record_prune(
                "pipeline", f"S={2 ** i} M=2", "planning_exception",
                exc=TypeError("boom"))
        observatory.record_prune(
            "spmd", "MeshTopology(data=8)", "planning_exception",
            exc=ValueError("indivisible"))
        class _Cost:
            total_duration = 1.0
            coll_ratio = 0.0
            bubble_ratio = 0.0
            peak_bytes_per_device = 1.0
            memory_feasible = True

            def key(self):
                return (0, self.total_duration)

        cand = {"kind": "spmd", "topology": "MeshTopology(model=8)",
                "cost": _Cost(), "duration_s": 1.0}
        rep = observatory.build_report(
            col, [cand], cand, n_devices=8, entry_point="unit")
    d = rep.to_dict()
    assert [p for p in d["prunes"] if p["exc_type"] == "TypeError"
            and p["suspect_bug"]]
    assert not [p for p in d["prunes"] if p["exc_type"] == "ValueError"
                and p["suspect_bug"]]
    # pipeline had 3/3 proposals die with one exc_type and zero survivors.
    assert any("pipeline" in w and "TypeError" in w for w in d["warnings"]), \
        d["warnings"]
    # spmd has a surviving candidate, so no uniform-failure warning.
    assert not any(w.startswith("spmd") for w in d["warnings"])


def test_record_prune_is_safe_outside_capture():
    """The prune hook never throws when no collector is active (library
    callers outside explore())."""
    observatory.record_prune("spmd", "MeshTopology(data=2)",
                             "enumeration_skip", message="no collector")


# ------------------------------------------------------------- plan diff


def test_plan_diff_identical_runs_is_empty():
    r1, r2 = _explore_report(), _explore_report()
    d = observatory.diff_reports(r1, r2)
    assert not d["flip"]
    assert not d["candidates_added"] and not d["candidates_removed"]
    assert all(row["delta_total_s"] == 0 for row in d["cost_deltas"])


def test_plan_diff_seeded_perturbation_flips_with_named_driver():
    """Shrinking HBM makes full replication (data=8) memory-infeasible
    while sharded candidates survive: the winner flips and plan_diff
    names the driver. (0.02 GB, not lower: the evaluator now charges
    optimizer state per device — ISSUE 14 — so the smallest budgets
    starve EVERY candidate and nothing is left to flip to.)"""
    base = _explore_report()
    pert = _explore_report(HBM_GB=0.02)
    assert base["winner"]["config"] != pert["winner"]["config"]
    d = observatory.diff_reports(base, pert)
    assert d["flip"], d
    assert d["driver"] == "memory_feasible", d
    assert d["old_winner"] != d["new_winner"]
    assert d["detail"]


def test_plan_diff_cli_contract(tmp_path):
    """--check exits 1 on a flip and 0 on identical reports;
    --expect-flip inverts that (the detector self-test)."""
    from tools import plan_diff as pd

    base, pert = _explore_report(), _explore_report(HBM_GB=0.02)
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(pert))
    assert pd.main([str(a), str(a), "--check"]) == 0
    assert pd.main([str(a), str(b), "--check"]) == 1
    assert pd.main([str(a), str(b), "--expect-flip"]) == 0
    assert pd.main([str(a), str(a), "--expect-flip"]) == 1


# ------------------------------------------------------------ scoreboard


def test_scoreboard_joins_predicted_to_measured_two_worker_run():
    """plan_explain's fixture runs the real two-worker inproc cluster and
    joins the executed candidate's predicted cost terms against the
    fidelity report's measured attribution lanes."""
    from tools.plan_explain import run_fixture

    rep, fid, config = run_fixture(steps=4)
    comp = observatory.completeness(rep)
    assert comp["ok"], comp
    sb = observatory.scoreboard(rep, fid, config=config)
    assert sb["ok"], sb
    assert sb["n_worker_lanes"] >= 1
    for term in ("compute_ms", "coll_ms", "bubble_ms", "total_ms"):
        row = sb["terms"][term]
        assert row["predicted_ms"] >= 0
        assert row["measured_ms"] >= 0
    assert sb["terms"]["total_ms"]["measured_ms"] > 0


# ----------------------------------------------------------- RPC surface


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_rpc_explore_returns_report_and_trace_embeds_it(tmp_path):
    """BuildExecutionPlan's explore mode ships the full report over the
    wire; the client session exposes it and folds it into dump_trace
    metadata next to fidelity (the artifact plan_explain --trace reads)."""
    from tepdist_tpu.client.session import TepdistSession
    from tepdist_tpu.optim import optimizer_spec
    from tepdist_tpu.rpc.client import TepdistClient

    def loss_fn(params, x, y):
        h = jax.nn.relu(x @ params["w0"])
        return jnp.mean((h @ params["w1"] - y) ** 2)

    k = jax.random.PRNGKey(0)
    params = {"w0": jax.random.normal(k, (64, 64)) * 0.1,
              "w1": jax.random.normal(jax.random.fold_in(k, 1),
                                      (64, 64)) * 0.1}
    x = jax.random.normal(jax.random.fold_in(k, 2), (64, 64))
    y = jnp.zeros((64, 64))

    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["TEPDIST_CKPT_DIR"] = tempfile.mkdtemp(prefix="tepdist_ckpt_")
    proc = subprocess.Popen(
        [sys.executable, "-m", "tepdist_tpu.rpc.server",
         "--port", str(port), "--platform", "cpu", "--task_index", "0"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    try:
        client = TepdistClient(f"127.0.0.1:{port}")
        try:
            client.wait_ready(timeout=60.0)
        finally:
            client.close()
        sess = TepdistSession(f"127.0.0.1:{port}", mesh_axes=())
        summary = sess.compile_training(
            loss_fn, optax.sgd(0.1), params, x, y,
            optimizer_spec=optimizer_spec("sgd", learning_rate=0.1))
        rep = (summary.get("explored") or {}).get("report")
        assert rep is not None, "explore RPC response carried no report"
        assert rep["entry_point"] == "BuildExecutionPlan"
        assert observatory.completeness(rep)["ok"]
        assert sess.exploration_report == rep

        sess.run(x, y)
        trace_path = str(tmp_path / "trace.json")
        sess.dump_trace(trace_path)
        with open(trace_path) as f:
            trace = json.load(f)
        embedded = observatory.report_from_trace(trace)
        assert embedded is not None
        assert observatory.canonical(embedded) == observatory.canonical(rep)
        sess.close()
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()
