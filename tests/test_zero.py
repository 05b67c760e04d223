"""ZeRO weight-update sharding tests: the planner prices optimizer-state
partitioning (arXiv:2004.13336) as a composable candidate modifier, and
the runtime paths the @zero winner selects keep the fidelity contract.

Covers ISSUE-14's guarantees:
  * the cost algebra — RS + AG at equal bytes never beats ring AR, so
    ZeRO wins ONLY through memory feasibility (the 1/dp state term);
  * enumeration — every DP-bearing proposal gets an @zero variant (and
    @bf16@zero/@int8@zero combos), fidelity-first on exact ties;
  * the committed winner-flip fixture pair diffs with driver
    ``memory_feasible``;
  * numerics — the explicit shard_map GA path tracks plain DP within a
    reduction-order band, the planner ``zero_invars`` path matches to
    float tolerance while halving per-device optimizer bytes at dp=2;
  * checkpoints — sharded optimizer state saves per-shard
    (``shard_addressable``) and restores whole AND resharded onto a
    different DP width.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tepdist_tpu.core.mesh import MeshTopology
from tepdist_tpu.parallel.performance_utils import (
    OPT_STATE_FACTOR,
    PerfUtils,
    TpuChipSpec,
    param_wire_dtype,
)
from tepdist_tpu.parallel.redistribution import plan_redistribution
from tepdist_tpu.parallel.sync_free import build_ga_step, zero_pad_params
from tepdist_tpu.runtime.checkpoint import CheckpointUtil

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures")


# ---------------------------------------------------------------- cost model
def _spec(ici_gbps: float = 100.0):
    return TpuChipSpec(name="test", bf16_tflops=100.0, hbm_gb=16.0,
                       hbm_gbps=800.0, ici_gbps_per_link=ici_gbps,
                       ici_links=6, dcn_gbps=6.25)


def test_zero_update_never_beats_all_reduce_on_seconds():
    """RS + AG at equal bytes = ring AR + one extra alpha sweep: ZeRO
    must NOT win on pure time — the planner's fidelity-first tie-break
    depends on it (an @zero winner always means memory was binding)."""
    spec = _spec()
    for b in (1 << 16, 1 << 24, 1 << 28):
        for dp in (2, 4, 8):
            assert (PerfUtils.zero_update_cost(b, dp, "", spec)
                    >= PerfUtils.all_reduce_cost(b, dp, spec))


def test_zero_update_cost_dp1_is_free():
    assert PerfUtils.zero_update_cost(1 << 24, 1, "", _spec()) == 0.0
    assert PerfUtils.zero_update_cost(1 << 24, 0, "int8", _spec()) == 0.0


def test_zero_update_cost_composes_comm_dtype():
    """On a starved wire the compressed ZeRO collectives beat the
    fidelity ones, int8 (grads at 1/4, params capped at bf16) beating
    bf16 (both wires at 1/2)."""
    slow = _spec(ici_gbps=0.01)
    b = 256 * 1024 * 1024
    fid = PerfUtils.zero_update_cost(b, 8, "", slow)
    bf16 = PerfUtils.zero_update_cost(b, 8, "bfloat16", slow)
    i8 = PerfUtils.zero_update_cost(b, 8, "int8", slow)
    assert i8 < bf16 < fid


def test_param_wire_dtype_caps_int8_at_bf16():
    """Params are never int8-quantized on the AG wire (per-step bias
    would accumulate into the weights); gradients may be."""
    assert param_wire_dtype("int8") == "bfloat16"
    assert param_wire_dtype("bfloat16") == "bfloat16"
    assert param_wire_dtype("") == ""
    assert param_wire_dtype("float32") == "float32"


def test_opt_state_factor_prices_adam():
    # Two fp32 moments per param — the worst common case the planner
    # charges every candidate equally.
    assert OPT_STATE_FACTOR == 2.0


# ------------------------------------------------------- candidate space
def _gpt2_graph():
    import dataclasses

    from tepdist_tpu.graph.jaxpr_graph import trace_graph
    from tepdist_tpu.models import gpt2

    cfg = dataclasses.replace(gpt2.CONFIGS["test"], n_layer=1)
    params = jax.eval_shape(
        lambda k: gpt2.init_params(cfg, k), jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((8, 33), jnp.int32)
    graph, _, _ = trace_graph(
        jax.value_and_grad(lambda p, t: gpt2.loss_fn(p, t, cfg)),
        params, toks)
    return graph


def test_evaluator_prices_zero_state_savings():
    """The @zero re-pricing of the SAME sharding: optimizer state
    drops to 1/dp per device (lower peak), total seconds go UP (the
    RS+AG latency term) — exactly the trade the argmin arbitrates."""
    from tepdist_tpu.parallel.auto_parallel import plan_axes
    from tepdist_tpu.parallel.evaluator import Evaluator

    graph = _gpt2_graph()
    topo = MeshTopology([("data", 2), ("model", 4)])
    strategies = plan_axes(graph, topo, None, "cost")
    fid = Evaluator(topo).run(graph, strategies, 1)
    zro = Evaluator(topo, zero=True).run(graph, strategies, 1)
    assert fid.opt_state_bytes_per_device > 0
    np.testing.assert_allclose(zro.opt_state_bytes_per_device,
                               fid.opt_state_bytes_per_device / 2,
                               rtol=1e-6)
    assert zro.peak_bytes_per_device < fid.peak_bytes_per_device
    assert zro.total_duration > fid.total_duration


def test_spmd_candidates_enumerate_zero_variants():
    """Every DP-bearing comm-bearing mesh is re-priced @zero, including
    the comm-dtype combos; the suffixes stack (@int8@zero)."""
    from tepdist_tpu.parallel.exploration import (
        candidate_summary,
        spmd_candidates,
        zero_suffix,
    )

    assert zero_suffix(True) == "@zero"
    assert zero_suffix(False) == ""
    cands = spmd_candidates(_gpt2_graph(), 8)
    zeros = [c for c in cands if c.get("zero", False)]
    assert zeros
    # The modifier only exists where there's a DP axis to shard over.
    for c in zeros:
        dp = dict(c["topology"].device_axes()).get("data", 1)
        assert dp > 1
    dts = {c.get("comm_dtype", "") for c in zeros}
    assert {"", "bfloat16", "int8"} <= dts
    summaries = candidate_summary(cands)
    assert any(s["config"].endswith("@zero")
               and "@int8" not in s["config"] for s in summaries)
    assert any(s["config"].endswith("@int8@zero") for s in summaries)


def test_fidelity_enumerated_before_its_zero_variant():
    """Python's min keeps the earliest on exact cost ties, so the
    fidelity proposal must precede its @zero variant in the candidate
    list — @zero has to win STRICTLY (via feasibility) to be picked."""
    from tepdist_tpu.parallel.exploration import spmd_candidates

    cands = spmd_candidates(_gpt2_graph(), 8)
    seen_fid = set()
    for c in cands:
        key = str(c["topology"])
        if c.get("zero", False):
            assert key in seen_fid, f"@zero before fidelity for {key}"
        elif not c.get("comm_dtype", ""):
            seen_fid.add(key)


# ------------------------------------------------------ winner-flip fixture
def test_flip_fixture_driver_is_memory_feasible():
    """The committed before/after reports (scripts/gen_flip_fixtures.py:
    GPT-2 ``test`` graph, healthy wire, HBM starved to 2.4 MB) must flip
    the winner to an @zero mesh with ``memory_feasible`` as the named
    driver — the old fidelity winner stays enumerated but infeasible."""
    with open(os.path.join(FIXTURES, "zero_flip_before.json")) as f:
        rep_b = json.load(f)
    with open(os.path.join(FIXTURES, "zero_flip_after.json")) as f:
        rep_a = json.load(f)
    for rep in (rep_b, rep_a):
        cfgs = [c.get("config", "") for c in rep["candidates"]]
        assert any("@zero" in c for c in cfgs), cfgs
    from tepdist_tpu.telemetry.observatory import diff_reports

    d = diff_reports(rep_b, rep_a)
    assert d["flip"] is True
    assert d["driver"] == "memory_feasible"
    assert d["new_winner"].endswith("@zero")
    # The flip is the modifier, not a different mesh: same topology
    # string on both winners.
    assert d["new_winner"].replace("@zero", "") == d["old_winner"]
    # And the before-winner is genuinely infeasible in the after-report
    # (diff_reports winner ids carry the "kind:" prefix; rows don't).
    after_by_cfg = {f"{c['kind']}:{c['config']}": c
                    for c in rep_a["candidates"]}
    old = after_by_cfg[d["old_winner"]]
    assert old["cost"]["memory_feasible"] is False


# ----------------------------------------------------------- GA numerics
def _train_setup(seed=0):
    def loss_fn(params, x, y):
        h = jnp.tanh(x @ params["w1"])
        return jnp.mean((h @ params["w2"] - y) ** 2)

    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = {"w1": jax.random.normal(k1, (32, 64)) * 0.1,
              "w2": jax.random.normal(k2, (64, 8)) * 0.1}
    x = jax.random.normal(k3, (16, 32))
    y = jax.random.normal(k4, (16, 8))
    return loss_fn, params, x, y


def _run_plain(steps=8, micro=4):
    loss_fn, params, x, y = _train_setup()
    opt = optax.adam(0.02)
    grad_fn = jax.value_and_grad(loss_fn)

    def apply_fn(p, s, g):
        upd, s = opt.update(g, s, p)
        return optax.apply_updates(p, upd), s

    step = jax.jit(build_ga_step(grad_fn, apply_fn, micro,
                                 batch_argnums=(1, 2)))
    opt_state = opt.init(params)
    losses = []
    for _ in range(steps):
        loss, params, opt_state = step(params, opt_state, x, y)
        losses.append(float(loss))
    return losses, params


def _run_zero_shard_map(comm_dtype="", steps=8, micro=4, dp=2):
    """The explicit ZeRO-1 GA path under shard_map: per-replica
    half-batch gradient means, psum_scatter (SUM) onto 1/dp shards, the
    apply folds 1/dp back to mean semantics, updated params all-gather."""
    loss_fn, params, x, y = _train_setup()
    opt = optax.adam(0.02)
    mesh = Mesh(np.array(jax.devices()[:dp]), ("data",))

    def grad_fn(p, xb, yb):
        loss, g = jax.value_and_grad(loss_fn)(p, xb, yb)
        return lax.pmean(loss, "data"), g

    def apply_fn(p, s, g):
        g = jax.tree_util.tree_map(lambda v: v / dp, g)
        upd, s = opt.update(g, s, p)
        return optax.apply_updates(p, upd), s

    inner = build_ga_step(grad_fn, apply_fn, micro, batch_argnums=(1, 2),
                          comm_dtype=comm_dtype, zero_dp=dp,
                          zero_axis_name="data")
    opt_state = opt.init(zero_pad_params(params, dp))
    opt_specs = jax.tree_util.tree_map(
        lambda v: P("data") if getattr(v, "ndim", 0) >= 1 else P(),
        opt_state)
    step = jax.jit(shard_map(
        inner, mesh=mesh,
        in_specs=(P(), opt_specs, P("data"), P("data")),
        out_specs=(P(), P(), opt_specs),
        # The all-gathered params ARE replicated, but lax.all_gather's
        # result is typed varying, so the static check cannot see it.
        check_vma=False))
    losses = []
    for _ in range(steps):
        loss, params, opt_state = step(params, opt_state, x, y)
        losses.append(float(loss))
    return losses, params, opt_state


def test_ga_step_zero_tracks_plain_dp():
    """ZeRO-1 is the SAME update in a different reduction order
    (half-batch means summed then folded vs one full-batch mean), so the
    trajectory must track plain GA to float32 accumulation tolerance —
    far tighter than the compressed-gradient band."""
    fid, pf = _run_plain()
    zro, pz, opt_state = _run_zero_shard_map()
    for a, b in zip(fid, zro):
        assert abs(a - b) <= 1e-4 * max(abs(a), 1e-6), (fid, zro)
    assert zro[-1] < zro[0]
    for k in pf:
        np.testing.assert_allclose(np.asarray(pz[k]), np.asarray(pf[k]),
                                   rtol=2e-4, atol=1e-6)
    # The whole point: each device holds a DISTINCT 1/dp moment shard.
    for leaf in jax.tree_util.tree_leaves(opt_state):
        if getattr(leaf, "ndim", 0) >= 1:
            assert CheckpointUtil._distinct_extents(leaf) == 2, leaf.shape


def test_ga_step_zero_composes_with_int8():
    """@int8@zero: fake-quantized gradient contributions through the
    ZeRO update must still TRACK the fidelity trajectory (the compressed
    band) while actually perturbing the bits."""
    fid, _ = _run_plain()
    q, _, _ = _run_zero_shard_map(comm_dtype="int8")
    assert fid != q, "int8 path did not engage"
    for a, b in zip(fid, q):
        assert abs(a - b) <= 0.05 * max(abs(a), 1e-6), (fid, q)
    assert q[-1] < q[0]


# ---------------------------------------------------------- planner path
def test_auto_parallel_zero_invars_shards_state_and_matches():
    """The single-jit SPMD realization: ``zero_invars`` force-splits the
    optimizer-state invars over the data axis, GSPMD emits the
    equivalent RS/sharded-apply/AG — same trajectory as the unsharded
    step, half the per-device optimizer bytes at dp=2."""
    from tepdist_tpu.parallel.auto_parallel import auto_parallel

    loss_fn, params, x, y = _train_setup()
    opt = optax.adam(0.02)

    def grad_fn(p, *b):
        return jax.value_and_grad(loss_fn)(p, *b)

    def apply_fn(p, s, g):
        upd, s = opt.update(g, s, p)
        return optax.apply_updates(p, upd), s

    step_fn = build_ga_step(grad_fn, apply_fn, 1, batch_argnums=(1, 2))
    opt_state = opt.init(params)
    n_param = len(jax.tree_util.tree_leaves(params))
    n_state = len(jax.tree_util.tree_leaves((params, opt_state)))

    # Reference: the same step, unsharded on one device.
    ref_step = jax.jit(step_fn)
    rp, rs_ = params, opt_state
    ref_losses = []
    for _ in range(6):
        loss, rp, rs_ = ref_step(rp, rs_, x, y)
        ref_losses.append(float(loss))

    topo = MeshTopology([("data", 2)])
    state_alias = {1 + i: i for i in range(n_state)}
    plan = auto_parallel(step_fn, topo, params, opt_state, x, y,
                         state_alias=state_alias,
                         zero_invars=list(range(n_param, n_state)))
    assert plan.zero is True
    devs = jax.devices()[:2]
    shardings = plan.input_shardings(devs)
    split = [i for i in range(n_param, n_state)
             if "data" in str(getattr(shardings[i], "spec", ""))]
    assert split, "no optimizer-state invar was split over the data axis"

    exe = plan.executable(devices=devs)
    state = [jax.device_put(v, s) for v, s in
             zip(jax.tree_util.tree_leaves((params, opt_state)),
                 shardings[:n_state])]
    batch = [jax.device_put(v, s)
             for v, s in zip([x, y], shardings[n_state:])]
    losses = []
    for _ in range(6):
        outs = exe(*state, *batch)
        state = list(outs[1:1 + n_state])
        losses.append(float(jax.device_get(outs[0])))
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)

    # Per-device optimizer bytes: split leaves hold half the elements.
    dev0_bytes = full_bytes = 0
    for v in state[n_param:n_state]:
        full_bytes += int(np.prod(v.shape)) * v.dtype.itemsize
        sh = [s for s in v.addressable_shards if s.device == devs[0]]
        dev0_bytes += sum(int(np.prod(s.data.shape)) * v.dtype.itemsize
                          for s in sh)
    assert dev0_bytes <= 0.6 * full_bytes, (dev0_bytes, full_bytes)


# ------------------------------------------------------------ checkpoints
def test_checkpoint_shard_addressable_writes_per_shard(tmp_path, devices):
    """shard_addressable=True keeps a fully addressable but SHARDED
    array per-shard on disk (+ index sidecar); replicated and host
    arrays still store whole. Plain restore reassembles the full
    array."""
    mesh = Mesh(np.array(devices[:2]), ("data",))
    mu = jax.device_put(jnp.arange(8.0, dtype=jnp.float32),
                        NamedSharding(mesh, P("data")))
    rep = jax.device_put(jnp.ones((4,), jnp.float32),
                         NamedSharding(mesh, P()))
    util = CheckpointUtil(str(tmp_path), shard_addressable=True)
    util.save(3, {"opt.mu": mu, "w": rep,
                  "host": np.full((2, 2), 7.0, np.float32)})
    data = np.load(str(tmp_path / "step_000000000003" / "worker0.npz"))
    shard_keys = [k for k in data.files if k.startswith("opt.mu::shard")]
    assert len(shard_keys) == 2, data.files
    assert "w" in data.files and "host" in data.files
    out, step = util.restore()
    assert step == 3
    np.testing.assert_array_equal(out["opt.mu"],
                                  np.arange(8, dtype=np.float32))
    np.testing.assert_array_equal(out["w"], np.ones((4,), np.float32))


def test_checkpoint_zero_state_restores_onto_wider_dp(tmp_path, devices):
    """The reshard contract: optimizer state saved as dp=2 ZeRO shards
    lands on dp=4 destination bounds via restore_resharded — per-shard
    reads, never the full array."""
    mesh = Mesh(np.array(devices[:2]), ("data",))
    full = np.arange(16, dtype=np.float32)
    mu = jax.device_put(jnp.asarray(full), NamedSharding(mesh, P("data")))
    util = CheckpointUtil(str(tmp_path), shard_addressable=True)
    util.save(1, {"opt.mu": mu})
    dsts = [[[i * 4, (i + 1) * 4]] for i in range(4)]
    out, step = util.restore_resharded({"opt.mu": dsts})
    assert step == 1
    for d, got in zip(dsts, out["opt.mu"]):
        (lo, hi), = d
        np.testing.assert_array_equal(got, full[lo:hi])


def test_checkpoint_zero_state_restores_onto_narrower_dp(tmp_path, devices):
    """The elastic-shrink direction of the reshard contract: dp=4 ZeRO
    shards land on dp=2 destination bounds. Each destination spans TWO
    source shards, so plan_redistribution must stitch multi-piece
    assemblies — the path a fleet-shrink live migration rides."""
    mesh = Mesh(np.array(devices[:4]), ("data",))
    full = np.arange(16, dtype=np.float32)
    mu = jax.device_put(jnp.asarray(full), NamedSharding(mesh, P("data")))
    util = CheckpointUtil(str(tmp_path), shard_addressable=True)
    util.save(5, {"opt.mu": mu})
    # The shard index on disk holds four dp=4 pieces; the dp=2 plan
    # stitches two of them per destination.
    src = [((i * 4, (i + 1) * 4),) for i in range(4)]
    plan = plan_redistribution(src, [((0, 8),), ((8, 16),)])
    assert all(len(pieces) == 2 for pieces in plan), plan
    dsts = [[[i * 8, (i + 1) * 8]] for i in range(2)]
    out, step = util.restore_resharded({"opt.mu": dsts})
    assert step == 5
    for d, got in zip(dsts, out["opt.mu"]):
        (lo, hi), = d
        np.testing.assert_array_equal(got, full[lo:hi])


def test_checkpoint_default_save_stays_whole(tmp_path, devices):
    """Without shard_addressable, a fully addressable sharded array
    stores WHOLE — the pre-ZeRO contract other savers rely on."""
    mesh = Mesh(np.array(devices[:2]), ("data",))
    mu = jax.device_put(jnp.arange(8.0, dtype=jnp.float32),
                        NamedSharding(mesh, P("data")))
    util = CheckpointUtil(str(tmp_path))
    util.save(2, {"opt.mu": mu})
    data = np.load(str(tmp_path / "step_000000000002" / "worker0.npz"))
    assert data.files == ["opt.mu"]
