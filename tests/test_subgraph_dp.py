"""Subgraph-DP planner tests (VERDICT r1 item 2; reference
FindSubGraphs/SubGraphStrategy, cost_spmd_strategy.h:610-898,913-1257)."""

import time

import jax
import jax.numpy as jnp
import pytest

from tepdist_tpu.core.mesh import MeshTopology
from tepdist_tpu.core.service_env import ServiceEnv
from tepdist_tpu.graph.jaxpr_graph import trace_graph
from tepdist_tpu.parallel.auto_parallel import plan_axes


def _chain_mlp(n_layers, d, batch, bias=False):
    def loss(params, x, y):
        h = x
        for i in range(n_layers):
            h = h @ params[f"w{i}"]
            if bias:
                h = h + params[f"b{i}"]
            h = jax.nn.relu(h)
        return jnp.mean((h - y) ** 2)

    f32 = jnp.float32
    params = {}
    for i in range(n_layers):
        params[f"w{i}"] = jax.ShapeDtypeStruct((d, d), f32)
        if bias:
            params[f"b{i}"] = jax.ShapeDtypeStruct((d,), f32)
    x = jax.ShapeDtypeStruct((batch, d), f32)
    y = jax.ShapeDtypeStruct((batch, d), f32)
    return jax.value_and_grad(loss), params, x, y


@pytest.mark.parametrize("axes", [[("data", 8)], [("model", 4)]])
def test_subgraph_dp_matches_whole_graph_ilp(axes):
    """Forcing subgraph mode on a battery-sized graph reproduces the
    whole-graph ILP's optimal cost (plans may permute symmetric dims)."""
    fn, params, x, y = _chain_mlp(8, 256, 512)
    topo = MeshTopology(axes)

    graph, _, _ = trace_graph(fn, params, x, y)
    whole = plan_axes(graph, topo)[0]
    assert whole.ilp_status == "ilp"

    ServiceEnv.reset({"SUBGRAPH_NODES": "10"})
    try:
        graph2, _, _ = trace_graph(fn, params, x, y)
        dp = plan_axes(graph2, topo)[0]
    finally:
        ServiceEnv.reset()
    assert dp.ilp_status == "subgraph-dp"
    assert abs(dp.total_cost - whole.total_cost) <= (
        1e-12 + 1e-6 * abs(whole.total_cost)), (dp.total_cost,
                                                whole.total_cost)
    # Same sharding decisions for the graph inputs (storage plan).
    for v, s in whole.var_strategies.items():
        ds = dp.var_strategies.get(v)
        assert ds is not None
        assert ds.is_split() == s.is_split()


def test_subgraph_dp_scales_past_whole_graph_ilp():
    """A deep-chain training graph well past the whole-graph ILP comfort
    zone plans via subgraph DP in bounded time. (The full 105k-node
    measurement runs out-of-CI: 105,008 nodes planned in ~80s on a single
    CPU core at cost 2.39e-4, where the whole-graph ILP needs 230s and
    returns a ~1000x worse incumbent (0.257) at its time limit; this is
    the fast regression guard at ~30k nodes.)"""
    # Dimensions where batch-splitting clearly pays (per-layer compute
    # saving > per-weight psum alpha cost) so the plan is non-degenerate.
    fn, params, x, y = _chain_mlp(2200, 256, 4096, bias=True)
    graph, _, _ = trace_graph(fn, params, x, y)
    assert len(graph.nodes) > 25000
    t0 = time.time()
    gs = plan_axes(graph, MeshTopology([("data", 8)]))[0]
    dt = time.time() - t0
    assert gs.ilp_status == "subgraph-dp"
    assert dt < 90, f"subgraph DP took {dt:.1f}s"
    # The plan is non-degenerate: batch-split compute, sharded storage.
    n_split = sum(1 for outs in gs.node_out.values()
                  for s in outs if s is not None and s.is_split())
    assert n_split > 1000


def _gpt2_grad_graph():
    """Attention-bearing transformer grad graph (VERDICT r2 weak #6: chain
    MLPs exercise none of the cross-boundary reshard structure residuals +
    attention create — segments cut THROUGH blocks, so boundary states
    carry Q/K/V, residual-stream, and layernorm-stat vars)."""
    from tepdist_tpu.models import gpt2

    cfg = gpt2.GPT2Config(vocab_size=1024, n_ctx=64, n_embd=128,
                          n_layer=4, n_head=4, dtype=jnp.float32)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, 8, 64)
    fn = (lambda p, t: jax.value_and_grad(
        lambda q: gpt2.loss_fn(q, t, cfg))(p))
    return fn, params, tokens


def _whole_graph_plan(graph, topo):
    """The whole-graph ILP's plan of the transformer graph, given the time
    a loaded test worker needs to prove its optimum (3.5 s alone, 29 s
    beside 26 busy processes on 8 cores; ``ILP_TIME_LIMIT``'s 5 s ran out
    under the suite's own load and the solver fell back to a worse plan).
    The solver stops at its optimum, so an idle run pays nothing for it.
    The DP's segment solves are capped apart (0.8 s each, whatever this
    says: ``cost_spmd_strategy.py:_solve_ilp``)."""
    ServiceEnv.reset({"ILP_TIME_LIMIT": 120.0})
    try:
        return plan_axes(graph, topo)[0]
    finally:
        ServiceEnv.reset()


@pytest.mark.parametrize("axes", [[("data", 8)], [("model", 8)]])
def test_subgraph_dp_parity_on_transformer_grad_graph(axes):
    """Forced subgraph-DP (with one-segment lookahead) reproduces the
    whole-graph ILP cost exactly on a 4-block GPT-2 grad graph — the case
    whose cross-boundary structure saturated the pre-lookahead beam at a
    161% gap."""
    fn, params, tokens = _gpt2_grad_graph()
    topo = MeshTopology(axes)

    graph, _, _ = trace_graph(fn, params, tokens)
    whole = _whole_graph_plan(graph, topo)
    assert whole.ilp_status == "ilp"

    ServiceEnv.reset({"SUBGRAPH_NODES": "10"})
    try:
        g2, _, _ = trace_graph(fn, params, tokens)
        dp = plan_axes(g2, topo)[0]
    finally:
        ServiceEnv.reset()
    assert dp.ilp_status == "subgraph-dp"
    assert abs(dp.total_cost - whole.total_cost) <= (
        1e-12 + 1e-6 * abs(whole.total_cost)), (dp.total_cost,
                                                whole.total_cost)


def test_subgraph_dp_beam_width_curve_on_transformer():
    """Beam-quality curve on the transformer graph, from data (recorded
    2026-07, GPT-2 4-block grad graph, data axis, with lookahead):

        beam=1: +2372% vs whole-graph ILP (no diversity: the forced-
                replicated rescue variant is dropped immediately)
        beam=2: exact parity
        beam>=3: exact parity (default 3 = minimum exact + 1 margin)

    Asserts the shape of that curve: beam=2 already exact, beam=1 no
    better than beam=2."""
    fn, params, tokens = _gpt2_grad_graph()
    topo = MeshTopology([("data", 8)])
    graph, _, _ = trace_graph(fn, params, tokens)
    whole = _whole_graph_plan(graph, topo)

    costs = {}
    for beam in (1, 2):
        ServiceEnv.reset({"SUBGRAPH_NODES": "10",
                          "SUBGRAPH_BEAM": str(beam)})
        try:
            g2, _, _ = trace_graph(fn, params, tokens)
            costs[beam] = plan_axes(g2, topo)[0].total_cost
        finally:
            ServiceEnv.reset()
    assert abs(costs[2] - whole.total_cost) <= (
        1e-12 + 1e-6 * abs(whole.total_cost)), (costs[2], whole.total_cost)
    assert costs[1] >= costs[2] * (1 - 1e-9)
