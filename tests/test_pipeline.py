"""Pipeline planning tests: stage ILP, decomposition wiring, and pipelined
GA numerics vs plain training (reference: GraphSketch::StagePlan +
StageDecomposition correctness)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tepdist_tpu.graph.jaxpr_graph import trace_graph
from tepdist_tpu.parallel.graph_sketch import GraphSketch
from tepdist_tpu.parallel.pipeline import plan_pipeline
from tepdist_tpu.parallel.stage_decomposition import StageDecomposition


def _mlp4(batch=32, d=64):
    def loss_fn(params, x, y):
        h = x
        for i in range(4):
            h = jnp.tanh(h @ params[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    k = jax.random.PRNGKey(0)
    keys = jax.random.split(k, 6)
    params = {f"w{i}": jax.random.normal(keys[i], (d, d)) * 0.3
              for i in range(4)}
    x = jax.random.normal(keys[4], (batch, d))
    y = jax.random.normal(keys[5], (batch, d))
    return loss_fn, params, x, y


def test_sketch_clusters_and_ranks():
    loss_fn, params, x, y = _mlp4()
    graph, _, _ = trace_graph(loss_fn, params, x, y)
    sketch = GraphSketch(graph)
    # Clustering must reduce node count (elementwise absorbed into dots).
    assert len(sketch.nodes) < len(graph.nodes)
    assert sketch.total_flops() == pytest.approx(graph.total_flops())
    for sn in sketch.nodes:
        for o in sn.operands:
            assert o < sn.id  # topological ids


def test_stage_plan_balances_flops():
    loss_fn, params, x, y = _mlp4()
    graph, _, _ = trace_graph(loss_fn, params, x, y)
    sketch = GraphSketch(graph)
    assignment = sketch.stage_plan(2)
    flops = [0.0, 0.0]
    for n in graph.nodes:
        assert assignment[n.id] in (0, 1)
        flops[assignment[n.id]] += n.flops
    total = sum(flops)
    assert flops[0] > 0.05 * total and flops[1] > 0.05 * total
    # Precedence at jaxpr level.
    for n in graph.nodes:
        for op in n.operands:
            assert assignment[op.id] <= assignment[n.id]


def test_decomposition_wiring():
    loss_fn, params, x, y = _mlp4()
    graph, _, _ = trace_graph(loss_fn, params, x, y)
    sketch = GraphSketch(graph)
    assignment = sketch.stage_plan(2)
    decomp = StageDecomposition(graph, assignment, 2)
    s0, s1 = decomp.stages
    # Stage 1 must consume at least one activation from stage 0.
    acts = s1.activation_positions()
    assert acts, "no cross-stage activation edge"
    for pos in acts:
        src = s1.input_def_map[pos]
        assert src[0] == "stage" and src[1] == 0
    # Forward composition reproduces the loss.
    flat, _ = jax.tree_util.tree_flatten(((params, x, y), {}))
    f0, f1 = decomp.forward_fns()
    outs0 = f0(*[flat[src[1]] if src[0] == "arg" else None
                 for src in (s0.input_def_map[p] for p in range(len(s0.invars)))])
    ins1 = []
    for p in range(len(s1.invars)):
        src = s1.input_def_map[p]
        ins1.append(flat[src[1]] if src[0] == "arg" else outs0[src[2]])
    outs1 = f1(*ins1)
    loss_idx = s1.graph_out_map.get(0)
    assert loss_idx is not None
    np.testing.assert_allclose(
        np.asarray(outs1[loss_idx]), np.asarray(loss_fn(params, x, y)),
        rtol=1e-5)


@pytest.mark.parametrize("num_stages,num_micro", [(2, 4), (4, 2)])
def test_pipeline_step_matches_plain_training(num_stages, num_micro):
    loss_fn, params, x, y = _mlp4(batch=32)
    prog = plan_pipeline(loss_fn, num_stages, num_micro, params, x, y)
    assert len(prog.stages) == num_stages

    tx = optax.sgd(0.1)
    opt_state = tx.init(params)

    def apply_fn(p, s, g):
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    step = jax.jit(prog.reference_step(apply_fn))
    loss, new_params, _ = step(params, opt_state, x, y)

    # Plain GA training step with the same micro-batching.
    def plain_step(p, s, x, y):
        M = num_micro
        m = x.shape[0] // M
        loss_sum = 0.0
        grads = jax.tree_util.tree_map(jnp.zeros_like, p)
        for i in range(M):
            xi = x[i * m:(i + 1) * m]
            yi = y[i * m:(i + 1) * m]
            l, g = jax.value_and_grad(loss_fn)(p, xi, yi)
            loss_sum += l
            grads = jax.tree_util.tree_map(jnp.add, grads, g)
        grads = jax.tree_util.tree_map(lambda g: g / M, grads)
        updates, s = tx.update(grads, s, p)
        return loss_sum / M, optax.apply_updates(p, updates), s

    ref_loss, ref_params, _ = jax.jit(plain_step)(params, opt_state, x, y)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_loss),
                               rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6),
        new_params, ref_params)


def test_stage_flops_reporting():
    loss_fn, params, x, y = _mlp4()
    prog = plan_pipeline(loss_fn, 2, 2, params, x, y)
    flops = prog.stage_flops()
    assert len(flops) == 2 and all(f > 0 for f in flops)
    assert prog.decomp.cross_stage_bytes() > 0


def test_plan_pipeline_refuses_pallas_kernel():
    """Stage programs take jax.vjp of the staged forward; a flash model's
    custom_vjp is inlined away by tracing, so the proposal must be refused
    up front (exploration prunes it) instead of failing inside jax at the
    first step."""
    import dataclasses

    from tepdist_tpu.models import gpt2

    cfg = dataclasses.replace(gpt2.CONFIGS["test"], attn="flash")
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, 8, 32)
    with pytest.raises(NotImplementedError, match="pallas kernel"):
        plan_pipeline(lambda p, t: gpt2.loss_fn(p, t, cfg), 2, 2, params,
                      tokens)
