"""Sequence axis as a planner strategy (VERDICT r1 item 4 / SURVEY §5.7).

The reference only reserves a 'token parallel' slot (README.md:16); here
the planner detects softmax(QK^T)V motifs, proposes data x seq meshes,
prices them with the overlap-aware ring cost, and lowers the winner to
ops/ring_attention via a pre-differentiation rewrite."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tepdist_tpu.core.mesh import MeshTopology
from tepdist_tpu.graph.jaxpr_graph import trace_graph
from tepdist_tpu.models import gpt2
from tepdist_tpu.parallel.attention_motif import (
    build_ring_rewritten,
    detect_motifs,
    ring_comm_cost,
)
from tepdist_tpu.train import explore_parallelism, plan_training


def test_motif_detection_on_gpt2():
    """One closed motif per layer on the forward loss graph, with the
    model's scale and causal mask recognized."""
    cfg = gpt2.CONFIGS["test"]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    toks = gpt2.fake_batch(cfg, 2, 32)
    graph, _, _ = trace_graph(lambda p, t: gpt2.loss_fn(p, t, cfg),
                              params, toks)
    motifs = detect_motifs(graph)
    assert len(motifs) == cfg.n_layer
    for m in motifs:
        assert m.causal
        assert m.seq_len == 32
        np.testing.assert_allclose(m.scale, 1.0 / np.sqrt(cfg.head_dim),
                                   rtol=1e-6)
    # Grad graph: fwd motifs escape into the backward — only visible with
    # allow_escape (pricing mode).
    ggrad, _, _ = trace_graph(
        jax.value_and_grad(lambda p, t: gpt2.loss_fn(p, t, cfg)),
        params, toks)
    assert not detect_motifs(ggrad)
    assert len(detect_motifs(ggrad, allow_escape=True)) == cfg.n_layer


def test_ring_rewrite_matches_dense_forward(devices):
    """The pre-differentiation rewrite computes the same loss."""
    from jax.sharding import Mesh

    cfg = gpt2.CONFIGS["test"]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(1))
    toks = gpt2.fake_batch(cfg, 2, 32)
    loss = lambda p, t: gpt2.loss_fn(p, t, cfg)
    graph, _, _ = trace_graph(loss, params, toks)
    motifs = detect_motifs(graph)
    mesh = Mesh(np.array(devices[:4]).reshape(4), ("seq",))
    rw = build_ring_rewritten(graph, motifs, mesh, "seq")
    flat = jax.tree_util.tree_leaves(((params, toks), {}))
    np.testing.assert_allclose(float(jax.jit(rw)(*flat)[0]),
                               float(jax.jit(loss)(params, toks)), rtol=2e-5)


def test_seq_plan_training_matches_dense(devices):
    """data x seq training (ring attention in fwd AND bwd) follows the
    dense single-mesh trajectory exactly."""
    cfg = gpt2.CONFIGS["test"]
    toks = gpt2.fake_batch(cfg, 4, 32)
    tx = optax.adam(1e-2)
    loss = lambda p, t: gpt2.loss_fn(p, t, cfg)

    plan = plan_training(loss, tx, gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                         toks, topology=MeshTopology([("data", 2),
                                                      ("seq", 4)]),
                         num_micro_batches=1)
    seq_losses = [plan.step(toks) for _ in range(3)]
    ref = plan_training(loss, tx, gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                        toks, topology=MeshTopology([("data", 1)]),
                        num_micro_batches=1)
    ref_losses = [ref.step(toks) for _ in range(3)]
    np.testing.assert_allclose(seq_losses, ref_losses, rtol=2e-4)


def test_exploration_chooses_ring_attention_at_long_context():
    """VERDICT item 4 'done' bar: on a long-T small-batch GPT-2, the
    unannotated planner picks a topology with a seq axis — ring hops hide
    under block compute while TP keeps paying activation psums."""
    cfg = dataclasses.replace(gpt2.CONFIGS["test"], n_ctx=32768, n_head=2)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    toks = gpt2.fake_batch(cfg, 2, 32768)
    best = explore_parallelism(lambda p, t: gpt2.loss_fn(p, t, cfg),
                               params, toks, n_devices=8)
    assert best["kind"] == "spmd"
    assert any(n == "seq" for n, _ in best["topology"].device_axes()), (
        best["topology"])


def test_ring_cost_overlap_hides_at_long_t():
    """The exposed ring cost per token VANISHES as T grows (hop bytes are
    linear in T, block compute quadratic) — the economics that make the
    planner pick seq at long context."""
    def exposed_per_token(T):
        cfg_t = dataclasses.replace(gpt2.CONFIGS["test"], n_ctx=T)
        params = gpt2.init_params(cfg_t, jax.random.PRNGKey(0))
        toks = gpt2.fake_batch(cfg_t, 2, T)
        graph, _, _ = trace_graph(
            lambda p, t: gpt2.loss_fn(p, t, cfg_t), params, toks)
        motifs = detect_motifs(graph)
        return ring_comm_cost(motifs, 4) / T

    assert exposed_per_token(8192) < 0.5 * exposed_per_token(512)


def test_detection_handles_div_scale_and_rejects_additive_mask():
    """div-by-sqrt(d) folds into scale; an additive mask (mask * -1e9) or
    a windowed (two-comparison) mask is rejected rather than silently
    rewritten into plain causal attention."""
    import math

    def attn_div(q, k, v):
        T = q.shape[2]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e9)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    def attn_additive(q, k, v):
        T = q.shape[2]
        i = jnp.arange(T)[:, None]
        j = jnp.arange(T)[None, :]
        bias = (j > i).astype(jnp.float32) * (-1e9)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) + bias
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    def attn_window(q, k, v):
        T = q.shape[2]
        i = jnp.arange(T)[:, None]
        j = jnp.arange(T)[None, :]
        mask = (j <= i) & (j > i - 8)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
        s = jnp.where(mask, s, -1e9)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    q = jax.ShapeDtypeStruct((2, 2, 32, 16), jnp.float32)
    g_div, _, _ = trace_graph(attn_div, q, q, q)
    motifs = detect_motifs(g_div)
    assert len(motifs) == 1
    np.testing.assert_allclose(motifs[0].scale, 1.0 / np.sqrt(16), rtol=1e-6)
    assert motifs[0].causal

    g_add, _, _ = trace_graph(attn_additive, q, q, q)
    assert detect_motifs(g_add) == []
    g_win, _, _ = trace_graph(attn_window, q, q, q)
    assert detect_motifs(g_win) == []


def test_auto_parallel_direct_seq_topology_rewrites(devices):
    """auto_parallel called directly (not via plan_training) on a forward
    fn with a seq topology must EXECUTE the ring rewrite — the plan is
    priced with the ring cost, so GSPMD-gathered attention would silently
    underperform the estimate (r2 review finding)."""
    from tepdist_tpu.parallel.auto_parallel import auto_parallel

    cfg = gpt2.CONFIGS["test"]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(2))
    toks = gpt2.fake_batch(cfg, 2, 32)

    fwd = lambda p, t: gpt2.loss_fn(p, t, cfg)
    topo = MeshTopology([("seq", 4)])
    plan = auto_parallel(fwd, topo, params, toks)
    assert plan.sharding_plan.motifs, "seq plan must carry motif rewrites"
    out = plan.step(params, toks)
    np.testing.assert_allclose(float(out), float(fwd(params, toks)),
                               rtol=2e-5)


def test_flash_motif_detection_on_gpt2():
    """VERDICT r3 weak #3: a flash (custom_vjp/pallas) GPT-2 — where the
    attention chain is fused inside the kernel and invisible to the
    einsum matcher — still yields motifs via the kernel's self-describing
    name tag, with causal/scale recovered exactly."""
    cfg = dataclasses.replace(gpt2.CONFIGS["test"], attn="flash", n_ctx=256)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    toks = gpt2.fake_batch(cfg, 2, 256)
    graph, _, _ = trace_graph(lambda p, t: gpt2.loss_fn(p, t, cfg),
                              params, toks)
    motifs = detect_motifs(graph)
    assert len(motifs) == cfg.n_layer
    for m in motifs:
        assert m.flash and m.causal and m.seq_dim == 1
        assert m.seq_len == 256
        np.testing.assert_allclose(m.scale, 1.0 / np.sqrt(cfg.head_dim),
                                   rtol=1e-6)
    # Grad graphs (pricing mode) see them too — the fwd kernel keeps its
    # tag inside the VJP trace.
    ggrad, _, _ = trace_graph(
        jax.value_and_grad(lambda p, t: gpt2.loss_fn(p, t, cfg)),
        params, toks)
    assert len(detect_motifs(ggrad, allow_escape=True)) >= cfg.n_layer


def test_flash_ring_rewrite_matches_dense_forward(devices):
    """The rewrite lowers tagged flash call sites to
    ring_attention(inner='flash') and reproduces the dense loss."""
    from jax.sharding import Mesh

    cfg = dataclasses.replace(gpt2.CONFIGS["test"], attn="flash", n_ctx=256)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(1))
    toks = gpt2.fake_batch(cfg, 2, 256)
    loss = lambda p, t: gpt2.loss_fn(p, t, cfg)
    graph, _, _ = trace_graph(loss, params, toks)
    motifs = detect_motifs(graph)
    assert motifs and all(m.flash for m in motifs)
    mesh = Mesh(np.array(devices[:4]).reshape(4), ("seq",))
    rw = build_ring_rewritten(graph, motifs, mesh, "seq")
    flat = jax.tree_util.tree_leaves(((params, toks), {}))
    np.testing.assert_allclose(float(jax.jit(rw)(*flat)[0]),
                               float(jax.jit(loss)(params, toks)), rtol=2e-5)


def test_flash_seq_plan_training_matches_dense(devices):
    """Long-T GPT-2 with attn='flash' gets a ring plan UNANNOTATED via the
    topology's seq axis and follows the dense trajectory (the r3 'flash
    and auto-SP are mutually exclusive' gap, closed)."""
    cfg = dataclasses.replace(gpt2.CONFIGS["test"], attn="flash", n_ctx=256)
    toks = gpt2.fake_batch(cfg, 4, 256)
    tx = optax.adam(1e-2)
    loss = lambda p, t: gpt2.loss_fn(p, t, cfg)

    plan = plan_training(loss, tx,
                         gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                         toks, topology=MeshTopology([("data", 2),
                                                      ("seq", 4)]),
                         num_micro_batches=1)
    seq_losses = [plan.step(toks) for _ in range(3)]
    ref_cfg = dataclasses.replace(cfg, attn="einsum")
    ref = plan_training(lambda p, t: gpt2.loss_fn(p, t, ref_cfg), tx,
                        gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                        toks, topology=MeshTopology([("data", 1)]),
                        num_micro_batches=1)
    ref_losses = [ref.step(toks) for _ in range(3)]
    np.testing.assert_allclose(seq_losses, ref_losses, rtol=2e-4)


def test_auto_parallel_direct_seq_topology_rewrites_flash(devices):
    """The r4 review repro: auto_parallel called directly on a FLASH
    forward fn with a seq topology executes the flash-inner ring rewrite
    (rank-3 operands, live LSE residual re-bound) instead of crashing in
    the einsum lowering path."""
    from tepdist_tpu.parallel.auto_parallel import auto_parallel

    cfg = dataclasses.replace(gpt2.CONFIGS["test"], attn="flash", n_ctx=256)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(2))
    toks = gpt2.fake_batch(cfg, 2, 256)

    fwd = lambda p, t: gpt2.loss_fn(p, t, cfg)
    topo = MeshTopology([("seq", 4)])
    plan = auto_parallel(fwd, topo, params, toks)
    assert plan.sharding_plan.motifs, "seq plan must carry motif rewrites"
    out = plan.step(params, toks)
    np.testing.assert_allclose(float(out), float(fwd(params, toks)),
                               rtol=2e-5)


def test_flash_grad_graph_not_rewritable():
    """detect_motifs on a flash GRAD graph yields nothing without
    allow_escape (the lse residual feeds the backward kernels), so
    plan_axes keeps its plan-via-plan_training guidance error."""
    cfg = dataclasses.replace(gpt2.CONFIGS["test"], attn="flash", n_ctx=256)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    toks = gpt2.fake_batch(cfg, 2, 256)
    ggrad, _, _ = trace_graph(
        jax.value_and_grad(lambda p, t: gpt2.loss_fn(p, t, cfg)),
        params, toks)
    assert detect_motifs(ggrad) == []
    assert len(detect_motifs(ggrad, allow_escape=True)) >= cfg.n_layer


def test_seq_impl_choice_ring_vs_ulysses():
    """The seq strategy prices BOTH algorithms and returns the argmin;
    indivisible head counts make ulysses infeasible (inf) so ring wins
    regardless of shape."""
    from tepdist_tpu.parallel.attention_motif import (
        best_seq_comm,
        ring_comm_cost,
        ulysses_comm_cost,
    )

    def motifs_for(T, H):
        cfg_t = dataclasses.replace(gpt2.CONFIGS["test"], n_ctx=T,
                                    n_head=H, n_embd=H * 16)
        params = gpt2.init_params(cfg_t, jax.random.PRNGKey(0))
        toks = gpt2.fake_batch(cfg_t, 2, T)
        graph, _, _ = trace_graph(
            lambda p, t: gpt2.loss_fn(p, t, cfg_t), params, toks)
        return detect_motifs(graph)

    for (T, H, P) in [(8192, 4, 4), (256, 8, 8), (512, 4, 4)]:
        ms = motifs_for(T, H)
        impl, cost = best_seq_comm(ms, P)
        ring = ring_comm_cost(ms, P)
        uly = ulysses_comm_cost(ms, P)
        want = "ulysses" if uly < ring else "ring"
        assert impl == want and cost == min(ring, uly), (T, H, P)
        assert np.isfinite(cost)
    # Indivisible heads: ulysses infeasible -> ring regardless of shape.
    ms = motifs_for(256, 3)
    impl, cost = best_seq_comm(ms, 4)
    assert impl == "ring" and np.isfinite(cost)


def test_ulysses_lowering_matches_dense(devices):
    """Force the ulysses lowering through the motif rewrite (einsum and
    flash forms) and match the dense loss."""
    from jax.sharding import Mesh

    for attn in ("einsum", "flash"):
        cfg = dataclasses.replace(gpt2.CONFIGS["test"], attn=attn,
                                  n_ctx=256)
        params = gpt2.init_params(cfg, jax.random.PRNGKey(3))
        toks = gpt2.fake_batch(cfg, 2, 256)
        loss = lambda p, t: gpt2.loss_fn(p, t, cfg)
        graph, _, _ = trace_graph(loss, params, toks)
        motifs = detect_motifs(graph)
        assert motifs
        for m in motifs:
            m.impl = "ulysses"
        mesh = Mesh(np.array(devices[:4]).reshape(4), ("seq",))
        rw = build_ring_rewritten(graph, motifs, mesh, "seq")
        flat = jax.tree_util.tree_leaves(((params, toks), {}))
        np.testing.assert_allclose(float(jax.jit(rw)(*flat)[0]),
                                   float(jax.jit(loss)(params, toks)),
                                   rtol=2e-5, err_msg=f"attn={attn}")
