"""Xing4.0 (XingChen-AGI Xing4.0-29B-A4B): the program against the plain
float32 reference at a tiny preset (both heads' logits, both losses, every
leaf's gradient), a block with one lane and maps of one against
``sarvam_mla.block``, the query latent against DeepSeek-V3's equations, the
second loss's positions, the eight shares of the experts adding up to the
uncut layer, the walk through three stacks against the per-layer dicts, a
gradient-accumulation step whose three walks accumulate inside their layer
loops, the gauges its trace sets, the scopes its compiled operations carry
and the routers' choices. One file, so that one process traces each program
once (``tests/conftest.py`` starts it early)."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from kernel_checks import kernel_counts
from model_checks import KEY, tree_close

from benchmark.reference import xing as ref
from tepdist_tpu.models import afmoe, layers, sarvam_mla, xing
from tepdist_tpu.optim import make_optimizer
from tepdist_tpu.parallel.sync_free import build_ga_step
from tepdist_tpu.telemetry import metrics

# Level 1: at LLVM level 0 a gradient of 1e-9 (``hcmtp.alpha_mlp``) lies
# 2.5e-5 of its leaf's largest entry from the reference's where 2e-5 is
# allowed (``test_logits_losses_...[stacked-remat]``).
pytestmark = pytest.mark.usefixtures("optimized_programs")

CFG = xing.CONFIGS["test"]      # hidden 64, 4 heads, experts 2..3 of 8 held,
#                                 four lanes, 5 Sinkhorn rounds; a dense layer,
#                                 two expert layers, one prediction module
B, T = 1, 64                    # 64 tokens, one sequence: the reference
#                                 is a Python loop over sequences
L = CFG.num_hidden_layers
WHOLE = dataclasses.replace(CFG, experts_held=(0, CFG.num_experts))
REMAT = dataclasses.replace(CFG, remat=True, loss_chunk=16)
OPT = {"name": "adamw_bf16_router_bias", "learning_rate": 1e-3,
       "bias_rate": 0.001}
OUTSIDE = ("tok_emb", "norm_f", "lm_head", "mtp_eh", "mtp_hnorm",
           "mtp_enorm", "mtp_norm")
# Traced once a (shapes, configuration) and a module, not once a test.
loss_and_grads = jax.jit(jax.value_and_grad(xing.loss_fn), static_argnums=2)
losses_of = jax.jit(xing.losses, static_argnums=2)
both_logits = jax.jit(
    lambda p, t, cfg: (xing.forward(p, t[:, :-1], cfg),
                       xing.mtp_forward(p, t, cfg)), static_argnums=2)
ref_logits = jax.jit(lambda p, t, hp: ref.logits(p, t, hp), static_argnums=2)


def _ref_loss(p, t, hp):
    main, second = ref.losses(p, t, hp)
    return main + hp.mtp_weight * second, (main, second)


# (loss, (L_main, L_mtp)), gradients: one trace for every test that wants
# the reference's losses, always on the per-layer view of the parameters.
ref_loss_and_grads = jax.jit(jax.value_and_grad(_ref_loss, has_aux=True),
                             static_argnums=2)


@functools.lru_cache(maxsize=None)
def _init(cfg, stacked):
    init = xing.stacked_init_params if stacked else xing.init_params
    return init(cfg, KEY)


def init_params(cfg, stacked=False):
    """``cfg``'s parameters from ``KEY``, made once a preset and layout."""
    return _init(dataclasses.replace(cfg, remat=False, loss_chunk=0), stacked)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def hyper(cfg):
    return ref.Hyper(
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, top_k=cfg.num_experts_per_tok,
        held=cfg.experts_held, route_scale=cfg.routed_scaling_factor,
        rope_theta=cfg.rope_theta,
        yarn=ref.Yarn(cfg.yarn_factor, cfg.yarn_original_max_position,
                      cfg.yarn_beta_fast, cfg.yarn_beta_slow,
                      cfg.yarn_mscale, cfg.yarn_mscale_all_dim),
        eps=cfg.rms_norm_eps, lanes=cfg.hc_mult,
        sinkhorn_iters=cfg.hc_sinkhorn_iters, hc_eps=cfg.hc_eps,
        clamp=cfg.mhc_h_res_clamp, mtp_weight=cfg.mtp_loss_weight)


def to_reference(params):
    """The reference's per-layer view of either layout of the program's
    parameters (one view, so that the reference is traced once)."""
    blocks = [params[f"l{i}"] for i in range(L + 1)] if "l0" in params \
        else xing.layer_dicts(
            params, xing._stacks(CFG) + xing._mtp_stack(CFG), xing.GROUPS)
    return {**{k: params[k] for k in OUTSIDE}, "layers": blocks[:L],
            "mtp_layers": blocks[L:]}


def from_reference(tree, stacked):
    """``to_reference``'s way back, for the reference's gradients."""
    out = {k: tree[k] for k in OUTSIDE}
    out.update({f"l{i}": blk for i, blk in
                enumerate(tree["layers"] + tree["mtp_layers"])})
    if stacked:
        return xing.stack_layers(
            out, xing._stacks(CFG) + xing._mtp_stack(CFG), OUTSIDE,
            xing.GROUPS, xing._GROUP_OF)
    return out


def uneven(params):
    """Norm gains and selection biases away from their initial values, so
    that a gain or a bias left out shows."""
    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name))
        if name.endswith("_ln']") or "norm" in name:
            return a * (1 + 0.2 * jax.random.normal(key, a.shape))
        if "router_bias" in name:
            return 0.05 * jax.random.normal(key, a.shape)
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["unstacked-plain", "stacked-remat"])
def test_logits_losses_and_every_gradient_match_the_reference(stacked):
    """float32 against float32 at the highest precision: what is left is
    the order of sums (the lanes' mixing, the chunked loss, the dropless
    layout against every expert on every token), 2e-5 of a tensor's largest
    entry. The reference takes the second loss over T - 1 positions by a
    slice, the program by a weight of 0 on the last. The ``l{i}`` dicts'
    plain loop: both heads' logits and both losses; the three stacks,
    rematerialised under the chunked loss: the loss and every leaf's
    gradient (one trace of each backward pass is what the suite's clock
    allows; the loop over dicts is ``decoder.walk_layers``' own)."""
    cfg = REMAT if stacked else CFG
    params = uneven(init_params(cfg, stacked))
    tokens = xing.fake_batch(cfg, B, T, seed=1)
    hp, view = hyper(cfg), to_reference(params)
    (want_loss, (main, second)), want = ref_loss_and_grads(view, tokens, hp)
    assert float(second) > 1.0      # a loss, not a rounding
    if not stacked:
        for got, w in zip(both_logits(params, tokens, cfg),
                          ref_logits(view, tokens, hp)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(w),
                                       rtol=0, atol=2e-5)
        for got, w in zip(losses_of(params, tokens, cfg), (main, second)):
            assert float(got) == pytest.approx(float(w), rel=1e-5)
        return
    loss, grads = loss_and_grads(params, tokens, cfg)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    tree_close(grads, from_reference(want, stacked))
    # No gradient reaches a selection bias: it receives its step's counts,
    # the prediction module's router among the three.
    counts = [b for path, b in jax.tree_util.tree_flatten_with_path(grads)[0]
              if "router_bias" in jax.tree_util.keystr(path)]
    assert sum(float(c.sum()) for c in counts) \
        == 3 * B * T * cfg.num_experts_per_tok


def test_a_bf16_program_fails_the_float32_tolerance():
    """The same comparison with the program in bf16 (the precision below
    the preset's) fails the logits' tolerance, by two orders of magnitude,
    on both heads."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = uneven(init_params(CFG))
    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, params)
    tokens = xing.fake_batch(cfg, B, T, seed=1)
    want = ref_logits(to_reference(params), tokens, hyper(cfg))
    for g, w in zip(both_logits(low, tokens, cfg), want):
        assert 100 * 2e-5 < float(jnp.abs(g - w).max()) < 0.5


def test_one_lane_with_maps_of_one_is_sarvams_block(monkeypatch):
    """``hc_mult`` 1, the maps pinned to one, sarvam's weights (a query of
    one projection): the block is ``sarvam_mla.block`` on the same weights,
    dense and routed, bit for bit (float32; the sums are ``x + f`` in both).
    """
    cfg = dataclasses.replace(CFG, hc_mult=1)
    scfg = sarvam_mla.SarvamMLAConfig(**{
        f.name: getattr(cfg, f.name)
        for f in dataclasses.fields(sarvam_mla.SarvamMLAConfig)})
    params = sarvam_mla.init_params(scfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, T, cfg.hidden_size))
    monkeypatch.setattr(
        xing, "_maps", lambda blk, x, cfg, sub: jnp.ones(x.shape[:2] + (3,)))
    for name in ("l0", "l1"):
        blk = params[name]
        assert ("router" in blk) == (name == "l1")
        np.testing.assert_array_equal(
            np.asarray(jax.jit(xing.block, static_argnums=2)(blk, x, cfg)),
            np.asarray(jax.jit(sarvam_mla.block, static_argnums=2)(
                blk, x, scfg)))


def test_the_query_latent_is_deepseek_v3s_and_without_it_nothing_moves():
    """With ``wqa``: ``q = rms(a Wqa; q_ln) Wqb``, a head's first ``Dn``
    channels without position beside its ``Dr`` rotary ones
    (``modeling_deepseek_v3.py``: q_b_proj(q_a_layernorm(q_a_proj(x)))).
    Without it (sarvam's and Kimi's blocks) the operands are those of the
    one projection, bit for bit."""
    blk = uneven(init_params(CFG))["l1"]
    a = jax.random.normal(jax.random.PRNGKey(3), (B, T, CFG.hidden_size))
    Dn, H = CFG.qk_nope_head_dim, CFG.num_attention_heads
    q_nope, q_rope, *rest = sarvam_mla.attention_inputs(blk, a, CFG, None)
    cq = a @ blk["wqa"]
    cq = cq * jax.lax.rsqrt((cq * cq).mean(-1, keepdims=True)
                            + CFG.rms_norm_eps) * blk["q_ln"]
    q = (cq @ blk["wqb"]).reshape(B, T, H, -1)
    np.testing.assert_allclose(np.asarray(q_nope), np.asarray(q[..., :Dn]),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(q_rope), np.asarray(q[..., Dn:]),
                               rtol=0, atol=1e-6)
    rotated = sarvam_mla.attention_inputs(blk, a, CFG, CFG.rope_table)[1]
    np.testing.assert_allclose(
        np.asarray(rotated), np.asarray(layers.rope(
            q[..., Dn:].transpose(0, 2, 1, 3),
            CFG.rope_table).transpose(0, 2, 1, 3)), rtol=0, atol=1e-6)
    # A block without the latent: one projection, and the other operands
    # (the key/value path) do not depend on which the query took.
    plain = {k: v for k, v in blk.items() if k not in ("wqa", "wqb", "q_ln")}
    plain["wq"] = jax.random.normal(
        KEY, (CFG.hidden_size, blk["wqb"].shape[1]))
    got = sarvam_mla.attention_inputs(plain, a, CFG, None)
    np.testing.assert_array_equal(
        np.asarray(got[0]),
        np.asarray((a @ plain["wq"]).reshape(B, T, H, -1)[..., :Dn]))
    for one, other in zip(got[2:], rest):
        np.testing.assert_array_equal(np.asarray(one), np.asarray(other))


def test_the_second_loss_reads_two_ahead_and_not_past_the_end():
    """``L_mtp`` by a weight of 0 on the last position is the reference's
    mean over the first ``T - 1`` by a slice (a weight there, or a target one
    ahead, would be another number), and it moves with a sequence's last
    token, the token two ahead of its last position but one."""
    params = init_params(CFG)
    tokens = xing.fake_batch(CFG, B, T, seed=5)
    moved = tokens.at[:, -1].set((tokens[:, -1] + 1) % CFG.vocab_size)
    seconds = []
    for t in (tokens, moved):
        main, second = losses_of(params, t, CFG)
        _, (want_main, want_second) = ref_loss_and_grads(
            to_reference(params), t, hyper(CFG))[0]
        assert float(main) == pytest.approx(float(want_main), rel=1e-5)
        assert float(second) == pytest.approx(float(want_second), rel=1e-5)
        seconds.append(float(second))
    assert abs(seconds[0] - seconds[1]) > 1e-4
    none = dataclasses.replace(CFG, num_nextn_predict_layers=0)
    with pytest.raises(ValueError, match="one prediction module"):
        xing._depth(dataclasses.replace(CFG, num_nextn_predict_layers=2))
    assert xing._mtp_stack(none) == []


def test_the_eight_shares_add_up_with_everything_else_counted_once():
    """Ranks (0, 1) .. (7, 1) of the 8-wide router: their routed parts, the
    shared expert once, are the uncut reference's whole expert layer, and
    the layer this model runs is Trinity's own function."""
    assert xing.moe is afmoe.moe and xing.router is afmoe.router
    params = uneven(init_params(WHOLE))
    blk = params["l1"]
    x = jax.random.normal(jax.random.PRNGKey(6), (B, T, CFG.hidden_size))

    @jax.jit
    def every_rank(params, x):
        blk = params["l1"]
        shared = afmoe.swiglu(x, blk["shared_gate"], blk["shared_up"],
                              blk["shared_down"])
        total = shared
        for first in range(CFG.num_experts):
            share, cfg = xing.rank_share(params, WHOLE, (first, 1))
            assert share["l1"]["w_up"].shape[0] == 1 \
                and share[f"l{L}"]["w_up"].shape[0] == 1 \
                and share["l1"]["wqb"] is blk["wqb"]    # data-parallel: whole
            total = total + afmoe.moe(share["l1"], x, cfg) - shared
        return total

    want = jax.jit(lambda blk, x: jnp.stack([
        ref._moe(blk, s, hyper(WHOLE), ref.identity)[0] for s in x]))(blk, x)
    np.testing.assert_allclose(np.asarray(every_rank(params, x)),
                               np.asarray(want), rtol=0, atol=2e-6)
    # The held share of the whole model is the reference at that share.
    share, cfg = xing.rank_share(init_params(WHOLE), WHOLE, CFG.experts_held)
    assert cfg == CFG
    tokens = xing.fake_batch(cfg, B, T, seed=7)
    want = ref_loss_and_grads(to_reference(share), tokens, hyper(cfg))[0][1]
    for got, w in zip(losses_of(share, tokens, cfg), want):
        assert float(got) == pytest.approx(float(w), rel=1e-5)


def test_three_stacks_walk_as_the_per_layer_dicts():
    """``dense`` [1], ``blocks`` [2] and ``mtp`` [1] with their maps beside
    them, rematerialised and under the chunked loss, against the ``l{i}``
    dicts' plain loop: the same two losses."""
    stacked, dicts = init_params(REMAT, True), init_params(CFG)
    assert set(stacked) == set(OUTSIDE) | {
        "dense", "blocks", "mtp", "hcdense", "hcblocks", "hcmtp"}
    assert stacked["hcblocks"]["phi_attn"].shape == (
        2, CFG.hc_mult * CFG.hidden_size, 24)
    assert stacked["mtp"]["w_up"].shape[:2] == (1, 2)
    for name, first in (("dense", 0), ("blocks", 1), ("mtp", L)):
        for k, a in {**stacked[name], **stacked["hc" + name]}.items():
            np.testing.assert_array_equal(np.asarray(a[0]),
                                          np.asarray(dicts[f"l{first}"][k]))
    tokens = xing.fake_batch(CFG, B, T, seed=4)
    loss = loss_and_grads(stacked, tokens, REMAT)[0]
    main, second = losses_of(dicts, tokens, CFG)
    assert float(loss) == pytest.approx(
        float(main) + CFG.mtp_loss_weight * float(second), rel=1e-6)
    # At the published sizes a chunk is 1,024 positions of the cell's two
    # sequences.
    assert xing._widest(xing.CONFIGS["29b-a4b"]) == 4 * 3584
    assert layers.tokens_a_chunk(2, 4096, 4 * 3584) == 1024


@functools.lru_cache(maxsize=None)
def _step():
    """A gradient-accumulation step of two micro batches over the stacked
    layout, its token-wise parts forced into eight chunks of 8 positions (as
    the cell's sequences are four chunks of 1,024: the attention sub-layer's
    maps go from the chunk loop before the kernels to the one after, and the
    chunk loop's backward carries the experts' accumulators), compiled
    once: (optimizer, the update, the step, its optimized HLO, the gauges
    its trace set)."""
    params = init_params(REMAT, stacked=True)
    tokens = xing.fake_batch(REMAT, 2 * B, T, seed=8)
    tx = make_optimizer(dict(OPT))
    loss = lambda p, t: xing.loss_fn(p, t, REMAT)          # noqa: E731

    def apply_fn(p, s, g):
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    step = jax.jit(build_ga_step(
        lambda p, t: jax.value_and_grad(loss)(p, t), apply_fn, 2,
        loss_fn=loss))
    chunk, layers._CHUNK_ELEMENTS = layers._CHUNK_ELEMENTS, \
        B * 8 * xing._widest(REMAT)
    try:
        assert layers.tokens_a_chunk(B, T, xing._widest(REMAT)) == 8
        kernels = kernel_counts(step, params, tx.init(params), tokens)
        compiled = step.lower(params, tx.init(params), tokens).compile()
    finally:
        layers._CHUNK_ELEMENTS = chunk
    gauges = {n: metrics().gauge(n).value for n in (
        "ga_fused_bytes", "attn_kept_calls", "moe_stack_in_place_calls",
        "residual_lanes", "mhc_sinkhorn_rounds", "mhc_stream_bytes",
        "mtp_depth", "mtp_loss_weight", "ce_weighted_positions",
        "mla_heads_held")}
    return tx, apply_fn, compiled, kernels, gauges, params, tokens


def test_a_step_accumulates_inside_all_three_walks_and_the_gauges_say_so():
    """Two micro batches: every walked leaf of the three stacks accumulates
    inside its layer loop, each latent-attention forward is kept (4 layers),
    the experts' stacks are read where they lie (12 calls an expert layer,
    the prediction module's among the 3), the gauges hold the lanes, the
    rounds, the module's depth and weight and the weighted positions, and
    the step is a plain gradient and optimizer loop's."""
    tx, apply_fn, compiled, kernels, gauge, params, tokens = _step()
    assert sum(n for name, n in kernels.items()
               if name.startswith("tepdist_mla_fwd__")) == 3   # a walk each
    stacks = sum(a.size * a.dtype.itemsize for name in (
        "dense", "blocks", "mtp", "hcdense", "hcblocks", "hcmtp")
        for a in jax.tree_util.tree_leaves(params[name]))
    assert gauge["ga_fused_bytes"] == stacks
    assert gauge["attn_kept_calls"] == 4
    assert gauge["moe_stack_in_place_calls"] == 12 * 3
    assert gauge["residual_lanes"] == 4
    assert gauge["mhc_sinkhorn_rounds"] == 5
    assert gauge["mhc_stream_bytes"] == B * T * 4 * CFG.hidden_size * 4
    assert gauge["mtp_depth"] == 1
    assert gauge["mtp_loss_weight"] == pytest.approx(0.1)
    assert gauge["ce_weighted_positions"] == B * T
    assert gauge["mla_heads_held"] == 4
    got_loss, got_params, _ = compiled(
        jax.tree_util.tree_map(jnp.copy, params), tx.init(params), tokens)
    halves = [loss_and_grads(params, tokens[i * B:(i + 1) * B], REMAT)
              for i in range(2)]
    assert float(got_loss) == pytest.approx(
        (float(halves[0][0]) + float(halves[1][0])) / 2, rel=1e-5)
    mean = jax.tree_util.tree_map(lambda a, b: (a + b) / 2,
                                  halves[0][1], halves[1][1])
    # Adam's first step is the gradient's sign, 1e-3 a leaf's entry: a
    # tenth of that holds an entry whose gradient is a rounding from zero.
    tree_close(got_params, apply_fn(params, tx.init(params), mean)[0], 1e-3,
               skip=())


def test_the_new_work_carries_its_scopes():
    """In the compiled step's operation names: ``mhc_maps`` / ``mhc_read`` /
    ``mhc_write`` under the part they serve, the query latent's two
    projections beside the key/value path's, ``mtp_in`` under
    ``part_embed``, and the prediction module's block and loss under their
    own parts inside ``mtp``."""
    names = set(re.findall(r'op_name="([^"]*)"', _step()[2].as_text()))
    for words in (("part_mixer", "mla_in", "mhc_maps"),
                  ("part_mixer", "mla_in", "mhc_read"),
                  ("part_mixer", "mla_out", "mhc_write"),
                  ("part_moe", "mhc_maps"), ("part_mlp", "mhc_read"),
                  ("part_moe", "mhc_write"),
                  ("part_mixer", "mla_q_down"), ("part_mixer", "mla_q_up"),
                  ("part_mixer", "mla_kv_down"),
                  ("mtp", "part_embed", "mtp_in"),
                  ("mtp", "part_mixer", "mla_q_up"),
                  ("mtp", "part_moe", "mhc_write"),
                  ("mtp", "part_head_loss")):
        pattern = ".*".join(r"(?<![A-Za-z0-9_])%s(?![A-Za-z0-9_])" % w
                            for w in words)
        assert any(re.search(pattern, n) for n in names), words


def test_the_routers_choices_are_the_references_all_three():
    params = uneven(init_params(CFG))
    tokens = xing.fake_batch(CFG, B, T, seed=9)
    ids = jax.jit(xing.expert_choices, static_argnums=2)(params, tokens, CFG)
    assert ids.shape == (3, B * T, CFG.num_experts_per_tok)

    @jax.jit
    def reference(view, tokens):
        hp, out = hyper(CFG), []
        for t in tokens:
            g, chosen = ref.summed_lanes(view, t[:-1], hp)
            out.append(jnp.stack(
                chosen + ref.mtp_hidden(view, g, t[1:], hp)[1]))
        return jnp.concatenate(out, axis=1)

    want = reference(to_reference(params), tokens)
    assert float(jnp.mean(jnp.sort(ids, -1) == jnp.sort(want, -1))) > 0.99
    stats = xing.held_routing_stats(ids, CFG.num_experts, CFG.moe_tile_m,
                                    CFG.experts_held)
    assert stats["held_rows"].shape == (3, 2)
    assert stats["moe_tokens_dropped"] == 0
    assert xing.routing_stats.__doc__
