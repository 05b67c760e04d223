"""Qwen3-Next (``models/qwen3_next.py``) against the plain float32 reference
(``benchmark/reference/qwen3_next.py``): logits, loss and every leaf's
gradient in both layouts, the bf16 preset, rows of a batch that do not meet,
the zero-centred norm's gain in float32, the partial rotary, the eight ranks'
expert parts adding up to the uncut layer with the gated shared expert
counted once, and the held layer's routing statistics. The walks are
``test_qwen3_next_walk.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from model_checks import (
    KEY,
    Model,
    bf16_near_the_reference,
    match_the_reference,
)

from benchmark.reference import qwen3_next as ref
from tepdist_tpu.models import afmoe, decoder, kimi_linear, layers, mellum
from tepdist_tpu.models import qwen3_next as qwen
from tepdist_tpu.telemetry import metrics

CFG = qwen.CONFIGS["test"]           # experts 8..23 of 32 held; gdn, gdn,
#                                      gdn, attention
WHOLE = dataclasses.replace(CFG, experts_held=(0, CFG.num_experts))
OUTSIDE = ("tok_emb", "norm_f", "lm_head")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def hyper(cfg):
    return ref.Hyper(
        key_heads=cfg.linear_num_key_heads,
        value_heads=cfg.linear_num_value_heads,
        n_head=cfg.num_attention_heads, n_kv_head=cfg.num_key_value_heads,
        rotary_dim=cfg.rotary_dim, top_k=cfg.num_experts_per_tok,
        held=cfg.experts_held, rope_theta=cfg.rope_theta,
        eps=cfg.rms_norm_eps)


def uneven(params):
    """Norm leaves away from their initial values (a zero-centred one from
    0), so that a gain left out, or taken as ``w`` for ``1 + w``, shows."""
    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name))
        if name.endswith("_ln']") or "norm" in name:
            return a + 0.2 * jax.random.normal(key, a.shape)
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


# The row, and the file's (and ``test_qwen3_next_walk.py``'s) compiled
# programs. One batch for every case of the float32 preset, so that a program
# and the reference are compiled once a layout and the reference is run once.
MODEL = Model(
    qwen, ref, CFG, hyper, OUTSIDE,
    stack=lambda tree, cfg: decoder.stack_layers(
        tree, decoder.run_stacks(cfg.kinds), OUTSIDE, qwen.GROUPS),
    uneven=uneven, opt={"name": "adamw_bf16", "learning_rate": 1e-3})
TOKENS = MODEL.tokens()
init_params, uneven_params = MODEL.init_params, MODEL.uneven_params
to_reference, loss_and_grads = MODEL.to_reference, MODEL.loss_and_grads
ref_loss, ref_loss_and_grads = MODEL.ref_loss, MODEL.ref_loss_and_grads


def test_the_presets_hold_the_published_structure():
    big = qwen.CONFIGS["80b-a3b"]
    assert big.kinds.count(qwen.GDN) == 36 and len(big.kinds) == 48
    assert [i for i, m in enumerate(big.kinds) if m == qwen.ATTN] \
        == list(range(3, 48, 4))
    assert big.rotary_dim == 64 and big.head_dim == 256
    assert (big.linear_num_key_heads, big.linear_num_value_heads) == (16, 32)
    assert CFG.kinds == (qwen.GDN,) * 3 + (qwen.ATTN,)
    assert [(n, c) for _, n, c in decoder.run_stacks(CFG.kinds)] \
        == [(0, 3), (3, 1)]
    # The published ratios at the tests' widths.
    assert CFG.linear_num_value_heads == 2 * CFG.linear_num_key_heads
    assert CFG.num_attention_heads == 8 * CFG.num_key_value_heads
    assert CFG.rotary_dim * 4 == CFG.head_dim
    assert CFG.num_experts_per_tok == 10
    assert qwen.CONFIGS["test_bf16"].dtype == jnp.bfloat16
    smoke = qwen.CONFIGS["smoke"]
    assert (smoke.linear_key_head_dim, smoke.head_dim, smoke.rotary_dim) \
        == (128, 256, 64)
    # The decays' initialisation: about (0.2, 0.999) a token, one a head.
    blk = init_params(CFG)["l0"]
    g, beta = qwen.gdn_gates(blk, jnp.zeros((1, 1, 8)))
    assert g.shape == beta.shape == (1, 1, 4) and g.dtype == jnp.float32
    assert -1.7 < float(g.min()) and float(g.max()) < -0.0009
    # What is shared is called, not copied.
    assert qwen.l2_norm is kimi_linear.l2_norm \
        and qwen.gated_norm is kimi_linear.gated_norm \
        and qwen.swiglu is afmoe.swiglu and qwen.mellum is mellum \
        and qwen.attn_gate is layers.attn_gate


@pytest.mark.parametrize("stacked,remat", [(False, False), (True, True)],
                         ids=["unstacked-plain", "stacked-remat"])
def test_logits_loss_and_every_gradient_match_the_reference(stacked, remat):
    # The logits once: the loss holds both.
    match_the_reference(MODEL, stacked, remat, logits=not stacked)


def test_bf16_program_stays_near_the_float32_reference():
    cfg = dataclasses.replace(qwen.CONFIGS["test_bf16"], remat=True,
                              loss_chunk=16)
    bf16_near_the_reference(MODEL, cfg, TOKENS)


def test_the_conv_and_the_state_never_cross_between_rows_of_a_batch():
    """The rows in another order give the same rows (no conv tail, no
    state and no routing goes from one sequence to the next), and a
    position never sees a later one: the conv, the rule and the attention
    are causal. One shape, so one compiled program (``test_logits...``'s)."""
    params = uneven_params(False)
    order = jnp.array([1, 0])
    both = MODEL.logits(params, TOKENS, CFG)
    np.testing.assert_allclose(
        np.asarray(both[order]),
        np.asarray(MODEL.logits(params, TOKENS[order], CFG)), rtol=0,
        atol=1e-6)
    assert float(jnp.abs(both[0] - both[1]).max()) > 1e-3
    later = TOKENS.at[:, 16:].set((TOKENS[:, 16:] + 7) % CFG.vocab_size)
    changed = MODEL.logits(params, later, CFG)
    np.testing.assert_allclose(np.asarray(both[:, :16]),
                               np.asarray(changed[:, :16]), rtol=0,
                               atol=1e-6)
    assert float(jnp.abs(both[:, 16:] - changed[:, 16:]).max()) > 1e-3


def test_the_zero_centred_gain_is_made_in_float32():
    """``1 + w`` in bf16 is 1 for ``|w| < 2^-8``: the gain is widened
    first, so a small leaf still moves the result."""
    x = jax.random.normal(KEY, (4, 64), jnp.float32).astype(jnp.bfloat16)
    w = jnp.full((64,), 2.0 ** -10, jnp.bfloat16)
    assert float((1 + w).astype(jnp.float32).max()) == 1.0
    got = layers.rms_norm0(x.astype(jnp.float32), w, 1e-6)
    plain = layers.rms_norm(x.astype(jnp.float32), jnp.ones((64,)), 1e-6)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(plain) * (1 + 2.0 ** -10),
                               rtol=1e-6)
    assert layers.rms_norm0(x, w, 1e-6).dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(layers.rms_norm0(x, jnp.zeros((64,)), 1e-6)),
        np.asarray(layers.rms_norm(x, jnp.ones((64,)), 1e-6)))


def test_the_rotary_leaves_the_channels_past_its_width_as_they_were():
    """At the published widths: 64 of 256 channels turn, 64-255 pass; and
    ``gqa_heads`` hands ``rotary_dim`` on (the attention layer against the
    reference's, whose rotary is its own)."""
    x = jax.random.normal(KEY, (1, 2, 8, 256))
    out = layers.rope(x, 1e7, start=3, rotary_dim=64)
    np.testing.assert_array_equal(np.asarray(out[..., 64:]),
                                  np.asarray(x[..., 64:]))
    assert float(jnp.abs(out[..., :64] - x[..., :64]).max()) > 1e-2
    blk = uneven_params(False)["l3"]
    a = jax.random.normal(jax.random.PRNGKey(4), (2, 32, CFG.hidden_size))
    got = qwen.attention(blk, a, CFG)
    assert metrics().gauge("attn_rotary_dim").value == 4
    want = jnp.stack([ref._attention(blk, s, hyper(CFG), ref.identity)
                      for s in a])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-6)
    whole = jnp.stack([ref._attention(
        blk, s, hyper(CFG)._replace(rotary_dim=CFG.head_dim), ref.identity)
        for s in a])
    assert float(jnp.abs(whole - want).max()) > 1e-4


def test_the_eight_ranks_add_up_with_the_gated_shared_expert_counted_once():
    """Shares (0,4) .. (28,4) of the 32-wide router, the shared expert
    times its sigmoid gate once: the uncut reference's whole expert
    layer."""
    params = uneven(init_params(WHOLE))
    blk = params["l1"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, CFG.hidden_size))
    ranks = range(0, CFG.num_experts, 4)
    assert len(ranks) == 8
    for first in ranks:
        share, _ = qwen.rank_share(params, WHOLE, (first, 4))
        assert share["l1"]["w_gate"].shape[0] == 4 \
            and share["l1"]["wqkv"] is params["l1"]["wqkv"] \
            and share["l3"]["wq"] is params["l3"]["wq"]

    @jax.jit        # one trace for the eight ranks, not eight dispatches
    def every_rank(params, x):
        blk = params["l1"]
        shared = afmoe.swiglu(x, blk["shared_gate"], blk["shared_up"],
                              blk["shared_down"]) * jax.nn.sigmoid(
            x @ blk["shared_expert_gate"])
        total = shared
        for first in ranks:
            share, cfg = qwen.rank_share(params, WHOLE, (first, 4))
            total = total + qwen.moe(share["l1"], x, cfg) - shared
        return total

    hp = hyper(WHOLE)
    want = jax.jit(lambda blk, x: jnp.stack(
        [ref._moe(blk, s, hp, ref.identity)[0] for s in x]))(blk, x)
    np.testing.assert_allclose(np.asarray(every_rank(params, x)),
                               np.asarray(want), rtol=0, atol=2e-6)


def test_a_rank_of_the_whole_model_is_the_reference_at_the_same_share():
    params = init_params(WHOLE)
    share, cfg = qwen.rank_share(params, WHOLE, CFG.experts_held)
    assert cfg == CFG
    # Both compiled already: the share has ``CFG``'s shapes.
    want, _ = ref_loss_and_grads(to_reference(share, cfg), TOKENS, hyper(cfg))
    (got, _), _ = MODEL.all_three(share, TOKENS, cfg)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    whole = ref_loss(to_reference(params, WHOLE), TOKENS, hyper(WHOLE))
    assert abs(float(whole) - float(want)) > 1e-5


def test_the_held_layers_routing_stats():
    """Rows a held expert and the live share of the tiles laid out, from
    the routers' own choices outside any step."""
    stats = decoder.routing_stats(
        jax.jit(qwen.expert_choices, static_argnums=2), init_params(CFG),
        TOKENS, CFG)
    assert qwen.routing_stats.func is decoder.routing_stats \
        and qwen.routing_stats.args == (qwen.expert_choices,)
    L, S, k = CFG.num_hidden_layers, 2 * 32, CFG.num_experts_per_tok
    assert stats["experts"].shape == (L, S, k)
    assert stats["held_rows"].shape == (L, CFG.experts_held[1])
    assert stats["moe_assignments_held"] \
        + stats["moe_assignments_elsewhere"] == L * S * k
    assert stats["moe_tokens_dropped"] == 0
    assert 0 < stats["moe_layout_live_share"] <= 1
    assert stats["moe_held_rows_mean"] == pytest.approx(
        stats["moe_assignments_held"] / (L * CFG.experts_held[1]))
