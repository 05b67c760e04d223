"""Kimi Linear (``models/kimi_linear.py``) against the plain float32
reference (``benchmark/reference/kimi_linear.py``): logits, loss and every
leaf's gradient in both layouts, the bf16 preset, rows of a batch that do
not meet, the latent layer without rotary as sarvam-105b's with an identity
table, the sixteen ranks' expert parts adding up to the uncut layer and the
chunks of the sequence. The walks are ``test_kimi_linear_walk.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from model_checks import (
    Model,
    bf16_near_the_reference,
    match_the_reference,
    tree_close,
)

from benchmark.reference import kimi_linear as ref
from tepdist_tpu.models import afmoe, decoder, layers, sarvam_mla
from tepdist_tpu.models import kimi_linear as kimi

CFG = kimi.CONFIGS["test"]           # experts 8..23 of 32 held; a dense
#                                      layer, then kda, kda, mla, kda
WHOLE = dataclasses.replace(CFG, experts_held=(0, CFG.num_experts))
MOE_LAYERS = (1, 2, 3, 4)
OUTSIDE = ("tok_emb", "norm_f", "lm_head")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def hyper(cfg):
    return ref.Hyper(
        kda_heads=cfg.kda_num_heads, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, top_k=cfg.num_experts_per_tok,
        held=cfg.experts_held, route_scale=cfg.routed_scaling_factor,
        eps=cfg.rms_norm_eps)


def uneven(params):
    """Norm gains and a selection bias away from their initial values, so
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


# The row, and the file's (and ``test_kimi_linear_walk.py``'s) compiled
# programs. One batch for every case of the float32 preset, so that a program
# and the reference are compiled once a layout and the reference is run once.
MODEL = Model(
    kimi, ref, CFG, hyper, OUTSIDE,
    stack=lambda tree, cfg: decoder.stack_layers(
        tree, decoder.run_stacks(cfg.kinds), OUTSIDE, kimi.GROUPS),
    uneven=uneven,
    opt={"name": "adamw_bf16_router_bias", "learning_rate": 1e-3,
         "bias_rate": 0.001})
TOKENS = MODEL.tokens()
init_params, uneven_params = MODEL.init_params, MODEL.uneven_params
to_reference, loss_and_grads = MODEL.to_reference, MODEL.loss_and_grads
ref_loss, ref_loss_and_grads = MODEL.ref_loss, MODEL.ref_loss_and_grads
ref_expert_counts = MODEL.ref_expert_counts


def biases(tree, stacked):
    """The expert layers' selection biases (or their counts) [4, E]."""
    if stacked:
        return jnp.concatenate([tree[f"run{r}"]["router_bias"]
                                for r in (1, 2, 3)])
    return jnp.stack([tree[f"l{i}"]["router_bias"] for i in MOE_LAYERS])


def test_the_presets_hold_the_published_structure():
    big = kimi.CONFIGS["48b-a3b"]
    assert big.mixers.count(kimi.KDA) == 20 and len(big.mixers) == 27
    assert [i + 1 for i, m in enumerate(big.mixers) if m == kimi.MLA] \
        == [4, 8, 12, 16, 20, 24, 27]
    assert big.kinds[0] == (kimi.KDA, kimi.DENSE) \
        and big.kinds[3] == (kimi.MLA, kimi.MOE)
    assert big.rope_table is None and big.softmax_scale == 192 ** -0.5
    assert CFG.kinds == ((kimi.KDA, kimi.DENSE), (kimi.KDA, kimi.MOE),
                         (kimi.KDA, kimi.MOE), (kimi.MLA, kimi.MOE),
                         (kimi.KDA, kimi.MOE))
    assert [(n, c) for _, n, c in decoder.run_stacks(CFG.kinds)] \
        == [(0, 1), (1, 2), (3, 1), (4, 1)]
    assert kimi.CONFIGS["test_bf16"].dtype == jnp.bfloat16
    smoke = kimi.CONFIGS["smoke"]
    assert (smoke.kda_head_dim, smoke.qk_nope_head_dim,
            smoke.qk_rope_head_dim, smoke.v_head_dim) == (128, 128, 64, 128)
    # The decays' initialisation: about (0.2, 0.999) a token.
    blk = init_params(CFG)["l1"]
    a = jnp.zeros((1, 1, CFG.hidden_size))
    g = kimi.log_decays(blk, a, CFG)
    assert g.dtype == jnp.float32 and -1.7 < float(g.min()) \
        and float(g.max()) < -0.0009


@pytest.mark.parametrize("stacked,remat", [(False, False), (True, True)],
                         ids=["unstacked-plain", "stacked-remat"])
def test_logits_loss_and_every_gradient_match_the_reference(stacked, remat):
    # The logits once: the loss holds both.
    grads = match_the_reference(MODEL, stacked, remat, logits=not stacked)
    # The bias's "gradient" is the count of its router's choices.
    np.testing.assert_array_equal(np.asarray(biases(grads, stacked)),
                                  np.asarray(MODEL.reference("counts")))


def test_bf16_program_stays_near_the_float32_reference():
    cfg = dataclasses.replace(kimi.CONFIGS["test_bf16"], remat=True,
                              loss_chunk=16)
    bf16_near_the_reference(MODEL, cfg, TOKENS)


def test_the_convs_and_the_state_never_cross_between_rows_of_a_batch():
    """The rows in another order give the same rows (no conv tail, no
    state and no routing goes from one sequence to the next), and a
    position never sees a later one: the convs and the rule are causal.
    One shape, so one compiled program (``test_logits...``'s)."""
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


def test_the_latent_layer_without_rotary_is_sarvams_with_an_identity_table():
    """``attention_inputs`` handed None leaves the rotary parts as they are:
    what a table that turns nothing gives, and what the reference computes
    with no position anywhere."""
    blk = uneven_params(False)["l3"]
    a = jax.random.normal(jax.random.PRNGKey(4), (2, 32, CFG.hidden_size))
    still = layers.RopeTable((0.0,) * (CFG.qk_rope_head_dim // 2))
    plain = sarvam_mla.attention_inputs(blk, a, CFG, None)
    turned = sarvam_mla.attention_inputs(blk, a, CFG, still, 5)
    for got, want in zip(plain, turned):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-7)
    assert jax.eval_shape(lambda b, x: kimi.block(b, x, CFG, kimi.MLA), blk,
                          a).shape == a.shape
    got = sarvam_mla.attend(blk, a, CFG) @ blk["wo"]
    normed = layers.rms_norm(a, blk["input_ln"], CFG.rms_norm_eps)
    want = jnp.stack([ref._attention(blk, s, hyper(CFG), ref.identity)
                      for s in normed])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-6)


def test_the_sixteen_ranks_add_up_with_the_shared_expert_counted_once():
    """Shares (0,2) .. (30,2) of the 32-wide router, the shared expert once:
    the uncut reference's whole expert layer; and the layer this model runs
    is Trinity's own function."""
    assert kimi.moe is afmoe.moe and kimi.swiglu is afmoe.swiglu
    params = uneven(init_params(WHOLE))
    blk = params["l2"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, CFG.hidden_size))
    ranks = range(0, CFG.num_experts, 2)
    assert len(ranks) == 16
    for first in ranks:
        share, _ = kimi.rank_share(params, WHOLE, (first, 2))
        assert share["l2"]["w_gate"].shape[0] == 2 \
            and share["l2"]["wq"] is params["l2"]["wq"] \
            and share["l0"]["w_gate"] is params["l0"]["w_gate"]

    @jax.jit        # one trace for the sixteen ranks, not sixteen dispatches
    def every_rank(params, x):
        blk = params["l2"]
        shared = afmoe.swiglu(x, blk["shared_gate"], blk["shared_up"],
                              blk["shared_down"])
        total = shared
        for first in ranks:
            share, cfg = kimi.rank_share(params, WHOLE, (first, 2))
            total = total + afmoe.moe(share["l2"], x, cfg) - shared
        return total

    hp = hyper(WHOLE)
    want = jax.jit(lambda blk, x: jnp.stack(
        [ref._moe(blk, s, hp, ref.identity)[0] for s in x]))(blk, x)
    np.testing.assert_allclose(np.asarray(every_rank(params, x)),
                               np.asarray(want), rtol=0, atol=2e-6)


def test_a_rank_of_the_whole_model_is_the_reference_at_the_same_share():
    params = init_params(WHOLE)
    share, cfg = kimi.rank_share(params, WHOLE, CFG.experts_held)
    assert cfg == CFG
    # Both compiled already: the share has ``CFG``'s shapes.
    want, _ = ref_loss_and_grads(to_reference(share, cfg), TOKENS, hyper(cfg))
    (got, _), _ = MODEL.all_three(share, TOKENS, cfg)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    whole = ref_loss(to_reference(params, WHOLE), TOKENS, hyper(WHOLE))
    assert abs(float(whole) - float(want)) > 1e-5


def test_the_blocks_token_wise_parts_in_chunks_change_nothing(monkeypatch):
    """Forced small, four chunks of 8 positions give the whole sequence's
    loss and gradients (the convs and the kernels see the whole sequence,
    routing is a token's own), and at the published sizes a chunk is 2,048
    tokens."""
    big = kimi.CONFIGS["48b-a3b"]
    assert sarvam_mla._widest(big) == 9216
    assert layers.tokens_a_chunk(1, 8192, sarvam_mla._widest(big)) == 2048
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params, tokens = uneven_params(True), TOKENS
    whole = loss_and_grads(params, tokens, cfg)
    monkeypatch.setattr(layers, "_CHUNK_ELEMENTS",
                        2 * 8 * sarvam_mla._widest(cfg))
    assert layers.tokens_a_chunk(2, 32, sarvam_mla._widest(cfg)) == 8
    # Traced anew: the chunk's size is read while the loss is traced.
    loss, grads = jax.jit(jax.value_and_grad(kimi.loss_fn),
                          static_argnums=2)(params, tokens, cfg)
    assert float(loss) == pytest.approx(float(whole[0]), rel=1e-6)
    tree_close(grads, whole[1], 1e-5, skip=())
