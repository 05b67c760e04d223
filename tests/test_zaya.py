"""ZAYA (Zyphra ZAYA1-8B): the program against the plain float32 reference at
a tiny preset with the published ratios, the mixing (its kernels alone:
``test_cca_mix.py``) inside each sequence of a batch and on its kernels in the
model, rotary over half a head, the
router's state from layer to layer and one expert a token through the
dropless layout (the walks, a planned step and the gauges:
``test_zaya_walk.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from kernel_checks import kernel_counts
from model_checks import (
    KEY,
    Model,
    bf16_near_the_reference,
    match_the_reference,
    tree_close,
)

from benchmark.reference import zaya as ref
from tepdist_tpu.models import decoder, layers, zaya
from tepdist_tpu.ops.grouped_matmul import layout_rows
from tepdist_tpu.ops.pallas import cca_mix as cm

CFG = zaya.CONFIGS["test"]           # 8 heads over 2 of 8, three layers
# Heads the kernels take (128 wide), everything else small.
WIDE = dataclasses.replace(CFG, hidden_size=64, head_dim=128,
                           moe_intermediate_size=32, remat=True,
                           loss_chunk=16)
attention = jax.jit(zaya.attention, static_argnums=2)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def hyper(cfg):
    return ref.Hyper(head_dim=cfg.head_dim, rotary_dim=cfg.rotary_dim,
                     rope_theta=cfg.rope_theta, eps=cfg.rms_norm_eps)


def uneven(params):
    """Norm gains, conv biases, temperature, ``gamma`` and selection bias
    away from their initial values, and a router whose probabilities differ
    (at normal(0.02) they are all a sixteenth), so that a leaf left out
    shows."""
    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name))
        noise = jax.random.normal(key, a.shape)
        if name.endswith("_ln']") or "norm_f" in name or "tau" in name \
                or "gamma" in name:
            return a * (1 + 0.2 * noise)
        if "conv_b" in name:
            return 0.1 * noise
        if "router_bias" in name:
            return 0.01 * noise
        if "router_w" in name:
            return a * 20
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


# The row, and the file's (and ``test_zaya_walk.py``'s) compiled programs.
MODEL = Model(
    zaya, ref, CFG, hyper, ("tok_emb", "norm_f"),
    stack=lambda tree, cfg: decoder.stack_layers(
        tree, zaya._stacks(cfg), ("tok_emb", "norm_f")),
    uneven=uneven,
    opt={"name": "adamw_bf16_router_bias", "learning_rate": 1e-3,
         "bias_rate": 0.001})
loss_and_grads, loss_of = MODEL.loss_and_grads, MODEL.loss_of


def test_the_presets_have_the_published_ratios():
    for cfg in (zaya.CONFIGS["8b"], CFG, zaya.CONFIGS["test-bf16"]):
        H, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        assert (H, Hkv) == (8, 2) and H * D == cfg.hidden_size // 2 \
            and Hkv * D == cfg.hidden_size // 8
        assert cfg.rotary_dim == D // 2
        assert (cfg.num_experts, cfg.num_experts_per_tok) == (16, 1)
    big = zaya.CONFIGS["8b"]
    assert (big.head_dim, big.router_hidden_size, big.rope_theta) \
        == (128, 256, 5e6)
    with pytest.raises(ValueError, match="taps"):
        dataclasses.replace(CFG, cca_time0=3)


@pytest.mark.parametrize("stacked,remat", [(False, False), (True, True)],
                         ids=["unstacked-plain", "stacked-remat"])
def test_logits_loss_and_every_gradient_match_the_reference(stacked, remat):
    grads = match_the_reference(MODEL, stacked, remat)
    # Every leaf of a layer takes part, the router's state among them: the
    # first layer's gamma meets r_{-1} = 0, the later layers' a state.
    first = grads["blocks"] if stacked else grads["l0"]
    for name, g in first.items():
        g = g[0] if stacked else g
        assert (name == "router_gamma") == (not np.any(np.asarray(g))), name
    later = grads["blocks"]["router_gamma"][1] if stacked \
        else grads["l1"]["router_gamma"]
    assert np.any(np.asarray(later))
    # The bias's "gradient" is the count of its router's choices; more than
    # one expert is chosen.
    counts = MODEL.reference("counts")
    got = grads["blocks"]["router_bias"] if stacked else jnp.stack(
        [grads[f"l{i}"]["router_bias"] for i in range(3)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(counts))
    assert np.all((np.asarray(counts) > 0).sum(-1) > 4)
    np.testing.assert_array_equal(np.asarray(counts).sum(-1), [64] * 3)


def test_bf16_program_stays_near_the_float32_reference():
    cfg = dataclasses.replace(zaya.CONFIGS["test-bf16"], remat=True,
                              loss_chunk=16)
    bf16_near_the_reference(MODEL, cfg, zaya.fake_batch(cfg, 2, 32, seed=2))


@pytest.mark.parametrize("cfg", [CFG, WIDE], ids=["jax.numpy", "kernels"])
def test_no_mixing_crosses_from_one_sequence_to_the_next(cfg):
    """The value shift and both convs inside each sequence of a batch: a
    batch's attention sublayer is each sequence's alone, and another first
    sequence leaves the second as it was."""
    blk = uneven(zaya.init_params(cfg, KEY))["l1"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg.hidden_size))
    both = attention(blk, x, cfg)
    for i in range(2):
        np.testing.assert_allclose(
            np.asarray(attention(blk, x[i:i + 1], cfg)[0]),
            np.asarray(both[i]), rtol=0, atol=2e-6)
    other = attention(blk, x.at[0].set(-x[0]), cfg)
    np.testing.assert_array_equal(np.asarray(other[1]), np.asarray(both[1]))
    # ... and the shift is by one position, zeros first.
    s = zaya.shifted(x)
    np.testing.assert_array_equal(np.asarray(s[:, 0]), 0)
    np.testing.assert_array_equal(np.asarray(s[:, 1:]), np.asarray(x[:, :-1]))


def test_the_models_kernels_are_its_jax_numpy_mixing(monkeypatch):
    """A model of 128-wide heads runs the kernels (interpreted), one of
    narrower heads the ``jax.numpy`` form; with that form in the kernels'
    place the first model has one loss and one set of gradients."""
    params = uneven(zaya.stacked_init_params(WIDE, KEY))
    tokens = zaya.fake_batch(WIDE, 2, 32, seed=6)
    found = kernel_counts(lambda p: zaya.loss_fn(p, tokens, WIDE), params)
    assert found.get("tepdist_cca_mix_fwd") == 1, found
    small = zaya.stacked_init_params(CFG, KEY)
    assert "tepdist_cca_mix_fwd" not in kernel_counts(
        lambda p: zaya.loss_fn(p, tokens, CFG), small)
    loss, grads = loss_and_grads(params, tokens, WIDE)
    monkeypatch.setattr(zaya.cca_mix, "cca_mix", zaya.cca_mix.reference)
    # Traced anew: which mixing runs is read while the loss is traced.
    want_loss, want = jax.jit(jax.value_and_grad(zaya.loss_fn),
                              static_argnums=2)(params, tokens, WIDE)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    tree_close(grads, want, 1e-5, skip=())


def test_rope_over_half_a_head():
    """``rotary_dim``: the whole-head call on the first channels, the
    identity on the rest; None is the whole head as before."""
    x = jax.random.normal(KEY, (2, 3, 16, 8))
    half = layers.rope(x, 100.0, rotary_dim=4)
    np.testing.assert_array_equal(np.asarray(half[..., 4:]),
                                  np.asarray(x[..., 4:]))
    np.testing.assert_array_equal(
        np.asarray(half[..., :4]), np.asarray(layers.rope(x[..., :4], 100.0)))
    np.testing.assert_array_equal(np.asarray(layers.rope(x, 100.0)),
                                  np.asarray(layers.rope(x, 100.0,
                                                         rotary_dim=8)))
    np.testing.assert_allclose(
        np.asarray(layers.rope(x[:, :, 8:], 100.0, 8, rotary_dim=4)),
        np.asarray(half[:, :, 8:]), rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(half), np.asarray(ref._rope(x, hyper(CFG))), rtol=0,
        atol=1e-6)
    assert half.dtype == x.dtype


# -- the router's state and the walk ------------------------------------------

def test_the_routers_state_is_carried_from_layer_to_layer():
    """With ``gamma = 0`` everywhere a layer's router sees its own
    down-projection alone: another loss, so the state is on the path; and
    the stacked walk's state is the ``l{i}`` loop's."""
    cfg = dataclasses.replace(CFG, remat=True)
    params = uneven(zaya.init_params(cfg, KEY))
    tokens = zaya.fake_batch(cfg, 2, 32, seed=5)
    loss = float(loss_of(params, tokens, cfg))
    cut = {k: ({**v, "router_gamma": 0 * v["router_gamma"]}
               if k.startswith("l") else v) for k, v in params.items()}
    assert abs(float(loss_of(cut, tokens, cfg)) - loss) > 1e-4
    # The carry by hand: layer 2's state holds layer 1's, times gamma.
    block = jax.jit(lambda blk, carry: zaya.block(blk, carry, cfg))
    x, r = zaya._start(params, tokens[:, :-1], cfg)
    assert r.shape == (2, 32, 16) and r.dtype == jnp.float32 \
        and not np.any(np.asarray(r))
    for i in range(2):
        x, r = block(params[f"l{i}"], (x, r))
    blk = params["l2"]
    after = block(blk, (x, r))[1]
    x = x + jax.jit(lambda b, h: zaya.attention(b, h, cfg))(blk, x)
    h = layers.rms_norm(x, blk["moe_ln"], cfg.rms_norm_eps)
    own = layers.rms_norm(
        jnp.dot(h, blk["router_down"]), blk["router_ln"], cfg.rms_norm_eps)
    np.testing.assert_allclose(
        np.asarray(after), np.asarray(own + blk["router_gamma"] * r),
        rtol=0, atol=2e-6)


def test_one_expert_a_token_through_the_dropless_layout():
    """``k = 1`` through ``routed_experts``: every expert applied to every
    token and the chosen one kept, times its own probability (the
    reference's layer), the state and the choices with it."""
    params = uneven(zaya.init_params(CFG, KEY))
    blk, hp = params["l1"], hyper(CFG)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, CFG.hidden_size))
    r = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 16))
    y, state, experts = zaya.expert_layer(blk, x, r, CFG)
    assert experts.shape == (64, 1) and len(np.unique(experts)) > 4
    for i in range(2):
        h = ref._rms_norm(x[i], blk["moe_ln"], hp.eps)
        want, want_state, ids = ref._moe(blk, h, r[i], hp, ref.identity)
        np.testing.assert_allclose(np.asarray(y[i]), np.asarray(want),
                                   rtol=0, atol=2e-6)
        np.testing.assert_allclose(np.asarray(state[i]),
                                   np.asarray(want_state), rtol=0, atol=2e-6)
        np.testing.assert_array_equal(
            np.asarray(experts[32 * i:32 * (i + 1)]), np.asarray(ids))
    # The gate is the choice's own probability: it has a gradient.
    _, probs, gate, chosen = zaya.router(blk, x[0], r[0], CFG)
    np.testing.assert_array_equal(
        np.asarray(gate), np.asarray(jnp.take_along_axis(probs, chosen, -1)))
    assert float(gate.max()) < 1.0
    # One size of layout: the tokens and a tile's pads an expert.
    assert layout_rows(8192, 1, 16, 16, 128) == (10240,)
    stats = zaya.routing_stats(params, zaya.fake_batch(CFG, 2, 32), CFG)
    assert stats["moe_tokens_dropped"] == 0 \
        and stats["experts"].shape == (3, 64, 1)


def test_the_sublayers_carry_their_scopes():
    cfg = dataclasses.replace(WIDE, remat=True)
    params = zaya.stacked_init_params(cfg, KEY)
    tokens = zaya.fake_batch(cfg, 1, 32)
    text = jax.jit(zaya.loss_fn, static_argnums=2).lower(
        params, tokens, cfg).as_text(debug_info=True)
    for scope in ("cca_down", "cca_mix", "cca_norm_rope", "cca_out",
                  "zaya_router", "moe_experts", "moe_dispatch", "rope_plain",
                  "tepdist_cca_mix_fwd"):
        assert scope in text, scope
