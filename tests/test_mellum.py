"""Mellum2 (JetBrains): the program against the plain float32 reference at a
tiny preset, the YaRN rotary table, the expert layer that holds a share of
the experts under a softmax router, and the counters both expert models
with a held share report."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from model_checks import KEY, Model, match_the_reference, tree_close

from benchmark.reference import mellum as ref
from tepdist_tpu.models import decoder, layers, mellum
from tepdist_tpu.ops import grouped_matmul as gm
from tepdist_tpu.telemetry import metrics

CFG = mellum.CONFIGS["test"]         # 16-wide router, experts 4..7 held;
#                                      window, global, window; the YaRN
#                                      table's original context is 8 of the
#                                      tests' 32 positions


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def hyper(cfg):
    return ref.Hyper(
        n_head=cfg.num_attention_heads, n_kv_head=cfg.num_key_value_heads,
        top_k=cfg.num_experts_per_tok, layer_types=cfg.layer_types,
        window=cfg.sliding_window, held=cfg.experts_held,
        rope_theta=cfg.rope_theta,
        yarn=ref.Yarn(cfg.yarn_factor, cfg.yarn_original_max_position,
                      cfg.yarn_beta_fast, cfg.yarn_beta_slow,
                      cfg.yarn_attention_factor),
        eps=cfg.rms_norm_eps)


MODEL = Model(
    mellum, ref, CFG, hyper, ("tok_emb", "norm_f", "lm_head"),
    stack=lambda tree, cfg: decoder.stack_layers(
        tree, mellum._stacks(cfg), ("tok_emb", "norm_f", "lm_head")),
    logits_atol=2e-6, opt={"name": "adamw_bf16", "learning_rate": 1e-3})
loss_and_grads, to_reference = MODEL.loss_and_grads, MODEL.to_reference


@pytest.mark.parametrize("stacked,remat", [
    (False, False), (False, True), (True, False), (True, True)],
    ids=["unstacked-plain", "unstacked-remat", "stacked-plain",
         "stacked-remat"])
def test_logits_loss_and_every_gradient_match_the_reference(stacked, remat):
    grads = match_the_reference(MODEL, stacked, remat)
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(grads))


def test_yarn_table_at_the_published_parameters():
    """``rope_parameters.full_attention`` of the published config, against
    values worked out by hand: corr(32) = 18.08, corr(1) = 34.98, so pairs
    0..18 turn at the plain rate, 35..63 at a sixteenth of it."""
    table = mellum.MellumConfig().global_rope
    got = np.asarray(table.inv_freq)
    assert got.shape == (64,) and table.name == "rope_yarn"
    assert table.scale == 1.2772588722239782
    want = {0: 1.0, 18: 0.024955408670558694, 26: 0.0027043825167258227,
            35: 4.7781061769823416e-05, 63: 1.5344629944572555e-07}
    for i, value in want.items():
        assert got[i] == pytest.approx(value, rel=2e-6), i
    plain = 5e5 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(got[:19], plain[:19], rtol=2e-6)   # <= low
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=2e-6)
    assert (got[19:35] < plain[19:35]).all() \
        and (got[19:35] > plain[19:35] / 16).all()
    # No attention factor given: 0.1 ln(factor) + 1, the published number.
    assert layers.yarn_table(128, 5e5, 16, 8192).scale == pytest.approx(
        1.2772588722239782, rel=1e-12)
    # The reference's own lines give the same table.
    inv_freq, scale = ref.yarn_inv_freq(128, 5e5, ref.Yarn())
    np.testing.assert_allclose(np.asarray(inv_freq), got, rtol=2e-6)
    assert scale == table.scale


def test_yarn_table_with_factor_one_is_the_plain_table():
    x = jax.random.normal(KEY, (2, 3, 40, 16))
    table = layers.yarn_table(16, 100.0, 1.0, 8, 2.0, 0.5)
    assert table.scale == 1.0
    np.testing.assert_allclose(np.asarray(layers.rope(x, table)),
                               np.asarray(layers.rope(x, 100.0)),
                               rtol=0, atol=1e-6)
    # The test preset's table is not: past its original 8 positions the
    # slow pairs lag, and every pair carries the scale.
    yarn = CFG.global_rope
    assert yarn.scale == pytest.approx(0.1 * np.log(4.0) + 1.0)
    assert yarn.inv_freq[0] == 1.0 and yarn.inv_freq[1] == pytest.approx(
        0.5 * (1 + 0.25) * 100.0 ** (-1 / 8), rel=1e-6)
    assert yarn.inv_freq[7] == pytest.approx(100.0 ** (-7 / 8) / 4, rel=1e-6)
    out = layers.rope(x, yarn)
    assert float(jnp.abs(out - yarn.scale * layers.rope(x, 100.0)).max()) \
        > 0.1
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(out, axis=-1)),
        yarn.scale * np.asarray(jnp.linalg.norm(x, axis=-1)), rtol=1e-5)


@pytest.mark.parametrize("window,block", [(12, 8), (5, 16), (8, 8)])
def test_a_window_the_block_size_does_not_divide(window, block):
    cfg = dataclasses.replace(CFG, sliding_window=window,
                              flash_block_q=block, flash_block_k=block)
    params = mellum.stacked_init_params(cfg, KEY)
    tokens = mellum.fake_batch(cfg, 2, 32, seed=3)
    loss, grads = loss_and_grads(params, tokens, cfg)
    # The reference over the stack as it is (it reads either layout).
    want_loss, want = MODEL.ref_loss_and_grads(params, tokens, hyper(cfg))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    tree_close(grads, want)


def _two_steps(cfg, params, batches, micro):
    tx, step = MODEL.ga_step(cfg, micro)
    state, losses = tx.init(params), []
    for tokens in batches:
        loss_value, params, state = step(params, state, tokens)
        losses.append(float(loss_value))
    return losses, params


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["unstacked", "stacked"])
def test_two_steps_do_not_depend_on_the_accumulation_split(stacked):
    cfg = MODEL.variant(True)
    params = MODEL.init_params(cfg, stacked)
    batches = [mellum.fake_batch(cfg, 4, 32, seed=s) for s in (2, 3)]
    one, p_one = _two_steps(cfg, params, batches, 1)
    four, p_four = _two_steps(cfg, params, batches, 4)
    np.testing.assert_allclose(one, four, rtol=2e-6)
    tree_close(p_four, p_one, rtol=2e-4)      # bf16 moments
    if stacked:       # the in-loop accumulation and the fused loss engaged
        fused = metrics().gauge("ga_fused_bytes").value
        unfused = metrics().gauge("ga_unfused_bytes").value
        assert fused / (fused + unfused) > 0.5
        assert metrics().gauge("ce_fused_chunks").value == 2


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The parts that shares (0,4) .. (12,4) of a 16-wide router give are
    the uncut reference's whole layer (nothing is computed on every rank
    alike: no shared expert)."""
    E, G = CFG.num_experts, 4
    whole = dataclasses.replace(CFG, experts_held=(0, E))
    blk = mellum.init_params(whole, KEY)["l1"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, CFG.hidden_size))
    total = 0.0
    for first in range(0, E, G):
        cfg = dataclasses.replace(CFG, experts_held=(first, G))
        part = {**blk, **{k: blk[k][first:first + G]
                          for k in ("w_gate", "w_up", "w_down")}}
        total = total + mellum.moe(part, x, cfg)
    hp = hyper(whole)
    want = jnp.stack([ref._moe(blk, s, hp, ref.identity)[0] for s in x])
    assert float(jnp.abs(want).max()) > 1e-4
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=0, atol=2e-6)
    np.testing.assert_allclose(np.asarray(mellum.moe(blk, x, whole)),
                               np.asarray(want), rtol=0, atol=2e-6)
    # The weights are normalised over all k choices, held or not.
    weights, _ = mellum.router(blk, x.reshape(64, -1), whole)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)


@pytest.mark.parametrize("send", ["all_held", "none_held", "mixed"])
def test_no_assignment_to_a_held_expert_is_dropped(send):
    """A router that sends every token to held experts, one that sends
    none, and the seed's. A zero router gives every expert the same
    probability and ``top_k`` then takes the lowest ids, 0 and 1: all held
    by the share (0, 4), none by (4, 4)."""
    cfg = dataclasses.replace(
        CFG, experts_held={"all_held": (0, 4)}.get(send, (4, 4)))
    params = mellum.init_params(cfg, KEY)
    if send != "mixed":
        for i in range(cfg.num_hidden_layers):
            params[f"l{i}"]["router"] = jnp.zeros_like(
                params[f"l{i}"]["router"])
    tokens = mellum.fake_batch(cfg, 2, 32, seed=4)
    stats = mellum.routing_stats(params, tokens, cfg)
    S, k, L = 64, cfg.num_experts_per_tok, cfg.num_hidden_layers
    assert stats["moe_tokens_dropped"] == 0
    assert stats["moe_assignments_held"] \
        + stats["moe_assignments_elsewhere"] == L * S * k
    if send == "all_held":
        assert stats["moe_assignments_elsewhere"] == 0
        assert stats["moe_held_rows_max"] == S
    if send == "none_held":
        assert stats["moe_assignments_held"] == 0
    if send == "mixed":
        assert 0 < stats["moe_assignments_held"] < L * S * k
    held = np.asarray(decoder.held_mask(stats["experts"], cfg.experts_held)).sum()
    assert held == stats["moe_assignments_held"]
    assert stats["held_rows"].shape == (L, 4)
    assert metrics().gauge("moe_held_rows_max").value \
        == stats["moe_held_rows_max"]
    # The size each layer's layout takes: the worst case only where the
    # routing fills it.
    assert stats["moe_layout_worst_case"] == {"all_held": L}.get(send, 0)
    assert (stats["moe_layout_rows_share"] < 1) == (send != "all_held")
    if send == "none_held":     # the first size of 88 and 168 rows
        assert stats["moe_layout_rows_share"] == pytest.approx(88 / 168)
    assert metrics().gauge("moe_layout_rows_share").value \
        == stats["moe_layout_rows_share"]
    # The gradient runs whatever the routing (no live tile, or all of them);
    # under the preset's own share the program is the reference case's.
    (loss, _), grads = MODEL.all_three(params, tokens, cfg)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))
    want_loss = MODEL.ref_loss(to_reference(params, cfg), tokens, hyper(cfg))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)


@pytest.mark.parametrize("send", ["all_held", "none_held", "mixed"])
def test_the_layers_values_do_not_depend_on_the_size_taken(send, monkeypatch):
    """``mellum.moe`` under a router forced to each end and the seed's: the
    output and the gradient of the router, of the three expert weights and
    of the input are those of the program whose ladder is the worst case
    alone. ``all_held`` takes the worst case, ``none_held`` the first
    size."""
    cfg = dataclasses.replace(
        CFG, experts_held={"all_held": (0, 4)}.get(send, (4, 4)))
    blk = mellum.init_params(cfg, KEY)["l1"]
    if send != "mixed":
        # Equal probabilities: ``top_k`` takes experts 0 and 1. A router of
        # exact zeros would have no gradient to compare.
        blk["router"] = blk["router"] * 1e-30
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, cfg.hidden_size))
    experts = mellum.router(blk, x.reshape(64, -1), cfg)[1]
    sizes = gm.layout_rows(64, cfg.num_experts_per_tok, 4, cfg.num_experts,
                           cfg.moe_tile_m)
    taken = int(gm.layout_index(gm.route(
        experts, cfg.num_experts, cfg.moe_tile_m, cfg.experts_held).n_tiles,
        sizes, cfg.moe_tile_m))
    assert sizes == (88, 168)
    assert taken == {"all_held": 1, "none_held": 0}.get(send, taken)

    def values():
        def out(blk, x):
            y = mellum.moe(blk, x, cfg)
            return jnp.sum(y * jnp.sin(jnp.arange(y.size).reshape(y.shape))), y
        return jax.jit(jax.value_and_grad(out, argnums=(0, 1),
                                          has_aux=True))(blk, x)

    got = values()
    whole = gm.layout_rows
    monkeypatch.setattr(gm, "layout_rows", lambda *a: whole(*a)[-1:])
    want = values()
    if send != "none_held":
        assert float(jnp.abs(want[0][1]).max()) > 1e-4
        assert float(jnp.abs(want[1][0]["router"]).max()) > 0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_both_held_share_models_report_through_one_implementation():
    from tepdist_tpu.models import afmoe
    assert afmoe.routing_stats.func is mellum.routing_stats.func \
        is decoder.routing_stats
    assert afmoe.held_weights is mellum.held_weights is decoder.held_weights
    assert afmoe.gqa_heads is mellum.gqa_heads is layers.gqa_heads
    ids = jnp.asarray([[[0, 5], [4, 5], [7, 1]]], jnp.int32)  # [1, 3, 2]
    stats = layers.held_routing_stats(ids, 8, 4, (4, 4))
    assert (stats["moe_assignments_held"],
            stats["moe_assignments_elsewhere"],
            stats["moe_tokens_dropped"], stats["moe_held_rows_max"]) == (
                4, 2, 0, 2)
    assert stats["held_rows"].tolist() == [[1, 2, 0, 1]]


def test_the_rotary_scopes_say_their_table():
    cfg = dataclasses.replace(CFG, remat=True)
    params = mellum.stacked_init_params(cfg, KEY)
    tokens = mellum.fake_batch(cfg, 1, 32)
    text = jax.jit(mellum.loss_fn, static_argnums=2).lower(
        params, tokens, cfg).as_text(debug_info=True)
    for scope in ("rope_plain", "rope_yarn", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine"):
        assert scope in text, scope
