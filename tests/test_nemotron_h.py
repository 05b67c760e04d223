"""Nemotron-H (``models/nemotron_h.py``) against the plain float32 reference
(``benchmark/reference/nemotron_h.py``): logits, loss and every leaf's
gradient in the per-layer and the stacked-unit layouts, the bf16 preset, rows
of a batch that do not meet, what a bf16 state, a dropped ``D u``, a dropped
conv bias and a norm over the whole width instead of a group would cost (each
caught by the tolerance), the two-stack squared-relu expert against a dense
loop over experts, the eight ranks' expert parts adding up to the uncut layer
with the shared expert counted once, and the held layer's routing statistics.
The walks are ``test_nemotron_h_walk.py``'s."""

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
    tree_close,
)

from benchmark.reference import nemotron_h as ref
from tepdist_tpu.models import afmoe, decoder, kimi_linear, layers
from tepdist_tpu.models import nemotron_h as nemo
from tepdist_tpu.ops import grouped_matmul
from tepdist_tpu.ops.pallas import causal_conv, ssd_attention

CFG = nemo.CONFIGS["test"]           # experts 8..15 of 32 held; MEMEM*EME
WHOLE = dataclasses.replace(CFG, experts_held=(0, CFG.num_experts))
OUTSIDE = ("tok_emb", "norm_f", "lm_head")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def hyper(cfg):
    return ref.Hyper(
        heads=cfg.mamba_num_heads, groups=cfg.n_groups,
        n_head=cfg.num_attention_heads, n_kv_head=cfg.num_key_value_heads,
        top_k=cfg.num_experts_per_tok, held=cfg.experts_held,
        units=cfg.units, route_scale=cfg.routed_scaling_factor,
        eps=cfg.layer_norm_epsilon)


def stacked_like(tree, cfg=CFG):
    """An ``l{i}`` tree in the stacked layout: units, a stack a run."""
    return decoder.stack_layers(nemo.in_units(tree, cfg),
                                decoder.run_stacks(cfg.units), OUTSIDE,
                                nemo.GROUPS)


def uneven(params):
    """Gains, biases and the skip away from their initial values, so that
    one left out shows: every norm's gain, the conv's bias, ``D`` and the
    routers' selection biases."""
    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name) + a.size)
        if name.endswith("_ln']") or "norm" in name or name.endswith("['D']"):
            return a + 0.2 * jax.random.normal(key, a.shape)
        if name.endswith("['conv_b']"):
            return a + 0.3 * jax.random.normal(key, a.shape)
        if name.endswith("['router_bias']"):
            return a + 0.05 * jax.random.normal(key, a.shape)
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


# Float32 program against float32 reference: what differs is the order of
# the sums (a chunked rule against a token at a time, tiles against a loop
# over experts), 1e-6 of a leaf; 2e-5 of a leaf's largest entry leaves room
# for that and none for a term left out (the cases below): ``tree_close``.
# The row, and the file's (and ``test_nemotron_h_walk.py``'s) compiled
# programs. One batch for every case of the float32 preset, so that a program
# and the reference are compiled once a layout and the reference is run once.
MODEL = Model(
    nemo, ref, CFG, hyper, OUTSIDE, stack=stacked_like, uneven=uneven,
    opt={"name": "adamw_bf16_router_bias", "learning_rate": 1e-3,
         "bias_rate": 0.001})
TOKENS = MODEL.tokens()
init_params, uneven_params = MODEL.init_params, MODEL.uneven_params
to_reference, loss_and_grads = MODEL.to_reference, MODEL.loss_and_grads
ref_loss, ref_loss_and_grads = MODEL.ref_loss, MODEL.ref_loss_and_grads


def test_the_presets_hold_the_published_structure():
    big = nemo.CONFIGS["3-nano-30b-a3b"]
    assert len(big.kinds) == 52 and big.kinds[:9] == tuple("MEMEM*EME")
    assert [big.kinds.count(k) for k in "ME*"] == [23, 23, 6]
    assert big.mamba_num_heads * big.mamba_head_dim == 4096 \
        and big.n_groups * big.ssm_state_size == 1024
    # A layer is one part alone; the stacked layout walks units that end
    # with an expert layer: three runs where the layers alone are nine.
    assert CFG.kinds == tuple("MEMEM*EME")
    assert CFG.units == ("ME", "ME", "M*E", "ME") \
        and "".join(big.units) == big.hybrid_override_pattern
    assert decoder.run_stacks(CFG.units) == ((0, 0, 2), (1, 2, 1), (2, 3, 1))
    assert decoder.units("MMEE*", "E") == ("M", "ME", "E", "*")
    assert len(decoder.run_stacks(big.units)) == 13
    # The published ratios at the tests' widths.
    assert CFG.num_attention_heads == 16 * CFG.num_key_value_heads
    assert CFG.mamba_num_heads == 2 * CFG.n_groups
    assert CFG.num_experts_per_tok == 6 and CFG.route_scale == 2.5
    assert CFG.moe_shared_expert_intermediate_size \
        == 2 * CFG.moe_intermediate_size
    smoke = nemo.CONFIGS["smoke"]
    assert (smoke.mamba_head_dim, smoke.ssm_state_size, smoke.head_dim) \
        == (64, 128, 128)
    # The start values: A = 1 .. H, D = 1, steps of 0.001 to 0.1.
    blk = init_params(CFG)["l0"]
    np.testing.assert_allclose(np.exp(np.asarray(blk["A_log"])),
                               np.arange(1, 5), rtol=1e-6)
    assert not (np.asarray(blk["D"]) - 1).any()
    step = np.asarray(jax.nn.softplus(blk["dt_bias"]))
    assert 0.00099 < step.min() and step.max() < 0.1001
    assert "w_gate" not in init_params(CFG)["l1"]       # no gate matrix
    # What is shared is called, not copied.
    assert nemo.afmoe is afmoe and nemo.gqa_heads is layers.gqa_heads \
        and nemo.scaled_by_head is layers.scaled_by_head \
        and kimi_linear.scaled_by_head is layers.scaled_by_head \
        and nemo.causal_conv is causal_conv.causal_conv \
        and nemo.routed_experts is grouped_matmul.routed_experts \
        and nemo.ssd_attention is ssd_attention.ssd_attention


@pytest.mark.parametrize("stacked,remat", [(False, False), (True, True)],
                         ids=["unstacked-plain", "stacked-remat"])
def test_logits_loss_and_every_gradient_match_the_reference(stacked, remat):
    # The logits once: the loss holds both.
    grads = match_the_reference(MODEL, stacked, remat, logits=not stacked)
    # The selection bias takes the step's counts where a gradient would be:
    # 2 x 32 tokens' 6 choices a layer.
    bias = grads["run0"]["router_bias"] if stacked \
        else grads["l1"]["router_bias"][None]
    assert float(bias[0].sum()) == 2 * 32 * 6


# What the tolerance has to catch, on the Mamba-2 mixer alone (its output and
# the gradients of its input and leaves against the reference's mixer): each
# departure moves one of them by far more than the 2e-5 of its largest entry
# that ``tree_close`` allows; the mixer as it is passes.
@pytest.mark.parametrize("what", [None, "bf16_state", "no_skip",
                                  "no_conv_bias",
                                  "norm_over_the_whole_width"])
def test_a_departure_from_the_equations_fails_the_tolerance(what,
                                                            monkeypatch):
    if what == "bf16_state":
        kernel = ssd_attention.forward
        monkeypatch.setattr(
            ssd_attention, "forward", lambda *a, **k: kernel(
                *a, **{**k, "state_dtype": jnp.bfloat16}))
    elif what == "no_skip":
        rule = nemo.ssd_attention
        monkeypatch.setattr(
            nemo, "ssd_attention", lambda u, B, C, dl, A, D, **k: rule(
                u, B, C, dl, A, jnp.zeros_like(D), **k))
    elif what == "no_conv_bias":
        conv = nemo.causal_conv
        monkeypatch.setattr(nemo, "causal_conv",
                            lambda u, w, b: conv(u, w, None))
    elif what:
        norm = nemo.gated_group_norm
        monkeypatch.setattr(
            nemo, "gated_group_norm",
            lambda y, z, gain, groups, eps, axis=None: norm(
                y, z, gain, 1, eps))
    blk, hp = uneven_params(False)["l0"], hyper(CFG)
    a = jax.random.normal(jax.random.PRNGKey(4), (1, 48, CFG.hidden_size))
    ct = jax.random.normal(jax.random.PRNGKey(5), a.shape)
    got = jax.value_and_grad(
        lambda blk, a: jnp.sum(nemo.mamba(blk, a, CFG) * ct), (0, 1))(blk, a)
    want = jax.value_and_grad(lambda blk, a: jnp.sum(ref._mamba(
        blk, a[0], hp, ref.identity) * ct[0]), (0, 1))(blk, a)
    if what is None:
        tree_close(got, want)
        return
    with pytest.raises(AssertionError):
        tree_close(got, want)
    worst = max(float(np.abs(np.asarray(g) - np.asarray(w)).max()
                      / np.abs(np.asarray(w)).max())
                for g, w in zip(jax.tree_util.tree_leaves(got),
                                jax.tree_util.tree_leaves(want))
                if np.asarray(w).any())     # ``ssm_ln`` is the layer's
    assert worst > 1e-4, worst      # five times what ``tree_close`` allows


def test_bf16_program_stays_near_the_float32_reference():
    cfg = dataclasses.replace(nemo.CONFIGS["test_bf16"], remat=True,
                              loss_chunk=16)
    bf16_near_the_reference(MODEL, cfg, TOKENS)


def test_the_conv_and_the_state_never_cross_between_rows_of_a_batch():
    """The rows in another order give the same rows (no conv tail, no state
    and no routing goes from one sequence to the next), and a position never
    sees a later one: the conv, the rule and the attention are causal. One
    shape, so one compiled program (``test_logits...``'s)."""
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


def test_the_attention_layer_has_no_position_and_the_mamba_layer_its_own():
    """Each part against the reference's, alone: the attention (no rotary,
    no QK-norm: its own scores are a permutation's apart from order only
    through the mask) and the Mamba-2 mixer."""
    params, hp = uneven_params(False), hyper(CFG)
    a = jax.random.normal(jax.random.PRNGKey(4), (2, 32, CFG.hidden_size))
    got = nemo.attention(params["l5"], a, CFG)
    want = jnp.stack([ref._attention(params["l5"], s, hp, ref.identity)
                      for s in a])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-6)
    got = nemo.mamba(params["l0"], a, CFG)
    want = jnp.stack([ref._mamba(params["l0"], s, hp, ref.identity)
                      for s in a])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-6 * float(jnp.abs(want).max()))


def test_the_two_stack_expert_is_a_dense_loop_over_experts():
    """``routed_experts`` with no gate matrix against ``sum_j w_j
    relu(h Wup[e_j])^2 Wdown[e_j]`` written out, values and gradients, for
    a whole layer and for a share of the experts."""
    S, d, f, E, k = 48, 16, 24, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    h = jax.random.normal(ks[0], (S, d))
    w_up = jax.random.normal(ks[1], (E, d, f)) * 0.3
    w_down = jax.random.normal(ks[2], (E, f, d)) * 0.3
    weights = jax.random.uniform(ks[3], (S, k))
    experts = jnp.argsort(jax.random.uniform(ks[4], (S, E)), axis=-1)[:, :k]

    def dense(h, weights, w_up, w_down, held):
        first, count = held
        out = jnp.zeros_like(h)
        for e in range(first, first + count):
            w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
            u = jnp.maximum(h @ w_up[e - first], 0.0)
            out = out + w[:, None] * ((u * u) @ w_down[e - first])
        return out

    for held in ((0, E), (2, 4)):
        first, count = held

        def layer(h, weights, w_up, w_down, held=held):
            return grouped_matmul.routed_experts(
                h, decoder.held_weights(weights, experts, held, E), experts,
                None, w_up, w_down, E, 8, held=held)

        args = (h, weights, w_up[first:first + count],
                w_down[first:first + count])
        ct = jax.random.normal(KEY, h.shape)
        got, pull = jax.vjp(layer, *args)
        want, pull_dense = jax.vjp(
            lambda *a, held=held: dense(*a, held), *args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=2e-5)
        for g, w in zip(pull(ct), pull_dense(ct)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                       atol=5e-5)
    # The activation alone keeps its operands and nothing float32.
    up = jax.random.normal(ks[0], (6, 8)).astype(jnp.bfloat16)
    row = jax.random.uniform(ks[1], (6, 1))
    act, pull = jax.vjp(grouped_matmul.relu2, up, row)
    assert act.dtype == jnp.bfloat16
    want = jnp.maximum(up.astype(jnp.float32), 0) ** 2 * row
    np.testing.assert_allclose(np.asarray(act, np.float32), np.asarray(want),
                               rtol=1e-2)
    d_up, d_row = pull(jnp.ones_like(act))
    assert d_up.dtype == jnp.bfloat16 and d_row.shape == (6, 1)


def test_the_eight_ranks_add_up_with_the_shared_expert_counted_once():
    """Shares (0,4) .. (28,4) of the 32-wide router, the shared expert once:
    the uncut reference's whole expert layer."""
    params = uneven(init_params(WHOLE))
    blk = params["l1"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, CFG.hidden_size))
    ranks = range(0, CFG.num_experts, 4)
    assert len(ranks) == 8
    for first in ranks:
        share, _ = nemo.rank_share(params, WHOLE, (first, 4))
        assert share["l1"]["w_up"].shape[0] == 4 \
            and share["l1"]["router"] is params["l1"]["router"] \
            and share["l0"]["w_xbc"] is params["l0"]["w_xbc"] \
            and share["l5"]["wq"] is params["l5"]["wq"]

    @jax.jit        # one trace for the eight ranks, not eight dispatches
    def every_rank(params, x):
        blk = params["l1"]
        shared = nemo.relu2_mlp(x, blk["shared_up"], blk["shared_down"])
        total = shared
        for first in ranks:
            share, cfg = nemo.rank_share(params, WHOLE, (first, 4))
            total = total + nemo.moe(share["l1"], x, cfg) - shared
        return total

    hp = hyper(WHOLE)
    want = jax.jit(lambda blk, x: jnp.stack(
        [ref._moe(blk, s, hp, ref.identity)[0] for s in x]))(blk, x)
    np.testing.assert_allclose(np.asarray(every_rank(params, x)),
                               np.asarray(want), rtol=0, atol=2e-6)


def test_a_rank_of_the_whole_model_is_the_reference_at_the_same_share():
    params = init_params(WHOLE)
    share, cfg = nemo.rank_share(params, WHOLE, CFG.experts_held)
    assert cfg == CFG
    # Both compiled already: the share has ``CFG``'s shapes.
    want, _ = ref_loss_and_grads(to_reference(share, cfg), TOKENS, hyper(cfg))
    (got, _), _ = MODEL.all_three(share, TOKENS, cfg)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    whole = ref_loss(to_reference(params, WHOLE), TOKENS, hyper(WHOLE))
    assert abs(float(whole) - float(want)) > 1e-5


def test_the_held_layers_routing_stats():
    """Rows a held expert and the live share of the tiles laid out, from the
    routers' own choices outside any step, the four expert layers'."""
    stats = decoder.routing_stats(
        jax.jit(nemo.expert_choices, static_argnums=2), init_params(CFG),
        TOKENS, CFG)
    assert nemo.routing_stats.func is decoder.routing_stats \
        and nemo.routing_stats.args == (nemo.expert_choices,)
    L, S, k = CFG.kinds.count("E"), 2 * 32, CFG.num_experts_per_tok
    assert stats["experts"].shape == (L, S, k)
    assert stats["held_rows"].shape == (L, CFG.experts_held[1])
    assert stats["moe_assignments_held"] \
        + stats["moe_assignments_elsewhere"] == L * S * k
    assert stats["moe_tokens_dropped"] == 0
    assert 0 < stats["moe_layout_live_share"] <= 1
    # The stacked layout's routers choose alike.
    choices = jax.jit(nemo.expert_choices, static_argnums=2)
    np.testing.assert_array_equal(
        np.asarray(choices(stacked_like(init_params(CFG)), TOKENS[:, :-1],
                           CFG)), np.asarray(stats["experts"]))
