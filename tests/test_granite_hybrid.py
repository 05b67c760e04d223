"""Granite 4.0-H (``models/granite_hybrid.py``) against the plain float32
reference (``benchmark/reference/granite_hybrid.py``): logits, loss and every
leaf's gradient for the uncut model and for one rank's share of each layer,
the bf16 preset, **the tie of the share to the model** (two ranks' partial
outputs of a Mamba-2 sub-layer, with the gated norm's ``psum`` live, of the
attention sub-layer and of the expert part add up to the uncut reference's
layer; the sliced-vocabulary loss is the whole loss restricted to the slice),
the four multipliers (a wrong one fails the tolerance), the stacked
accumulating walk against the layer loop, the gauges and scopes of a traced
step, and two steps through ``plan_training``."""

import dataclasses
import functools

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
    two_planned_steps,
)

from benchmark.reference import granite_hybrid as ref
from tepdist_tpu.models import afmoe, decoder, layers, mellum, nemotron_h
from tepdist_tpu.models import granite_hybrid as granite
from tepdist_tpu.ops import grouped_matmul
from tepdist_tpu.ops.pallas.grouped_matmul import ExpertStack
from tepdist_tpu.telemetry import metrics

WHOLE = granite.CONFIGS["test"]      # mamba, mamba, attention
RANKS = 2
BATCH = (1, 32)        # one sequence: the reference is traced a sequence
OUTSIDE = ("tok_emb", "norm_f")
MAMBA, ATTN = granite.MAMBA, granite.ATTN


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def hyper(cfg):
    return ref.Hyper(
        heads=cfg.mamba_n_heads, n_head=cfg.num_attention_heads,
        n_kv_head=cfg.num_key_value_heads, top_k=cfg.num_experts_per_tok,
        held=cfg.experts_held, kinds=cfg.layer_types,
        embedding_multiplier=cfg.embedding_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling, eps=cfg.rms_norm_eps)


def uneven(params):
    """Gains, the conv's bias and the skip away from their initial values,
    so that one left out shows."""
    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name) + a.size)
        if name.endswith("_ln']") or "norm" in name or name.endswith("['D']"):
            return a + 0.2 * jax.random.normal(key, a.shape)
        if name.endswith("['conv_b']"):
            return a + 0.3 * jax.random.normal(key, a.shape)
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


def _share(cfg, key, rank=1):
    """Rank ``rank``'s leaves of the uneven whole model."""
    return granite.rank_share(uneven(granite.init_params(WHOLE, key)), WHOLE,
                              rank, RANKS)[0]


CFG = granite.rank_share(granite.init_params(WHOLE, KEY), WHOLE, 1, RANKS)[1]
# Two rows, the file's compiled programs: the uncut model, and rank 1 of the
# two that share each layer (what a cell runs), each a program and a
# reference a layout.
UNCUT = Model(granite, ref, WHOLE, hyper, OUTSIDE, stack=granite.stacked,
              uneven=uneven, batch=BATCH, opt={"name": "adamw_bf16",
                                  "learning_rate": 1e-3})
MODEL = Model(granite, ref, CFG, hyper, OUTSIDE, stack=granite.stacked,
              init=_share, batch=BATCH, opt={"name": "adamw_bf16", "learning_rate": 1e-3})
TOKENS = MODEL.tokens()



def _sizes(tree):
    return sum(a.size for a in jax.tree_util.tree_leaves(tree))


def test_the_presets_hold_the_published_structure():
    big = granite.CONFIGS["4.0-h-small"]
    assert len(big.layer_types) == 40 and [
        i for i, m in enumerate(big.layer_types) if m == ATTN] \
        == [5, 15, 25, 35]
    assert big.mamba_n_heads * big.mamba_d_head == 2 * big.hidden_size
    assert big.attention_multiplier == 1 / 128 != big.head_dim ** -0.5
    assert (big.embedding_multiplier, big.residual_multiplier,
            big.logits_scaling) == (12.0, 0.22, 16.0)
    # The whole model and the rank of eight the benchmark's cell holds of
    # published layers 0-9, counted from the leaves' shapes.
    whole = jax.eval_shape(lambda: granite.init_params(big, KEY))
    assert _sizes(whole) == 32_207_337_984
    ten = dataclasses.replace(big, layer_types=big.layer_types[:10])
    made = {}

    def rank_0_of_8():
        held, made["cfg"] = granite.rank_share(
            granite.init_params(ten, KEY), ten, 0, 8)
        return held

    held, cfg = jax.eval_shape(rank_0_of_8), made["cfg"]
    assert _sizes(held) == 1_221_088_944
    assert _sizes(held["l0"]) == 117_816_624 \
        and _sizes(held["l5"]) == 109_355_008
    assert held["l0"]["w_xbc"].shape == (4096, 1024 + 128 + 128) \
        and held["l0"]["w_out"].shape == (1024, 4096)
    assert (cfg.mamba_n_heads, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.shared_intermediate_size,
            cfg.experts_held, cfg.vocab_size) == (16, 4, 1, 1536, (0, 9),
                                                  12544)
    # The tests' model: a period's runs, the published ratios.
    assert [(n, c) for _, n, c in decoder.run_stacks(WHOLE.layer_types)] \
        == [(0, 2), (2, 1)]
    assert WHOLE.num_attention_heads == 4 * WHOLE.num_key_value_heads
    assert WHOLE.num_experts_per_tok == 10 and CFG.experts_held == (8, 8)
    assert (CFG.mamba_n_heads, CFG.num_attention_heads,
            CFG.num_key_value_heads, CFG.vocab_size) == (4, 4, 1, 256)
    # The start values: A = 1 .. H, D = 1, steps of 0.001 to 0.1 (the
    # published module's ``dt_bias`` of 1 is a checkpoint's to overwrite).
    blk = UNCUT.init_params()["l0"]
    np.testing.assert_allclose(np.exp(np.asarray(blk["A_log"])),
                               np.arange(1, 9), rtol=1e-6)
    step = np.asarray(jax.nn.softplus(blk["dt_bias"]))
    assert 0.00099 < step.min() and step.max() < 0.1001
    # The head is the embedding, and a run's Mamba-2 leaves that a check
    # names lie in a group of their own.
    stack = MODEL.init_params(stacked=True)
    assert "lm_head" not in stack and set(stack) == {
        "tok_emb", "norm_f", "run0", "vec0", "out0", "run1"}
    assert set(stack["vec0"]) == {"A_log", "D", "dt_bias", "conv_b"} \
        and set(stack["out0"]) == {"w_out"}
    # What is shared is called, not copied.
    assert granite.mamba2 is nemotron_h.mamba2 \
        and granite.gqa_heads is layers.gqa_heads \
        and granite.swiglu is afmoe.swiglu \
        and granite.mellum is mellum \
        and granite.routed_experts is grouped_matmul.routed_experts \
        and granite.cross_entropy is layers.cross_entropy


def test_the_uncut_model_matches_the_reference():
    match_the_reference(UNCUT, False, False)


def test_a_ranks_share_matches_the_reference_at_the_same_share():
    """The stacked, rematerialised layout under the chunked loss; and what
    the other rank holds is left out, in both: the whole model's loss is
    another."""
    match_the_reference(MODEL, True, True, logits=False)
    whole = UNCUT.reference("loss")[0]
    assert abs(float(whole) - float(MODEL.reference("loss")[0])) > 1e-3


def test_bf16_program_stays_near_the_float32_reference():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, remat=True,
                              loss_chunk=16)
    flat = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a,
        MODEL.init_params())
    bf16_near_the_reference(MODEL, cfg, TOKENS, flat=flat)


# -- the tie of the share to the model --------------------------------------

def _ranks(params):
    """Every rank's leaves of layer ``l{i}``, stacked on a leading axis."""
    shares = [granite.rank_share(params, WHOLE, r, RANKS)[0]
              for r in range(RANKS)]
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *shares)


@pytest.mark.parametrize("layer,axis", [(0, "ranks"), (0, None), (2, None)],
                         ids=["mamba-psum", "mamba-alone", "attention"])
def test_the_ranks_mixers_add_up_to_the_uncut_layers(layer, axis):
    """The ranks' partial outputs of a mixer, run side by side under
    ``jax.vmap(..., axis_name=)``, summed: the uncut reference's mixer. A
    Mamba-2 mixer's only with the gated norm's sum of squares taken across
    the ranks (``mamba2(axis_name=)``); each rank alone norms by its own
    channels' mean square, which is the cell's departure, and does not add
    up."""
    params = uneven(UNCUT.init_params())
    kind = WHOLE.layer_types[layer]
    a = jax.random.normal(jax.random.PRNGKey(4), (2, 32, WHOLE.hidden_size))

    def fn(blk, a):
        if kind == ATTN:
            return granite.attention(blk, a, CFG)
        return nemotron_h.mamba2(
            blk, a, heads=CFG.mamba_n_heads, head_dim=CFG.mamba_d_head,
            groups=1, states=CFG.mamba_d_state, chunk=CFG.ssd_chunk,
            eps=CFG.rms_norm_eps, axis_name=axis)

    got = jax.jit(lambda blks, a: jnp.sum(jax.vmap(
        lambda blk: fn(blk, a), axis_name="ranks")(blks), axis=0))(
            _ranks(params)[f"l{layer}"], a)
    hp = hyper(WHOLE)
    want = jnp.stack([ref.mixer(params[f"l{layer}"], s, kind, hp)
                      for s in a])
    scale = float(jnp.abs(want).max())
    if kind == MAMBA and axis is None:
        assert float(jnp.abs(got - want).max()) > 1e-2 * scale
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=2e-6 * scale)


def test_the_ranks_expert_parts_add_up_with_the_router_counted_once():
    """Each rank routes over all 16 experts alike and adds its own experts'
    part; the shared MLP is whole on every rank, so what all compute alike
    counts once: the sum is the uncut reference's expert part."""
    params = uneven(UNCUT.init_params())
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, WHOLE.hidden_size))
    shares = [granite.rank_share(params, WHOLE, r, RANKS)
              for r in range(RANKS)]
    blk = params["l1"]
    assert all(share["l1"]["router"] is blk["router"]
               and share["l1"]["shared_up"] is blk["shared_up"]
               and share["l1"]["w_up"].shape[0] == 8 for share, _ in shares)
    got = jax.jit(lambda x: sum(
        granite.moe(share["l1"], x, cfg) for share, cfg in shares)
        - (RANKS - 1) * afmoe.swiglu(x, blk["shared_gate"], blk["shared_up"],
                                     blk["shared_down"]))(x)
    hp = hyper(WHOLE)
    want = jnp.stack([ref._moe(params["l1"], s, hp, ref.identity)[0]
                      for s in x])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-6)


def test_the_sliced_vocabulary_loss_is_the_whole_loss_over_the_slice():
    """Token ids from the slice; the program holds the slice's rows of the
    embedding and everything else whole: its loss is the cross entropy of
    the uncut reference's logits restricted to the slice's columns."""
    V = CFG.vocab_size
    params = UNCUT.uneven_params(False)
    sliced = {**params, "tok_emb": params["tok_emb"][:V]}
    want = UNCUT.ref_logits(UNCUT.to_reference(params), TOKENS[:, :-1],
                            hyper(WHOLE))[..., :V]
    gold = jnp.take_along_axis(want, TOKENS[:, 1:, None], axis=-1)[..., 0]
    want = jnp.mean(jax.nn.logsumexp(want, axis=-1) - gold)
    got = UNCUT.loss_of(sliced, TOKENS, dataclasses.replace(WHOLE,
                                                            vocab_size=V))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert int(TOKENS.max()) < V


# -- the four multipliers ------------------------------------------------------

ONE_LAYER = dataclasses.replace(WHOLE, layer_types=(ATTN,))


@functools.cache
def _one_layer():
    """The uneven model's attention layer as a model of one layer, and the
    reference's logits of it."""
    params = UNCUT.uneven_params(False)
    # Queries and keys large enough for the scores to shape the softmax.
    blk = {**params["l2"], "wq": 50 * params["l2"]["wq"],
           "wk": 50 * params["l2"]["wk"]}
    one = {"tok_emb": params["tok_emb"], "norm_f": params["norm_f"],
           "l0": blk}
    return one, ref.logits(UNCUT.to_reference(one, ONE_LAYER),
                           TOKENS[:, :-1], hyper(ONE_LAYER))


@pytest.mark.parametrize("wrong", [
    {}, {"attention_multiplier": WHOLE.head_dim ** -0.5},
    {"residual_multiplier": 1.0}, {"logits_scaling": 1.0},
    {"embedding_multiplier": 1.0}],
    ids=lambda w: next(iter(w), "as_published"))
def test_a_wrong_multiplier_fails_the_tolerance(wrong):
    """One attention layer's model, the program under a multiplier that is
    not the published one (the scores' at ``head_dim ** -0.5``, a missing
    0.22, 1/16 or 12) against the reference at the published ones: the
    logits leave the tolerance the matching cases hold, by far."""
    one, want = _one_layer()
    got = granite.forward(one, TOKENS[:, :-1],
                          dataclasses.replace(ONE_LAYER, **wrong))
    worst = float(jnp.abs(got - want).max())
    if wrong:
        assert worst > 10 * UNCUT.logits_atol, worst
    else:
        assert worst < UNCUT.logits_atol, worst


# -- the walks -------------------------------------------------------------------

def _as_layers(tree, cfg):
    """A stacked tree as ``l{i}`` dicts."""
    out = {k: tree[k] for k in OUTSIDE}
    for i, blk in enumerate(decoder.layer_dicts(
            tree, decoder.run_stacks(cfg.layer_types), granite.GROUPS)):
        out[f"l{i}"] = blk
    return out


def test_two_planned_steps_walk_the_stacks_as_the_layer_loop(devices,
                                                             monkeypatch):
    """``plan_training`` over the two runs, 2 micro batches accumulated in
    one program (the written-out backward, the expert leaves an
    ``ExpertStack`` in each run, the tied embedding's two gradients summed),
    against ``jax.grad`` of the whole batch and the optimizer by hand over
    the ``l{i}`` dicts, the three layers one by one: the same losses, the
    same parameters."""
    handed = []
    moe = granite.moe

    def watched(blk, h, c):
        handed.append(tuple(type(blk[k]) for k in decoder.EXPERT_LEAVES))
        return moe(blk, h, c)

    monkeypatch.setattr(granite, "moe", watched)
    got, p = two_planned_steps(MODEL, True, devices, plain_stacked=False)
    # Adam's step is sign-like: where a gradient is next to nothing the
    # order of the accumulation's sums is the leaf's third digit.
    tree_close(_as_layers(got, MODEL.variant(True)), p, 2e-3)
    assert sum(kinds == (ExpertStack,) * 3 for kinds in handed) >= 2, handed


def test_the_gauges_and_scopes_of_a_traced_step():
    """Two micro batches, three layers in two walks: the state-space
    forward runs in a layer's forward and again in its recomputation, the
    flash forward once (the walk keeps ``(o, lse)``); what the held mixer
    and the expert part note of themselves; every part under its scope."""
    cfg = MODEL.variant(True)
    params = MODEL.init_params(cfg, True)
    tokens = granite.fake_batch(cfg, 4, 32, seed=8)
    tx, step = MODEL.step_fn(cfg, 2)
    found = kernel_counts(step, params, tx.init(params), tokens)
    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    assert gauge("ssd_calls") == 2 * 2 and gauge("attn_kept_calls") == 1
    assert gauge("ssd_heads_held") == 4
    assert gauge("ssd_state_bytes") == 2 * 4 * 32 * 64 * 4
    assert gauge("moe_choices") == 2 * 32 * 10         # a micro batch's
    assert gauge("moe_experts_held") == 8
    assert gauge("router_choice_calls") == 3
    assert gauge("rope_calls") == 0             # no positional embedding
    assert found["tepdist_ssd_fwd__g1"] == 2 \
        and found["tepdist_ssd_bwd__g1"] == 1
    names = "".join(found)
    for kernel in ("tepdist_conv_fwd", "tepdist_flash_fwd__c1__s0.0078125",
                   "tepdist_flash_dkv", "tepdist_gmm_"):
        assert kernel in names, (kernel, sorted(found))
    text = jax.jit(granite.loss_fn, static_argnums=2).lower(
        params, tokens[:1], cfg).as_text(debug_info=True)
    for scope in ("ssd_in", "ssd_conv", "ssd_rule", "ssd_norm_out",
                  "attn_qkv", "attn_core", "attn_out", "moe_router",
                  "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
                  "part_embed", "part_mixer", "part_moe", "part_head_loss"):
        assert scope in text, scope
    assert "part_mlp" not in text and "attn_rope" not in text


def test_the_held_layers_routing_stats():
    stats = decoder.routing_stats(
        jax.jit(granite.expert_choices, static_argnums=2),
        MODEL.init_params(), TOKENS, CFG)
    assert granite.routing_stats.func is decoder.routing_stats \
        and granite.routing_stats.args == (granite.expert_choices,)
    L, S, k = CFG.num_hidden_layers, 32, CFG.num_experts_per_tok
    assert stats["experts"].shape == (L, S, k)
    assert stats["held_rows"].shape == (L, CFG.experts_held[1])
    assert stats["moe_assignments_held"] \
        + stats["moe_assignments_elsewhere"] == L * S * k
    assert stats["moe_tokens_dropped"] == 0
