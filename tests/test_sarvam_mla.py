"""Sarvam MLA (sarvamai sarvam-105b): the program against the plain float32
reference at a tiny preset, the latent-attention kernels at the published
head widths against dense attention, the shares of the heads and of the
experts adding up to the uncut layer, the ``deepseek_yarn`` table and the
softmax scale against the formula written out, a planned step against a
plain loop, and the gauges."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from kernel_checks import kernel_counts, rel_l2
from model_checks import (
    KEY,
    Model,
    bf16_near_the_reference,
    match_the_reference,
    tree_close,
    two_planned_steps,
)

from benchmark.reference import sarvam_mla as ref
from tepdist_tpu.models import afmoe, decoder, layers
from tepdist_tpu.models import sarvam_mla as sarvam
from tepdist_tpu.ops.pallas import flash_attention as fa
from tepdist_tpu.ops.pallas import mla_attention as mla
from tepdist_tpu.telemetry import metrics

CFG = sarvam.CONFIGS["test"]         # heads 2..3 of 4 and experts 4..7 of 16
#                                      held; a dense layer, two expert layers
OPT = {"name": "adamw_bf16_router_bias", "learning_rate": 1e-3,
       "bias_rate": 0.001}
WHOLE = dataclasses.replace(CFG, heads_held=(0, CFG.num_attention_heads),
                            experts_held=(0, CFG.num_experts))
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
        eps=cfg.rms_norm_eps)


def uneven(params):
    """Norm gains and a selection bias away from their initial values, so
    that a gain or a bias left out shows."""
    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name))
        if name.endswith("_ln']") or "norm_f" in name:
            return a * (1 + 0.2 * jax.random.normal(key, a.shape))
        if "router_bias" in name:
            return 0.05 * jax.random.normal(key, a.shape)
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


# The row, and the file's compiled programs.
MODEL = Model(
    sarvam, ref, CFG, hyper, ("tok_emb", "norm_f", "lm_head"),
    stack=lambda tree, cfg: decoder.stack_layers(
        tree, sarvam._stacks(cfg), ("tok_emb", "norm_f", "lm_head")),
    uneven=uneven, opt=OPT)
init_params, to_reference = MODEL.init_params, MODEL.to_reference
loss_and_grads, loss_of = MODEL.loss_and_grads, MODEL.loss_of
ref_loss, ref_loss_and_grads = MODEL.ref_loss, MODEL.ref_loss_and_grads
ref_expert_counts = MODEL.ref_expert_counts


@pytest.mark.parametrize("stacked,remat", [(False, False), (True, True)],
                         ids=["unstacked-plain", "stacked-remat"])
def test_logits_loss_and_every_gradient_match_the_reference(stacked, remat):
    grads = match_the_reference(MODEL, stacked, remat)
    # The bias's "gradient" is the count of its router's choices.
    counts = MODEL.reference("counts")
    got = grads["blocks"]["router_bias"] if stacked else jnp.stack(
        [grads[f"l{i}"]["router_bias"] for i in (1, 2)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(counts))


def test_bf16_program_stays_near_the_float32_reference():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, remat=True,
                              loss_chunk=16)
    bf16_near_the_reference(MODEL, cfg, sarvam.fake_batch(cfg, 2, 32, seed=2))


def dense_attention(qn, qr, kn, kr, v, scale, causal=True):
    """The plain form: the shared rotary key joined to every head's keys."""
    q = jnp.concatenate([qn, qr], axis=-1)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, qr.shape)], axis=-1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        T = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def operands(B, H, T, Dn, Dr, Dv, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = ((B, H, T, Dn), (B, H, T, Dr), (B, H, T, Dn), (B, 1, T, Dr),
              (B, H, T, Dv), (B, H, T, Dv))
    return [jax.random.normal(k, s, jnp.float32).astype(dtype)
            for k, s in zip(keys, shapes)]


@pytest.mark.parametrize("T,bq,bk,causal", [
    (256, 128, 128, True), (256, 64, 128, True), (256, 128, 64, True),
    (256, None, None, True), (128, 64, 64, False)],
    ids=["equal", "bq<bk", "bq>bk", "default", "not-causal"])
def test_kernels_match_dense_attention_at_the_published_widths(T, bq, bk,
                                                               causal):
    """128 + 64 and 128, interpreted: the output and all five gradients;
    ``dk_rope`` is the sum over the heads."""
    *ops, do = operands(2, 3, T, 128, 64, 128)
    scale = CFG.softmax_scale

    def kernel(*xs):
        return mla.mla_attention(*xs, causal=causal, scale=scale,
                                 block_q=bq, block_k=bk)

    o, vjp = jax.vjp(kernel, *ops)
    want, want_vjp = jax.vjp(lambda *xs: dense_attention(
        *xs, scale, causal), *ops)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), rtol=0,
                               atol=2e-5)
    for name, g, w in zip(("dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv"),
                          vjp(do), want_vjp(do)):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=0,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_the_shared_keys_gradient_is_the_sum_over_heads():
    """Each head alone, with the same ``k_rope``: their ``dk_rope`` add up
    to the call's over all heads."""
    *ops, do = operands(1, 4, 128, 16, 8, 12, seed=3)
    qn, qr, kn, kr, v = ops

    def dkr(rows):
        f = lambda kr: mla.mla_attention(          # noqa: E731
            qn[:, rows], qr[:, rows], kn[:, rows], kr, v[:, rows])
        return jax.vjp(f, kr)[1](do[:, rows])[0]

    total = sum(dkr(slice(h, h + 1)) for h in range(4))
    np.testing.assert_allclose(np.asarray(dkr(slice(0, 4))),
                               np.asarray(total), rtol=0, atol=1e-6)


def test_kernels_in_bf16_stay_at_the_flash_kernels_distance():
    *ops, do = operands(1, 2, 512, 128, 64, 128, jnp.bfloat16, seed=4)
    scale = sarvam.CONFIGS["105b"].softmax_scale
    o, vjp = jax.vjp(lambda *xs: mla.mla_attention(*xs, scale=scale), *ops)
    f32 = [x.astype(jnp.float32) for x in ops]
    want, want_vjp = jax.vjp(lambda *xs: dense_attention(*xs, scale), *f32)
    for g, w in zip((o,) + vjp(do),
                    (want,) + want_vjp(do.astype(jnp.float32))):
        assert rel_l2(g, w) < 0.005


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("B,H,T,bq,bk,causal", [
    (1, 2, 512, 64, 128, True), (1, 2, 512, 128, 64, True),
    (1, 2, 384, 64, 128, False), (2, 3, 256, 128, 64, False),
    (2, 3, 512, 128, 256, True)],
    ids=["bq<bk", "bq>bk", "not-causal", "not-causal-heads", "heads"])
def test_the_one_backward_call_gives_all_five_gradients(B, H, T, bq, bk,
                                                        causal, dtype):
    """Several query blocks and several key blocks a head, unequal tiles:
    the head's dQ^T is zeroed at its first key block, gathers every key
    block's part over the grid's inner axis and is written at the last; with
    ``B * H > 1`` heads follow each other on the grid and each starts from
    zero. All five gradients against dense float32 attention."""
    *ops, do = operands(B, H, T, 32, 16, 24, dtype, seed=6)
    assert T // bq > 1 and T // bk > 1 and bq != bk

    def kernel(*xs):
        return mla.mla_attention(*xs, causal=causal, scale=0.2, block_q=bq,
                                 block_k=bk)

    text = str(jax.make_jaxpr(lambda *xs: jax.vjp(kernel, *xs)[1](do))(*ops))
    assert text.count("tepdist_mla_dkv__") == 1 \
        and "tepdist_mla_dq" not in text
    got = jax.vjp(kernel, *ops)[1](do)
    f32 = [x.astype(jnp.float32) for x in ops]
    want = jax.vjp(lambda *xs: dense_attention(*xs, 0.2, causal), *f32)[1](
        do.astype(jnp.float32))
    for name, g, w in zip(("dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv"),
                          got, want):
        assert g.shape == w.shape and g.dtype == dtype, name
        assert rel_l2(g, w) < (0.005 if dtype == jnp.bfloat16 else 1e-5), name
    # A head's gradients do not depend on the heads before it on the grid.
    if B * H > 1:
        last = [x[-1:, -1:] if x.shape[1] > 1 else x[-1:] for x in ops]
        alone = jax.vjp(kernel, *last)[1](do[-1:, -1:])
        for i in (0, 1, 2, 4):       # dk_rope is a sum over the heads
            np.testing.assert_array_equal(np.asarray(alone[i][0, 0]),
                                          np.asarray(got[i][-1, -1]))


def test_kernel_names_and_what_the_flash_kernels_keep():
    """``tepdist_mla_<fwd|dkv>__c1__s<scale>__h<heads>`` (the backward pass
    is the one ``dkv`` call: no ``tepdist_mla_dq``), never
    ``tepdist_flash_*``; the flash kernels' names as they were."""
    *ops, do = operands(1, 2, 128, 16, 8, 12)
    text = str(jax.make_jaxpr(lambda *xs: jax.vjp(
        lambda *ys: mla.mla_attention(*ys, scale=0.25), *xs)[1](do))(*ops))
    for which in ("fwd", "dkv"):
        assert f"tepdist_mla_{which}__c1__s0.25__h2" in text, which
    assert "tepdist_mla_dq" not in text
    assert "tepdist_flash_" not in text
    assert fa._kernel_name("fwd", True, 0.125, 12) \
        == "tepdist_flash_fwd__c1__s0.125__h12"
    with pytest.raises(ValueError, match="k_rope"):
        mla.mla_attention(ops[0], ops[1], ops[2], ops[1], ops[4])
    with pytest.raises(ValueError, match="tile"):
        mla.mla_attention(*(x[:, :, :127] for x in ops))


@pytest.mark.parametrize("forward_kept", [False, True])
def test_attention_from_a_saved_forward_is_the_call(forward_kept):
    """``()`` gives the forward alone; from ``(o, lse)`` the primal is ``o``
    and the gradients are the call's, bit for bit."""
    *ops, do = operands(1, 2, 128, 16, 8, 12, seed=5)
    whole, vjp = jax.vjp(lambda *xs: mla.mla_attention(*xs), *ops)
    o, lse = mla.mla_attention_kept(*ops, ())
    assert lse.shape == (1, 2, 128) and lse.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(o), np.asarray(whole))
    if forward_kept:
        kept, kept_vjp = jax.vjp(
            lambda *xs: mla.mla_attention_kept(*xs, (o, lse)), *ops)
        np.testing.assert_array_equal(np.asarray(kept), np.asarray(o))
        for g, w in zip(kept_vjp(do), vjp(do)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_the_heads_shares_add_up_to_the_uncut_layer():
    """The two shares of two heads each, through their rows of ``wo``, are
    the attention all four heads give: the uncut reference's."""
    params = init_params(WHOLE)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, CFG.hidden_size))
    total = 0.0
    for first in (0, 2):
        share, cfg = sarvam.rank_share(params, WHOLE, (first, 2),
                                       WHOLE.experts_held)
        blk = share["l1"]
        assert blk["wq"].shape[1] == 2 * (CFG.qk_nope_head_dim
                                          + CFG.qk_rope_head_dim)
        assert blk["wo"].shape[0] == 2 * CFG.v_head_dim
        assert blk["wkva"].shape == params["l1"]["wkva"].shape   # whole
        total = total + sarvam.attend(blk, x, cfg) @ blk["wo"]
    blk, hp = params["l1"], hyper(WHOLE)
    want = jnp.stack([ref._attention(
        blk, ref._rms_norm(s, blk["input_ln"], hp.eps), hp, ref.identity)
        for s in x])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(sarvam.attend(blk, x, WHOLE) @ blk["wo"]),
        np.asarray(want), rtol=0, atol=2e-6)
    # The helper beside held_mask / held_weights cuts columns or rows.
    w = jnp.arange(24.0).reshape(2, 12)
    np.testing.assert_array_equal(
        np.asarray(decoder.held_heads(w, (1, 2), 3)), np.asarray(w[:, 3:9]))
    np.testing.assert_array_equal(
        np.asarray(decoder.held_heads(w.T, (1, 2), 3, axis=0)),
        np.asarray(w.T[3:9]))


def test_the_expert_shares_add_up_with_the_shared_expert_counted_once():
    """Shares (0,4) .. (12,4) of the 16-wide router, the shared expert once:
    the uncut reference's whole expert layer; and the layer this model runs
    is Trinity's own function."""
    assert sarvam.moe is afmoe.moe and sarvam.swiglu is afmoe.swiglu \
        and sarvam.router is afmoe.router
    params = uneven(init_params(WHOLE))
    blk = params["l1"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, CFG.hidden_size))
    shared = afmoe.swiglu(x, blk["shared_gate"], blk["shared_up"],
                          blk["shared_down"])
    total = shared
    for first in range(0, CFG.num_experts, 4):
        share, cfg = sarvam.rank_share(params, WHOLE, WHOLE.heads_held,
                                       (first, 4))
        total = total + afmoe.moe(share["l1"], x, cfg) - shared
    hp = hyper(WHOLE)
    want = jnp.stack([ref._moe(blk, s, hp, ref.identity)[0] for s in x])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=2e-6)


def test_a_rank_of_the_whole_model_is_the_reference_at_the_same_share():
    """``rank_share`` of the whole model's parameters: the rank's loss is the
    reference's on the same heads and experts."""
    params = init_params(WHOLE)
    share, cfg = sarvam.rank_share(params, WHOLE, CFG.heads_held,
                                   CFG.experts_held)
    assert cfg == CFG
    tokens = sarvam.fake_batch(cfg, 2, 32, seed=7)
    want = ref_loss(to_reference(share, cfg), tokens, hyper(cfg))
    assert float(loss_of(share, tokens, cfg)) \
        == pytest.approx(float(want), rel=1e-5)
    whole = ref_loss(to_reference(params, WHOLE), tokens, hyper(WHOLE))
    assert abs(float(whole) - float(want)) > 1e-5


def test_the_deepseek_yarn_table_and_the_scale_are_the_formulas():
    """Written out for sarvam-105b's ``rope_scaling``: 32 pairs of a 64-wide
    rotary part, theta 10000, factor 40 over an original 4096, beta 32 / 1;
    ``m = 0.1 ln 40 + 1``; cos and sin times ``mscale / mscale_all_dim``'s
    ratio, 1."""
    cfg = sarvam.CONFIGS["105b"]
    m = 0.1 * math.log(40) + 1
    assert m == pytest.approx(1.3689, abs=5e-5)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    table = cfg.rope_table
    assert table.scale == 1.0 and table.name == "rope_yarn"

    def correction(turns):
        return 64 * math.log(4096 / (turns * 2 * math.pi)) \
            / (2 * math.log(10000))

    low, high = math.floor(correction(32)), math.ceil(correction(1))
    assert (low, high) == (10, 23)
    want = []
    for i in range(32):
        plain = 10000.0 ** (-2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append((1 - ramp) * plain + ramp * plain / 40)
    np.testing.assert_allclose(table.inv_freq, want, rtol=2e-6)
    assert table.inv_freq[0] == 1.0 \
        and table.inv_freq[31] == pytest.approx(10000 ** (-62 / 64) / 40,
                                                rel=2e-6)
    # The reference's own table, and its scale, are the same numbers.
    hp = hyper(cfg)
    np.testing.assert_allclose(
        np.asarray(ref.yarn_inv_freq(64, 10000.0, hp.yarn)), want, rtol=2e-6)
    assert ref.softmax_scale(hp) == pytest.approx(cfg.softmax_scale)
    # A chunk's rotary part is rotated at its own positions.
    x = jax.random.normal(KEY, (1, 2, 32, 8))
    np.testing.assert_allclose(
        np.asarray(layers.rope(x[:, :, 16:], CFG.rope_table, 16)),
        np.asarray(layers.rope(x, CFG.rope_table)[:, :, 16:]), rtol=0,
        atol=1e-6)


def test_the_blocks_token_wise_parts_in_chunks_change_nothing(monkeypatch):
    """Forced small, four chunks of 8 positions give the whole sequence's
    loss and gradients (rotary at each chunk's own positions, routing a
    token's own), and at the published sizes a chunk is 2,048 tokens."""
    big = sarvam.CONFIGS["105b"]
    assert sarvam._widest(big) == 16384
    assert layers.tokens_a_chunk(1, 16384, sarvam._widest(big)) == 2048
    cfg = dataclasses.replace(CFG, remat=True)
    params = uneven(init_params(cfg, stacked=True))
    tokens = sarvam.fake_batch(cfg, 2, 32, seed=4)
    whole = loss_and_grads(params, tokens, cfg)
    monkeypatch.setattr(layers, "_CHUNK_ELEMENTS",
                        2 * 8 * sarvam._widest(cfg))
    assert layers.tokens_a_chunk(2, 32, sarvam._widest(cfg)) == 8
    # Traced anew: the chunk's size is read while the loss is traced.
    loss, grads = jax.jit(jax.value_and_grad(sarvam.loss_fn),
                          static_argnums=2)(params, tokens, cfg)
    assert float(loss) == pytest.approx(float(whole[0]), rel=1e-6)
    tree_close(grads, whole[1], 1e-5, skip=())


def test_a_walk_keeps_one_forward_a_layer_and_the_gauges_say_so():
    """Two micro batches, three layers in two walks: the forward kernel
    runs once a layer and micro batch (its ``(o, lse)`` handed over, 3 calls
    and their bytes), each layer's backward pass is one kernel
    (``mla_bwd_calls`` 3), every walked leaf accumulates inside the layer loop,
    and the noted gauges hold the heads held and one layer's latent."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params = init_params(cfg, stacked=True)
    tokens = sarvam.fake_batch(cfg, 4, 32, seed=8)
    tx, step = MODEL.step_fn(cfg, 2)

    def kernels(step):       # fwd, dkv: the places each stands in
        found = kernel_counts(step, params, tx.init(params), tokens)
        assert not [name for name in found if "tepdist_mla_dq" in name]
        return [sum(n for name, n in found.items()
                    if name.startswith(f"tepdist_mla_{which}__"))
                for which in ("fwd", "dkv")]

    standing = kernels(step)
    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    assert gauge("mla_fwd_calls") == 3 and gauge("attn_kept_calls") == 3
    assert gauge("mla_bwd_calls") == 3
    Hh, Dv = cfg.heads_held[1], cfg.v_head_dim
    assert gauge("attn_kept_bytes") == 3 * 2 * Hh * 32 * (Dv * 4 + 4)
    assert gauge("mla_heads_held") == 2
    assert gauge("mla_latent_bytes") == 2 * 32 * (24 + 8) * 4
    assert gauge("moe_rows_sum_calls") == 4     # 2 a walked expert layer
    fused, unfused = gauge("ga_fused_bytes"), gauge("ga_unfused_bytes")
    stacks = sum(a.nbytes for name in ("dense", "blocks")
                 for a in jax.tree_util.tree_leaves(params[name]))
    assert fused == stacks and unfused > 0
    # A stack's walk traces its body once: the forward kernel stands once
    # in each walk's forward loop and nowhere in its backward loop.
    assert standing == [2, 2]
    # One micro batch: the plain checkpointed scan keeps nothing, and the
    # forward kernel stands in the forward loop and in the backward loop's
    # recomputation.
    assert kernels(MODEL.step_fn(cfg, 1)[1]) == [4, 2]
    assert gauge("attn_kept_calls") == 0 and gauge("mla_fwd_calls") >= 3
    assert gauge("mla_bwd_calls") == 3


def test_the_projections_carry_their_scopes():
    cfg = dataclasses.replace(CFG, remat=True)
    params = init_params(cfg, stacked=True)
    tokens = sarvam.fake_batch(cfg, 1, 32)
    text = jax.jit(sarvam.loss_fn, static_argnums=2).lower(
        params, tokens, cfg).as_text(debug_info=True)
    for scope in ("mla_q", "mla_kv_down", "mla_kv_up", "mla_rope",
                  "mla_out", "mla_in", "mla_out_mlp", "rope_yarn",
                  "moe_router", "moe_shared", "tepdist_mla_fwd"):
        assert scope in text, scope


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["unstacked", "stacked"])
def test_two_planned_steps_are_a_plain_grad_and_optimizer_loop(stacked,
                                                               devices):
    """``plan_training`` with 2 micro batches accumulated in one program
    against ``jax.grad`` of the whole batch and the optimizer by hand: the
    same losses, the same parameters, the selection bias moved by the
    reference's update of each step's counts."""
    bias = [0.0]

    def the_references_update(p, tokens, cfg):
        counts = ref_expert_counts(to_reference(p, cfg), tokens, hyper(cfg))
        bias[0] = ref.bias_update(bias[0], counts, OPT["bias_rate"])

    got, p = two_planned_steps(MODEL, stacked, devices,
                               each=the_references_update)
    bias = bias[0]
    # Adam's first steps are sign-like: where a gradient is next to nothing
    # the order of the accumulation's sums shows in the update.
    tree_close(got, p, 1e-4, skip=())
    after = got["blocks"]["router_bias"] if stacked else jnp.stack(
        [got[f"l{i}"]["router_bias"] for i in (1, 2)])
    np.testing.assert_allclose(np.asarray(after), np.asarray(bias),
                               atol=1e-9)
    assert np.abs(np.asarray(bias)).max() > 0
