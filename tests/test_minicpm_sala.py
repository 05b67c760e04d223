"""MiniCPM-SALA (openbmb): the program against the plain float32 reference
at a tiny preset with the published structure (2 key/value groups, blocks of
8, top 4, window 16, ``dense_len`` 32, 128 positions, runs of 1, 2, 1 and 1
layers), the linear-attention kernels (interpret mode: their own code)
against the sequential recurrence, the sparse layer's choice and its kernels
against the reference's sets and mask, the block's token-wise parts in
chunks, and the walks under gradient accumulation."""

import dataclasses
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import minicpm_sala as ref
from kernel_checks import equations, kernel_counts, leaves_close
from kernel_checks import sala_hyper as hyper
from model_checks import (
    KEY,
    Model,
    bf16_near_the_reference,
    match_the_reference,
    rel_l2_close,
)
from tepdist_tpu.models import decoder, layers
from tepdist_tpu.models import minicpm_sala as sala
from tepdist_tpu.ops.pallas import block_topk_attention as bt
from tepdist_tpu.ops.pallas import flash_attention as fa
from tepdist_tpu.optim import make_optimizer
from tepdist_tpu.telemetry import metrics

CFG = sala.CONFIGS["test"]
GEO = CFG.sparse
OPT = {"name": "adamw_bf16", "learning_rate": 1e-3}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def uneven_gains(params):
    """Norm gains off 1, so that a gain applied in the wrong place shows."""
    def off(path, a):
        name = jax.tree_util.keystr(path)
        if a.dtype != jnp.float32 or "norm" not in name and "_ln" not in name:
            return a
        return a * (1 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(len(name) + a.size), a.shape))
    return jax.tree_util.tree_map_with_path(off, params)


# The row, and the file's compiled programs: the program and the reference
# (``hp`` a tuple of plain values), each traced once a (shapes,
# configuration).
MODEL = Model(
    sala, ref, CFG, hyper, ("tok_emb", "lm_head", "norm_f"),
    stack=lambda tree, cfg: decoder.stack_layers(
        tree, decoder.run_stacks(cfg.mixer_types),
        ("tok_emb", "lm_head", "norm_f"), sala.GROUPS, sala._GROUP_OF),
    init=lambda cfg, key: sala.init_params(cfg, key, std=0.05),
    uneven=uneven_gains, batch=(2, 128), chunk=48, logits_relative=True,
    close=rel_l2_close, opt=OPT)
loss_and_grads, loss_of = MODEL.loss_and_grads, MODEL.loss_of
ref_loss, ref_loss_and_grads = MODEL.ref_loss, MODEL.ref_loss_and_grads
to_reference = MODEL.to_reference


# -- the program against the reference ---------------------------------------

# 128 positions are past ``dense_len`` (the sparse layers choose blocks), 32
# are not (plain causal attention). 2e-5: float32 sums in another order
# through five layers; bf16 anywhere (2**-9 a rounding) reads 1e-3 or more.
@pytest.mark.parametrize("stacked,remat,T", [
    (False, False, 128), (True, False, 128), (True, True, 128),
    (True, True, 32)])
def test_logits_loss_and_every_gradient_match_the_reference(stacked, remat,
                                                            T):
    match_the_reference(MODEL, stacked, remat, T=T)


def test_the_dense_len_switch_counts_its_side():
    """At or under ``dense_len`` the sparse layers are plain causal
    attention (the flash kernels), past it the chosen blocks' kernels."""
    params = sala.stacked_init_params(CFG, KEY)
    gauge = lambda n: metrics().gauge(n).value or 0         # noqa: E731
    for T, dense, chosen in ((32, 2, 0), (40, 0, 2)):
        for name in ("topk_attn_dense_calls", "topk_attn_calls",
                     "lin_attn_calls"):
            metrics().gauge(name).set(0)
        jax.make_jaxpr(lambda p, t: sala.loss_fn(p, t, CFG))(
            params, sala.fake_batch(CFG, 1, T))
        assert (gauge("topk_attn_dense_calls"), gauge("topk_attn_calls")) \
            == (dense, chosen), T
        assert gauge("lin_attn_calls") == 3


def test_bf16_program_stays_near_the_float32_reference():
    """bf16 weights and activations, float32 inside the linear attention.
    The distance is bf16's rounding of every activation through five layers
    and the sets it flips: a per cent, not the float32 test's 1e-5."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, remat=True,
                              loss_chunk=48)
    grads = bf16_near_the_reference(MODEL, cfg, MODEL.tokens(),
                                    flat=sala.init_params(cfg, KEY))
    assert grads["vec1"]["o_norm"].dtype == jnp.float32
    assert grads["run1"]["wq"].dtype == jnp.bfloat16


def test_a_doubled_micro_batch_shows():
    """What the cell's check compares: a batch that repeats a sequence is
    the reference's weighted loss, and no other weighting."""
    params = sala.stacked_init_params(CFG, KEY)
    unique = sala.fake_batch(CFG, 2, 64, seed=3)
    batch = unique[jnp.asarray([0, 1, 1, 1])]
    want = ref_loss(params, unique, hyper(CFG), jnp.asarray([0.25, 0.75]))
    assert float(loss_of(params, batch, CFG)) \
        == pytest.approx(float(want), rel=1e-5)
    even = ref_loss(params, unique, hyper(CFG))
    assert abs(float(even) - float(want)) > 1e-4


def test_the_blocks_token_wise_parts_in_chunks_change_nothing(monkeypatch):
    """The chunk is chosen from the shapes; forced small, eight chunks of 16
    positions give the whole sequence's loss and gradients (the rotary
    embedding at each chunk's own positions), and no chunk is as wide as the
    sequence."""
    cfg = dataclasses.replace(CFG, remat=True)
    params = uneven_gains(sala.stacked_init_params(cfg, KEY, std=0.05))
    tokens = sala.fake_batch(cfg, 2, 128, seed=4)
    whole = loss_and_grads(params, tokens, cfg)
    monkeypatch.setattr(layers, "_CHUNK_ELEMENTS",
                        2 * 16 * cfg.intermediate_size)
    assert layers.tokens_a_chunk(2, 128, cfg.intermediate_size) == 16
    # Traced anew: the chunk's size is read while the loss is traced.
    loss, grads = jax.jit(jax.value_and_grad(sala.loss_fn),
                          static_argnums=2)(params, tokens, cfg)
    assert float(loss) == pytest.approx(float(whole[0]), rel=1e-6)
    leaves_close(grads, whole[1], 1e-5)
    text = str(jax.make_jaxpr(lambda p, t: sala.loss_fn(p, t, cfg))(
        params, tokens))
    assert f"f32[2,128,{cfg.intermediate_size}]" not in text
    assert f"f32[2,16,{cfg.intermediate_size}]" in text


def test_the_chunk_follows_the_shapes():
    assert layers.tokens_a_chunk(1, 32768, 16384) == 2048     # the cell's
    assert layers.tokens_a_chunk(1, 8192, 8192) == 4096
    assert layers.tokens_a_chunk(2, 128, 96) == 128           # one chunk
    assert layers.tokens_a_chunk(1, 3 * 1031, 2 ** 14) == 1031


# -- the decay slopes --------------------------------------------------------

def test_the_decay_slopes_are_the_published_layers():
    """Lightning Attention-2's slopes with MiniMax-01's per-layer factor at
    the layer's published index, whatever is held."""
    cfg = dataclasses.replace(sala.CONFIGS["9b"], first_layer=4,
                              mixer_types=(sala.LIGHTNING,) * 2)
    got = sala.log_decays(cfg, 1)                            # published 5
    h = np.arange(1, 33)
    np.testing.assert_allclose(
        got, -(2.0 ** (-8 * h / 32)) * (1 - 5 / 31 + 1e-5), rtol=1e-6)
    np.testing.assert_allclose(
        np.exp(got), np.asarray(ref.decays(hyper(cfg), 5)), rtol=1e-6)


# -- the structure and the walks ----------------------------------------------

def test_the_published_order_and_its_runs():
    full = sala.CONFIGS["9b"]
    assert full.num_hidden_layers == 32
    assert [i for i, k in enumerate(full.mixer_types) if k == sala.SPARSE] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    assert full.runs[:3] == ((sala.SPARSE, 0, 1), (sala.LIGHTNING, 1, 8),
                             (sala.SPARSE, 9, 1))
    assert full.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert CFG.runs == ((sala.SPARSE, 0, 1), (sala.LIGHTNING, 1, 2),
                        (sala.SPARSE, 3, 1), (sala.LIGHTNING, 4, 1))


def test_the_stacks_follow_the_runs():
    params = sala.stacked_init_params(CFG, KEY)
    flat = sala.init_params(CFG, KEY)
    assert set(params) == {"tok_emb", "lm_head", "norm_f"} | {
        f"{g}{r}" for g in ("run", "vec") for r in range(4)}
    assert params["run1"]["wq"].shape == (2, 64, 64)
    assert params["run0"]["wk"].shape == (1, 64, 32)         # 2 of 4 heads
    assert set(params["vec1"]) == {"input_ln", "ff_ln", "q_norm", "k_norm",
                                   "o_norm"}
    assert "o_norm" not in params["vec0"]
    np.testing.assert_array_equal(np.asarray(params["run1"]["wo"][1]),
                                  np.asarray(flat["l2"]["wo"]))
    np.testing.assert_array_equal(
        np.asarray(decoder.run_blocks(params, 2, sala.GROUPS)["w_up"][0]),
        np.asarray(flat["l3"]["w_up"]))


def test_the_reference_refuses_weights_out_of_order():
    params = to_reference(sala.init_params(CFG, KEY), CFG)
    params["layers"][0], params["layers"][1] = \
        params["layers"][1], params["layers"][0]
    with pytest.raises(ValueError, match="mixer_types says"):
        ref.hidden(params, jnp.zeros((16,), jnp.int32), hyper(CFG))


def nbytes(tree):
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


def test_every_walk_accumulates_in_the_layer_loop_and_counts_its_kernels():
    """Two micro batches: all four stacks' leaves are found by the sink
    (embedding, head and final norm are outside the blocks), the linear
    attention's forward runs twice a layer (the walk and its recomputation)
    and the block top-k attention's once: each sparse layer hands the walk
    its sets and its forward kernel's ``(o, lse)``."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=48)
    params = sala.stacked_init_params(cfg, KEY)
    tx, step = MODEL.step_fn(cfg, 2)
    tokens = sala.fake_batch(cfg, 2, 128)
    jax.make_jaxpr(step)(params, tx.init(params), tokens)
    gauge = lambda n: metrics().gauge(n).value          # noqa: E731
    stacks = {k: v for k, v in params.items()
              if k not in ("tok_emb", "lm_head", "norm_f")}
    assert gauge("ga_fused_bytes") == nbytes(stacks)
    assert gauge("ga_unfused_bytes") == nbytes(params) - nbytes(stacks)
    assert gauge("lin_attn_calls") == 2 * 3
    assert gauge("topk_attn_calls") == 2 * 1
    assert gauge("topk_attn_dense_calls") == 0
    assert gauge("topk_attn_keys_per_query") == pytest.approx(
        bt.mean_keys_per_query(128, GEO))
    assert gauge("ssm_scan_calls") == 0
    # A micro batch of one sequence, two sparse layers, two hand-overs each:
    # o float32 [128, 4, 16] with lse [2, 2, 128], and the sets [2, 128, 4].
    assert gauge("attn_kept_calls") == 2 * 2
    assert gauge("attn_kept_bytes") == 2 * 4 * 128 * (4 * 16 + 4 + 2 * 4)


def choices(fn, *args):
    """How often ``select_blocks`` is in ``fn``'s program: the one place of
    the model that reads a float's bits as an integer
    (``block_topk_attention._ordered_bits``)."""
    return sum(e.primitive.name == "bitcast_convert_type"
               for e in equations(fn, *args))


def whole_remat(monkeypatch):
    """Every block under ``nothing_kept``: all of it rematerialised, as
    before a walk kept anything."""
    block = sala.block

    def whole(*args, **kwargs):
        with fa.nothing_kept():
            return block(*args, **kwargs)
    monkeypatch.setattr(sala, "block", whole)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_walked_sparse_layer_keeps_its_forward_and_its_choice(dtype,
                                                                monkeypatch):
    """Two micro batches over the stacked model: each sparse layer's forward
    kernel and choice are in the step once (the walk; its recomputation
    takes them back) where a block rematerialised whole holds them twice,
    the other kernels as often as there, and two steps leave loss,
    parameters and optimizer state bit for bit the same."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=48, dtype=dtype)
    params = sala.stacked_init_params(cfg, KEY, std=0.05)
    tokens = sala.fake_batch(cfg, 2, 128, seed=3)
    tx, step = MODEL.step_fn(cfg, 2)
    jitted = MODEL.ga_step(cfg, 2)[1]       # the stacked split's, below
    state = tx.init(params)
    kept = kernel_counts(step, params, state, tokens)
    kept_choices = choices(step, params, state, tokens)
    got = (params, state)
    for _ in range(2):
        loss_got, *got = jitted(*got, tokens)

    whole_remat(monkeypatch)
    _, step = MODEL.step_fn(cfg, 2)
    whole = kernel_counts(step, params, state, tokens)
    assert metrics().gauge("attn_kept_calls").value == 0
    assert metrics().gauge("topk_attn_calls").value == 2 * 2
    want = (params, state)
    for _ in range(2):
        loss_want, *want = jax.jit(step)(*want, tokens)

    sparse_layers = cfg.mixer_types.count(sala.SPARSE)
    assert kept.pop("tepdist_topk_attn_fwd") == sparse_layers
    assert whole.pop("tepdist_topk_attn_fwd") == 2 * sparse_layers
    assert kept == whole and kept["tepdist_topk_attn_bwd"] == sparse_layers
    assert kept_choices == sparse_layers
    assert choices(step, params, state, tokens) == 2 * sparse_layers
    assert float(loss_got) == float(loss_want)
    assert np.isfinite(float(loss_got))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("stacked,remat,micro", [
    (True, False, 2),       # a plain scan, nothing rematerialised
    (False, True, 2),       # l0.. blocks, each under jax.checkpoint
    (True, True, 1)])       # one micro batch: scan_blocks' plain scan
def test_outside_a_walk_nothing_is_handed_over(stacked, remat, micro,
                                               monkeypatch):
    """Where no walk keeps anything the step is the program of a model with
    no hand-over in it, equation for equation, and the gauges read 0."""
    cfg = dataclasses.replace(CFG, remat=remat, loss_chunk=48)
    init = sala.stacked_init_params if stacked else sala.init_params
    params = init(cfg, KEY)
    tokens = sala.fake_batch(cfg, 2, 128)
    tx, step = MODEL.step_fn(cfg, micro)
    state = tx.init(params)
    here = [str(e.primitive) for e in equations(step, params, state, tokens)]
    assert metrics().gauge("attn_kept_calls").value == 0
    assert metrics().gauge("attn_kept_bytes").value == 0
    # Two sparse layers; a block under ``jax.checkpoint`` holds its forward
    # kernel again in the backward pass.
    assert kernel_counts(step, params, state, tokens)[
        "tepdist_topk_attn_fwd"] == 2 * (2 if remat else 1)
    for module in (bt, fa):
        monkeypatch.setattr(module, "hand_over",
                            lambda attend: attend(None))
    _, step = MODEL.step_fn(cfg, micro)
    assert [str(e.primitive) for e in equations(
        step, params, state, tokens)] == here
    assert "optimization_barrier" not in here


@pytest.mark.parametrize("stacked", [False, True])
def test_two_micro_batches_leave_the_state_one_batch_of_two_leaves(stacked):
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=48)
    init = sala.stacked_init_params if stacked else sala.init_params
    params = init(cfg, KEY)
    tokens = sala.fake_batch(cfg, 2, 128, seed=2)
    results = []
    for micro in (1, 2):
        tx, step = MODEL.ga_step(cfg, micro)
        state = (params, tx.init(params))
        for _ in range(2):
            loss, *state = step(*state, tokens)
        results.append((float(loss), state))
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-5)
    leaves_close(results[1][1][0], results[0][1][0], 1e-4)
    # The moments are bf16: where the two sums' float32 noise crosses a
    # rounding boundary an element moves by 2**-8 of itself.
    leaves_close(results[1][1][1], results[0][1][1], 1e-3)


def test_the_mixers_parts_carry_their_scopes():
    cfg = dataclasses.replace(CFG, remat=True)
    params = sala.stacked_init_params(cfg, KEY)
    tokens = sala.fake_batch(cfg, 1, 128)
    text = jax.jit(sala.loss_fn, static_argnums=2).lower(
        params, tokens, cfg).as_text(debug_info=True)
    for scope in ("mixer_in", "lin_attn", "topk_select", "topk_attend",
                  "mixer_out_mlp", "rope_plain", "tepdist_lightning_fwd",
                  "tepdist_topk_attn_fwd"):
        assert scope in text, scope


def test_a_planned_step_with_two_micro_batches_runs(devices):
    """``plan_training`` with the other models' entry point: 2 micro batches
    accumulated in one program, the five kernels inside the accumulation
    scan and the layer walks."""
    from tepdist_tpu.train import plan_training
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=48)
    params = sala.stacked_init_params(cfg, KEY)
    tokens = sala.fake_batch(cfg, 2, 128)
    plan = plan_training(lambda p, t: sala.loss_fn(p, t, cfg),
                         make_optimizer(OPT), params, tokens,
                         devices=devices[:1], explore=False,
                         num_micro_batches=2)
    want = float(sala.loss_fn(params, tokens, cfg))
    assert plan.step(tokens) == pytest.approx(want, rel=1e-5)
    text = plan.compiled_step_text()
    assert "tepdist_train_step" in text
    assert np.isfinite(plan.step(tokens))
