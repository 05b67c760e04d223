"""Jamba (AI21): the program against the plain float32 reference at a tiny
preset, the selective-scan kernel (interpret mode: its own code) against the
sequential scan, the order of the layers from the period rule, the walks of
unequal shape under gradient accumulation, and the scan's gauges and scopes."""

import dataclasses
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import jamba as ref
from kernel_checks import leaves_close
from model_checks import (
    KEY,
    Model,
    bf16_near_the_reference,
    match_the_reference,
    rel_l2_close,
)
from tepdist_tpu.models import decoder, jamba
from tepdist_tpu.ops.pallas import selective_scan as ssm
from tepdist_tpu.optim import make_optimizer
from tepdist_tpu.telemetry import metrics

CFG = jamba.CONFIGS["test"]          # Mamba x 2, attention, Mamba x 2;
#                                      128 channels of 8 states, chunks of 16
OPT = {"name": "adamw_bf16", "learning_rate": 1e-3}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def hyper(cfg):
    return ref.Hyper(
        n_head=cfg.num_attention_heads, n_kv_head=cfg.num_key_value_heads,
        attn_layer_period=cfg.attn_layer_period,
        attn_layer_offset=cfg.attn_layer_offset, d_state=cfg.mamba_d_state,
        dt_rank=cfg.mamba_dt_rank, eps=cfg.rms_norm_eps)


# The row, and the file's compiled programs: the program and the reference
# (``hp`` a tuple of plain numbers), each traced once a (shapes,
# configuration).
MODEL = Model(
    jamba, ref, CFG, hyper, ("tok_emb", "norm_f"),
    stack=lambda tree, cfg: decoder.stack_layers(
        tree, decoder.run_stacks(cfg.layer_kinds), ("tok_emb", "norm_f"),
        jamba.GROUPS, jamba._GROUP_OF),
    batch=(2, 40),                  # 2.5 chunks of 16
    logits_relative=True, close=rel_l2_close, opt=OPT)
loss_and_grads, loss_of = MODEL.loss_and_grads, MODEL.loss_of
ref_loss, ref_loss_and_grads = MODEL.ref_loss, MODEL.ref_loss_and_grads


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("stacked,remat", [(False, False), (True, False),
                                           (True, True)])
def test_logits_loss_and_every_gradient_match_the_reference(stacked, remat):
    match_the_reference(MODEL, stacked, remat)


def test_bf16_program_stays_near_the_float32_reference():
    """bf16 weights and activations, float32 inside the scan. The distance
    is bf16's rounding of every activation (2**-9 a rounding) through five
    layers, so a per cent, not the float32 test's 1e-5; a scan that lost
    its float32 state would not be told from it here, nor by the cell's
    step check on the chip (PERF.md section 2): the kernel's own tests
    below hold it."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, remat=True,
                              loss_chunk=16)
    grads = bf16_near_the_reference(MODEL, cfg, MODEL.tokens(), limit=0.03)
    assert grads["decay0"]["A_log"].dtype == jnp.float32
    assert grads["run0"]["in_proj"].dtype == jnp.bfloat16


def test_a_doubled_micro_batch_shows():
    """What the cell's check compares: a batch that repeats a sequence is
    the reference's weighted loss, and no other weighting."""
    params = jamba.stacked_init_params(CFG, KEY)
    unique = jamba.fake_batch(CFG, 2, 32, seed=3)
    batch = unique[jnp.asarray([0, 1, 1, 1])]
    want = ref_loss(params, unique, hyper(CFG), jnp.asarray([0.25, 0.75]))
    assert float(loss_of(params, batch, CFG)) \
        == pytest.approx(float(want), rel=1e-5)
    even = ref_loss(params, unique, hyper(CFG))
    assert abs(float(even) - float(want)) > 1e-4


# -- the order of the layers -------------------------------------------------

def test_28_layers_run_in_the_period_rules_order():
    cfg = jamba.CONFIGS["2-3b"]
    kinds = cfg.layer_kinds
    assert [i for i, k in enumerate(kinds) if k == jamba.ATTENTION] == [7, 21]
    assert cfg.runs == ((jamba.MAMBA, 0, 7), (jamba.ATTENTION, 7, 1),
                        (jamba.MAMBA, 8, 13), (jamba.ATTENTION, 21, 1),
                        (jamba.MAMBA, 22, 6))
    one = dataclasses.replace(cfg, num_hidden_layers=14)
    assert [(k, n) for k, _, n in one.runs] == [
        (jamba.MAMBA, 7), (jamba.ATTENTION, 1), (jamba.MAMBA, 6)]
    assert (cfg.d_inner, cfg.head_dim) == (5120, 128)


def test_the_stacks_follow_the_runs():
    params = jamba.stacked_init_params(CFG, KEY)
    # A run's leaves by group: matrices, per-channel leaves, ``A_log``.
    assert sorted(params) == ["decay0", "decay2", "norm_f", "run0", "run1",
                              "run2", "tok_emb", "vec0", "vec1", "vec2"]
    assert params["run0"]["in_proj"].shape == (2, 64, 256)
    assert params["run1"]["wk"].shape == (1, 64, 16)       # one key/value head
    assert "in_proj" not in params["run1"]
    assert sorted(params["vec1"]) == ["ff_ln", "input_ln"]  # no q/k norm
    assert sorted(params["vec0"]) == [
        "D", "b_norm", "c_norm", "conv_b", "conv_w", "dt_bias", "dt_norm",
        "ff_ln", "input_ln"]
    assert all(v.ndim == 3 for v in params["run2"].values())
    assert list(params["decay2"]) == ["A_log"]
    assert params["decay2"]["A_log"].shape == (2, 128, 8)
    assert sorted(decoder.run_blocks(params, 2, jamba.GROUPS)) \
        == sorted(jamba.init_params(CFG, KEY)["l4"])
    layered = jamba.init_params(CFG, KEY)
    np.testing.assert_array_equal(np.asarray(params["run2"]["out_proj"][1]),
                                  np.asarray(layered["l4"]["out_proj"]))
    # Mamba-1's initialisation: A = -(1..N), D = 1, dt in [1e-3, 1e-1].
    np.testing.assert_allclose(np.asarray(jnp.exp(layered["l0"]["A_log"][5])),
                               np.arange(1, 9), rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(layered["l0"]["dt_bias"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001


def test_the_reference_refuses_weights_out_of_the_rules_order():
    params = jamba.stacked_init_params(CFG, KEY)
    swapped = {**params, "run0": params["run1"], "run1": params["run0"]}
    del swapped["decay0"]
    with pytest.raises(ValueError, match="period rule"):
        ref.hidden(swapped, jnp.zeros((8,), jnp.int32), hyper(CFG))


# -- gradient accumulation over walks of unequal shape -----------------------

def nbytes(tree):
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


def test_every_walk_accumulates_in_the_layer_loop_and_attention_is_kept():
    """Four micro batches: all three stacks' leaves are found by the sink
    (the embedding and the final norm are outside the blocks), the attention
    layer's flash forward is kept, and the scan's and the conv's forward run
    twice a Mamba layer (the walk and its recomputation)."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params = jamba.stacked_init_params(cfg, KEY)
    tx, step = MODEL.step_fn(cfg, 4)
    tokens = jamba.fake_batch(cfg, 4, 32)
    jax.make_jaxpr(step)(params, tx.init(params), tokens)
    gauge = lambda n: metrics().gauge(n).value          # noqa: E731
    stacks = {k: v for k, v in params.items()
              if k not in ("tok_emb", "norm_f")}
    assert gauge("ga_fused_bytes") == nbytes(stacks)
    assert gauge("ga_unfused_bytes") == nbytes(params) - nbytes(stacks)
    assert gauge("attn_kept_calls") == 1
    assert gauge("ssm_scan_calls") == 2 * 4            # 4 Mamba layers
    assert gauge("ssm_conv_calls") == 2 * 4
    assert gauge("ssm_boundary_bytes") == ssm.boundary_bytes(
        1, 32, cfg.d_inner, cfg.mamba_d_state, cfg.ssm_chunk) \
        == 2 * 8 * 128 * 4


@pytest.mark.parametrize("stacked", [False, True])
def test_two_steps_do_not_depend_on_the_accumulation_split(stacked):
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params = MODEL.init_params(cfg, stacked)
    tokens = jamba.fake_batch(cfg, 4, 32, seed=2)
    results = []
    for micro in (1, 4):
        tx, step = MODEL.ga_step(cfg, micro)
        state = (params, tx.init(params))
        for _ in range(2):
            loss, *state = step(*state, tokens)
        results.append((float(loss), state[0]))
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-5)
    leaves_close(results[1][1], results[0][1], 1e-4)


def test_the_accumulating_walks_give_the_tree_wide_adds_step():
    """The step that adds gradients inside the three walks against the one
    that differentiates each micro batch whole and adds the trees: the same
    parameters after two steps."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params = jamba.stacked_init_params(cfg, KEY)
    tokens = jamba.fake_batch(cfg, 4, 32, seed=5)
    ends = []
    for fused in (True, False):     # the stacked case's step of 4, and its twin
        tx, step = MODEL.ga_step(cfg, 4, fused=fused)
        state = (params, tx.init(params))
        for _ in range(2):
            _, *state = step(*state, tokens)
        ends.append(state[0])
    leaves_close(ends[0], ends[1], 1e-5)


# -- scopes, and the step on other devices -----------------------------------

def test_the_mixers_parts_carry_their_scopes():
    cfg = dataclasses.replace(CFG, remat=True)
    params = jamba.stacked_init_params(cfg, KEY)
    tokens = jamba.fake_batch(cfg, 1, 32)
    text = jax.jit(jamba.loss_fn, static_argnums=2).lower(
        params, tokens, cfg).as_text(debug_info=True)
    for scope in ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_out_proj",
                  "tepdist_ssm_fwd", "tepdist_conv_fwd"):
        assert scope in text, scope


def test_a_planned_step_binds_the_scan_kernels_for_four_devices(devices):
    """``plan_training`` over four virtual devices: the scan kernels, inside
    the accumulation scan and the layer walks, are bound replicated under
    ``shard_map`` by ``parallel/spmd_transform.py`` as every kernel is, and
    the step runs."""
    from tepdist_tpu.train import plan_training
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params = jamba.stacked_init_params(cfg, KEY)
    tokens = jamba.fake_batch(cfg, 4, 32)
    plan = plan_training(lambda p, t: jamba.loss_fn(p, t, cfg),
                         make_optimizer(OPT), params, tokens,
                         devices=devices[:4], explore=False,
                         num_micro_batches=2)
    want = float(jamba.loss_fn(params, tokens, cfg))
    assert plan.step(tokens) == pytest.approx(want, rel=1e-5)
    text = plan.compiled_step_text()
    assert len(re.findall(r"num_partitions=4", text)) >= 1
    assert np.isfinite(plan.step(tokens))


@pytest.fixture(scope="module")
def v5e_chip():
    import contextlib

    from jax.sharding import SingleDeviceSharding

    from tools.described_chip import described_v5e
    with contextlib.ExitStack() as stack:
        try:
            devices = stack.enter_context(described_v5e())
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
        yield SingleDeviceSharding(devices[0])


def test_a_narrow_jambas_step_compiles_for_a_described_v5e(v5e_chip,
                                                           monkeypatch):
    """Kernels not interpreted, at a narrow model's shapes (512 channels of
    16 states, 1024 tokens, attention of one key/value head under four): the
    compiled step holds the scan's forward kernel in the walk and in its
    recomputation, its backward kernel, the flash kernels, and its peak is
    read. The cell's own step is compiled in ``tests/test_tpu_compile.py``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        CFG, hidden_size=256, intermediate_size=512, num_attention_heads=2,
        mamba_d_state=16, mamba_dt_rank=16, dtype=jnp.bfloat16, remat=True,
        loss_chunk=512, ssm_chunk=64, ssm_block_d=256, flash_block_q=512,
        flash_block_k=512)
    tx, step = MODEL.step_fn(cfg, 2)
    params = jax.eval_shape(
        lambda: jamba.stacked_init_params(cfg, jax.random.PRNGKey(0)))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
        (params, jax.eval_shape(tx.init, params),
         jax.ShapeDtypeStruct((2, 1025), jnp.int32)))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    calls = [line.split(" = ", 1)[0].strip() for line in
             compiled.as_text().splitlines() if " custom-call(" in line]
    # Two Mamba walks, each: the walk's forward, the recomputation's, the
    # backward.
    assert sum("tepdist_ssm_fwd" in c for c in calls) == 4, calls
    assert sum("tepdist_ssm_bwd" in c for c in calls) == 2, calls
    assert sum("tepdist_conv_fwd" in c for c in calls) == 4, calls
    assert sum("tepdist_conv_bwd" in c for c in calls) == 2, calls
    assert sum("tepdist_flash_fwd" in c for c in calls) == 1, calls
    assert compiled.memory_analysis().peak_memory_in_bytes > 0
