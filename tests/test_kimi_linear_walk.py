"""Kimi Linear's walks: the four runs of unequal shape through
``scan_blocks`` by the checkpointed scan and by the written-out backward
equal to the ``l{i}`` Python loop, the expert leaves an ``ExpertStack`` in
each expert run, what the gauges of a traced step say, the scopes, and two
steps through ``plan_training`` against a plain ``jax.grad`` and optimizer
loop."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from kernel_checks import kernel_counts
from model_checks import tree_close, two_planned_steps
from test_kimi_linear import (
    CFG,
    MODEL,
    OUTSIDE,
    biases,
    hyper,
    init_params,
    ref_expert_counts,
    to_reference,
)

from benchmark.reference import kimi_linear as ref
from tepdist_tpu.models import afmoe, decoder, sarvam_mla
from tepdist_tpu.models import kimi_linear as kimi
from tepdist_tpu.ops.pallas.grouped_matmul import ExpertStack
from tepdist_tpu.telemetry import metrics


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _unstacked(tree, cfg):
    """A stacked tree as ``l{i}`` dicts."""
    out = {k: tree[k] for k in OUTSIDE}
    for i, blk in enumerate(decoder.layer_dicts(
            tree, decoder.run_stacks(cfg.kinds), kimi.GROUPS)):
        out[f"l{i}"] = blk
    return out


def _one_step(cfg, micro, stacked, tokens):
    """One step of the row's jitted step of ``micro`` micro batches (the
    planned steps' plain loop below is the stacked walk's one-micro-batch
    step, compiled once)."""
    params = jax.tree_util.tree_map(jnp.copy, init_params(cfg, stacked))
    tx, step = MODEL.ga_step(cfg, micro)
    loss, new, _ = step(params, tx.init(params), tokens)
    return loss, new


_LOOP = {}


def _loop_step(cfg, tokens):
    """The ``l{i}`` loop's step (one micro batch), made once."""
    if "step" not in _LOOP:
        _LOOP["step"] = _one_step(cfg, 1, False, tokens)
    return _LOOP["step"]


@pytest.mark.parametrize("micro", [1, 2], ids=["plain", "accumulating"])
def test_the_stacked_walk_is_the_layer_loop(micro, monkeypatch):
    """One optimizer step over the four runs against the ``l{i}`` loop's,
    without accumulation (the plain checkpointed scan) and with (the
    written-out backward, the expert leaves an ``ExpertStack`` in each of
    the three expert runs)."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    tokens = kimi.fake_batch(cfg, 4, 32, seed=9)
    loss_l, loop = _loop_step(cfg, tokens)
    handed = []
    moe = afmoe.moe

    def watched(blk, h, c):
        handed.append(tuple(type(blk[k]) for k in decoder.EXPERT_LEAVES))
        return moe(blk, h, c)

    monkeypatch.setattr(kimi, "moe", watched)
    monkeypatch.setattr(sarvam_mla, "moe", watched)
    loss_s, stack = _one_step(cfg, micro, True, tokens)
    assert float(loss_s) == pytest.approx(float(loss_l), rel=2e-6)
    # Adam's first step is sign-like: where a gradient is next to nothing
    # the order of the sums shows in the update.
    tree_close(_unstacked(stack, cfg), loop, 5e-4, skip=())
    stacks = [kinds for kinds in handed if kinds == (ExpertStack,) * 3]
    if micro == 2:
        # Each of the three expert runs' bodies met stacks under the
        # written-out backward.
        assert len(stacks) >= 3, handed
        assert metrics().gauge("moe_stack_in_place_calls").value == 4 * 12
        # The activation in the walk's first forward, the addend in the
        # second input gradient: two a gated expert layer.
        assert metrics().gauge("moe_epilogue_calls").value == 4 * 2
    else:
        assert not stacks


def test_the_gauges_of_a_traced_step():
    """Two micro batches, five layers in four walks: the delta-rule forward
    runs once a KDA layer and micro batch and the latent layer's once (the
    walks keep both: ``(o, states, inv)`` and ``(o, lse)``), the convs
    three times a run of the mixer, and a walked block makes its mixer's
    other parts again."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params = init_params(cfg, stacked=True)
    tokens = kimi.fake_batch(cfg, 4, 32, seed=8)
    tx, step = MODEL.step_fn(cfg, 2)
    found = kernel_counts(step, params, tx.init(params), tokens)
    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    assert gauge("kda_calls") == 4
    assert gauge("mla_fwd_calls") == 1 and gauge("attn_kept_calls") == 1 + 4
    # A micro batch of 2 x 32 tokens in float32: a KDA layer's o [B, T, 4 x
    # 32], its states [B, 2 chunks, 4, 32, 32] and inverses [B, 2, 4, 16,
    # 16]; the latent layer's o [B, 2, T, 12] and lse [B, 2, T].
    assert gauge("attn_kept_bytes") == 4 * 2 * 4 * (
        32 * 4 * 32 + 2 * 4 * (32 * 32 + 16 * 16)) + 2 * 2 * 32 * 4 * (12 + 1)
    assert gauge("mla_bwd_calls") == 1
    assert gauge("ssm_conv_calls") == 4 * 3 * 2
    assert gauge("kda_state_bytes") == 2 * 4 * 32 * 32 * 4
    assert gauge("kda_decay_bytes") == 2 * 32 * 4 * 32 * 4
    assert gauge("mla_heads_held") == 2
    assert gauge("mla_latent_bytes") == 2 * 32 * (24 + 8) * 4
    assert gauge("moe_rows_sum_calls") == 2 * 4
    names = "".join(found)
    assert "tepdist_kda_bwd_states" not in names    # the states are kept
    assert found["tepdist_kda_fwd"] == found["tepdist_kda_bwd"] == 3
    for kernel in ("tepdist_kda_fwd", "tepdist_kda_bwd",
                   "tepdist_conv_fwd", "tepdist_conv_bwd",
                   "tepdist_mla_fwd", "tepdist_mla_dkv", "tepdist_gmm_"):
        assert kernel in names, (kernel, sorted(found))
    stacks = sum(a.nbytes for r in range(4)
                 for a in jax.tree_util.tree_leaves(params[f"run{r}"]))
    assert gauge("ga_fused_bytes") == stacks


def test_a_kda_block_rematerialised_whole_keeps_nothing(monkeypatch):
    """``tests/test_attn_kept.py``'s declining case for the delta rule: a
    KDA block under ``rematerialised_whole`` (a recipe that pins the
    rematerialisation of all of it) hands nothing to its walk, so the
    forward kernel is in each of the three walks' recomputation again and
    the latent layer's pair is all that is kept."""
    from tepdist_tpu.models.layers import rematerialised_whole
    monkeypatch.setattr(kimi, "kda_block",
                        rematerialised_whole(kimi.kda_block))
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params = init_params(cfg, stacked=True)
    tx, step = MODEL.step_fn(cfg, 2)
    found = kernel_counts(step, params, tx.init(params),
                          kimi.fake_batch(cfg, 4, 32, seed=8))
    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    assert gauge("kda_calls") == 8
    assert gauge("attn_kept_calls") == 1
    assert gauge("attn_kept_bytes") == 2 * 2 * 32 * 4 * (12 + 1)
    assert found["tepdist_kda_fwd"] == 6 and found["tepdist_kda_bwd"] == 3
    assert "tepdist_kda_bwd_states" not in found


def test_the_mixers_parts_carry_their_scopes():
    cfg = dataclasses.replace(CFG, remat=True)
    params = init_params(cfg, stacked=True)
    tokens = kimi.fake_batch(cfg, 1, 32)
    text = jax.jit(kimi.loss_fn, static_argnums=2).lower(
        params, tokens, cfg).as_text(debug_info=True)
    for scope in ("kda_in", "kda_conv", "kda_gates", "kda_core", "kda_out",
                  "kda_out_mlp", "mla_q", "mla_kv_down", "mla_kv_up",
                  "mla_out", "moe_router", "moe_shared", "part_mixer",
                  "part_mlp", "part_moe", "tepdist_kda_fwd",
                  "tepdist_mla_fwd"):
        assert scope in text, scope
    assert "rope_yarn" not in text and "rope_plain" not in text


@pytest.mark.parametrize("stacked", [True], ids=["stacked"])
def test_two_planned_steps_are_a_plain_grad_and_optimizer_loop(stacked,
                                                               devices):
    """``plan_training`` with 2 micro batches accumulated in one program
    against ``jax.grad`` of the whole batch and the optimizer by hand: the
    same losses, the same parameters, the selection bias moved by the
    reference's update of each step's counts."""
    bias = [0.0]

    def the_references_update(p, tokens, cfg):
        counts = ref_expert_counts(to_reference(p, cfg), tokens, hyper(cfg))
        bias[0] = ref.bias_update(bias[0], counts, MODEL.opt["bias_rate"])

    # The plain loop is the stacked walk's one-micro-batch step.
    got, p = two_planned_steps(MODEL, stacked, devices,
                               each=the_references_update)
    bias = bias[0]
    # Adam's first steps are sign-like: where a gradient is next to nothing
    # the order of the accumulation's sums shows in the update.
    tree_close(got, p, 5e-4, skip=())
    np.testing.assert_allclose(np.asarray(biases(got, stacked)),
                               np.asarray(bias), atol=1e-9)
    assert np.abs(np.asarray(bias)).max() > 0
