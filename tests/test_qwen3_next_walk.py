"""Qwen3-Next's walks: the two runs of two kinds through ``scan_blocks``
by the written-out backward of an accumulating step equal to the ``l{i}``
Python loop, the expert leaves an ``ExpertStack`` in each run, what
the gauges of a traced step say (three delta-rule forward calls a micro batch
at the published period: one a Gated-DeltaNet layer), the scopes, and two
steps through ``plan_training`` against a plain ``jax.grad`` and optimizer
loop."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from kernel_checks import kernel_counts
from model_checks import tree_close, two_planned_steps
from test_qwen3_next import CFG, MODEL, OUTSIDE, init_params

from tepdist_tpu.models import decoder
from tepdist_tpu.models import qwen3_next as qwen
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
            tree, decoder.run_stacks(cfg.kinds), qwen.GROUPS)):
        out[f"l{i}"] = blk
    return out


def _one_step(cfg, micro, stacked, tokens):
    params = jax.tree_util.tree_map(jnp.copy, init_params(cfg, stacked))
    tx, step = MODEL.ga_step(cfg, micro)    # one a (configuration, micro)
    loss, new, _ = step(params, tx.init(params), tokens)
    return loss, new


def test_the_stacked_accumulating_walk_is_the_layer_loop(monkeypatch):
    """One optimizer step over the two runs, 2 micro batches accumulated
    (the written-out backward, the expert leaves an ``ExpertStack`` in each
    run), against the ``l{i}`` loop's plain step. (Without accumulation the
    two layouts are each held to the reference: ``test_qwen3_next.py``.)"""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    tokens = qwen.fake_batch(cfg, 4, 32, seed=9)
    loss_l, loop = _one_step(cfg, 1, False, tokens)
    handed = []
    moe = qwen.moe

    def watched(blk, h, c):
        handed.append(tuple(type(blk[k]) for k in decoder.EXPERT_LEAVES))
        return moe(blk, h, c)

    monkeypatch.setattr(qwen, "moe", watched)
    loss_s, stack = _one_step(cfg, 2, True, tokens)
    assert float(loss_s) == pytest.approx(float(loss_l), rel=2e-6)
    # Adam's first step is sign-like: where a gradient is next to nothing
    # the order of the sums shows in the update.
    tree_close(_unstacked(stack, cfg), loop, 5e-4)
    stacks = [kinds for kinds in handed if kinds == (ExpertStack,) * 3]
    assert len(stacks) >= 2, handed
    assert metrics().gauge("moe_stack_in_place_calls").value == 4 * 12


def test_the_gauges_of_a_traced_step():
    """Two micro batches, four layers in two walks: the delta rule's
    forward runs once a Gated-DeltaNet layer and micro batch and the flash
    forward once (the walks keep ``(o, states, inv)`` and ``(o, lse)``),
    the one conv once a run of the mixer."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params = init_params(cfg, stacked=True)
    tokens = qwen.fake_batch(cfg, 4, 32, seed=8)
    tx, step = MODEL.step_fn(cfg, 2)
    found = kernel_counts(step, params, tx.init(params), tokens)
    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    assert gauge("gdn_calls") == 3
    assert gauge("attn_kept_calls") == 1 + 3
    # A micro batch of 2 x 32 tokens in float32: a Gated-DeltaNet layer's o
    # [B, T, 4 x 32], its states [B, 2 chunks, 4, 32, 32] and inverses [B,
    # 2, 4, 16, 16]; the attention layer's o [B, 8, T, 16] and lse [B, 8, T].
    assert gauge("attn_kept_bytes") == 3 * 2 * 4 * (
        32 * 4 * 32 + 2 * 4 * (32 * 32 + 16 * 16)) + 2 * 8 * 32 * 4 * (16 + 1)
    assert gauge("ssm_conv_calls") == 3 * 2
    assert gauge("gdn_state_bytes") == 2 * 4 * 32 * 32 * 4
    assert gauge("attn_rotary_dim") == 4
    assert gauge("moe_rows_sum_calls") == 2 * 4
    assert gauge("kda_calls") == 0
    assert found["tepdist_gdn_fwd"] == found["tepdist_gdn_bwd"] == 1
    names = "".join(found)
    for kernel in ("tepdist_conv_fwd", "tepdist_conv_bwd",
                   "tepdist_flash_fwd", "tepdist_flash_dkv", "tepdist_gmm_"):
        assert kernel in names, (kernel, sorted(found))
    stacks = sum(a.nbytes for r in range(2)
                 for a in jax.tree_util.tree_leaves(params[f"run{r}"]))
    assert gauge("ga_fused_bytes") == stacks


def test_the_layers_parts_carry_their_scopes():
    cfg = dataclasses.replace(CFG, remat=True)
    params = init_params(cfg, stacked=True)
    tokens = qwen.fake_batch(cfg, 1, 32)
    text = jax.jit(qwen.loss_fn, static_argnums=2).lower(
        params, tokens, cfg).as_text(debug_info=True)
    for scope in ("gdn_in", "gdn_conv", "gdn_gates", "gdn_core", "gdn_out",
                  "attn_qkv", "attn_rope", "attn_core", "attn_gate",
                  "attn_out", "moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine", "moe_shared", "part_mixer", "part_moe",
                  "tepdist_gdn_fwd", "tepdist_flash_fwd", "rope_plain"):
        assert scope in text, scope
    assert "part_mlp" not in text


def test_two_planned_steps_are_a_plain_grad_and_optimizer_loop(devices):
    """``plan_training`` with 2 micro batches accumulated in one program
    against ``jax.grad`` of the whole batch and the optimizer by hand: the
    same losses, the same parameters."""
    cfg = MODEL.variant(True)
    # The plain loop runs over the ``l{i}`` dicts (its step compiled for the
    # walk's test above already).
    got, p = two_planned_steps(MODEL, True, devices, plain_stacked=False)
    got = _unstacked(got, cfg)
    # A zero-centred norm leaf is all update after two steps (|w| = 2e-3):
    # where Adam's sign-like step meets a gradient next to nothing, the order
    # of the accumulation's sums is the leaf's third digit.
    tree_close(got, p, 2e-3)
