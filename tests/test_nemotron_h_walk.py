"""Nemotron-H's walks: the three runs of units (``ME ME | M*E | ME``) through
``scan_blocks`` by the written-out backward of an accumulating step equal to
the ``l{i}`` Python loop over the nine layers, the expert leaves an
``ExpertStack`` in each run (two stacks an expert layer: no gate matrix),
what the gauges of a traced step say (eight state-space forward calls a micro
batch: a Mamba-2 layer's forward and its recomputation), the scopes, and two
steps through ``plan_training`` against a plain ``jax.grad`` and optimizer
loop."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from kernel_checks import kernel_counts
from model_checks import tree_close, two_planned_steps
from test_nemotron_h import CFG, MODEL, OUTSIDE

from tepdist_tpu.models import decoder
from tepdist_tpu.models import nemotron_h as nemo
from tepdist_tpu.ops.pallas.grouped_matmul import ExpertStack
from tepdist_tpu.telemetry import metrics


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _in_units(tree, cfg):
    """A stacked tree as ``l{i}`` dicts, a unit each."""
    out = {k: tree[k] for k in OUTSIDE}
    for i, blk in enumerate(decoder.layer_dicts(
            tree, decoder.run_stacks(cfg.units), nemo.GROUPS)):
        out[f"l{i}"] = blk
    return out


def _one_step(cfg, micro, stacked, tokens):
    params = jax.tree_util.tree_map(jnp.copy,
                                    MODEL.init_params(cfg, stacked))
    tx, step = MODEL.ga_step(cfg, micro)    # one a (configuration, micro)
    loss, new, _ = step(params, tx.init(params), tokens)
    return loss, new


def test_the_stacked_accumulating_walk_is_the_layer_loop(monkeypatch):
    """One optimizer step over the three runs of units, 2 micro batches
    accumulated (the written-out backward, the expert leaves an
    ``ExpertStack`` in each run), against the ``l{i}`` loop's plain step
    over the nine layers one by one. (Without accumulation the two layouts
    are each held to the reference: ``test_nemotron_h.py``.)"""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    tokens = nemo.fake_batch(cfg, 4, 32, seed=9)
    loss_l, loop = _one_step(cfg, 1, False, tokens)
    assert "l8" in loop and "w_xbc" not in loop["l1"]   # a layer a dict
    handed = []
    moe = nemo.moe

    def watched(blk, h, c):
        handed.append(tuple(type(blk[k]) for k in nemo.EXPERT_LEAVES))
        return moe(blk, h, c)

    monkeypatch.setitem(nemo._PARTS, nemo.EXPERTS, ("moe", "moe_ln", watched))
    loss_s, stack = _one_step(cfg, 2, True, tokens)
    assert float(loss_s) == pytest.approx(float(loss_l), rel=2e-6)
    # Adam's first step is sign-like: where a gradient is next to nothing
    # the order of the sums shows in the update.
    tree_close(_in_units(stack, cfg), nemo.in_units(loop, cfg), 5e-4)
    # The selection bias took the sign update of the step's counts, the
    # same in either layout (``tree_close`` leaves it out).
    for n in range(4):
        bias = _in_units(stack, cfg)[f"l{n}"]["router_bias"]
        assert float(jnp.abs(bias).max()) == pytest.approx(1e-3, rel=0.2)
        assert bool(jnp.array_equal(
            bias, nemo.in_units(loop, cfg)[f"l{n}"]["router_bias"]))
    stacks = [kinds for kinds in handed if kinds == (ExpertStack,) * 2]
    assert len(stacks) >= 3, handed
    # Two matmuls an expert, each forward, recomputed, its input's and its
    # weight's gradient, in four expert layers.
    assert metrics().gauge("moe_stack_in_place_calls").value == 4 * 8
    # One epilogue a layer: the up projection's activation in the walk's
    # first forward; an expert of two matrices has no second input gradient.
    assert metrics().gauge("moe_epilogue_calls").value == 4


def test_the_gauges_of_a_traced_step():
    """Two micro batches, nine layers in three walks of units: the
    state-space forward runs in a unit's forward and again in its
    recomputation (the walk keeps no state-space forward), the flash forward
    once (the walk keeps ``(o, lse)``), the one conv with the rule."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params = MODEL.init_params(cfg, True)
    tokens = nemo.fake_batch(cfg, 4, 32, seed=8)
    tx, step = MODEL.step_fn(cfg, 2)
    found = kernel_counts(step, params, tx.init(params), tokens)
    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    assert gauge("ssd_calls") == 4 * 2
    assert gauge("ssm_conv_calls") == 4 * 2
    assert gauge("attn_kept_calls") == 1
    # A micro batch of 2 x 32 tokens in float32: the attention layer's o
    # [B, 16, T, 8] and lse [B, 16, T].
    assert gauge("attn_kept_bytes") == 2 * 16 * 32 * 4 * (8 + 1)
    assert gauge("ssd_state_bytes") == 2 * 4 * 16 * 16 * 4
    assert gauge("moe_rows_sum_calls") == 2 * 4
    assert gauge("rope_calls") == 0             # no positional embedding
    # A walk a run of units: forward, recomputed forward and backward.
    assert found["tepdist_ssd_fwd__g2"] == 2 * 3
    assert found["tepdist_ssd_bwd__g2"] == 3
    names = "".join(found)
    for kernel in ("tepdist_conv_fwd", "tepdist_conv_bwd",
                   "tepdist_flash_fwd", "tepdist_flash_dkv", "tepdist_gmm_"):
        assert kernel in names, (kernel, sorted(found))
    stacks = sum(a.nbytes for r in range(3)
                 for a in jax.tree_util.tree_leaves(params[f"run{r}"]))
    assert gauge("ga_fused_bytes") == stacks


def test_the_layers_parts_carry_their_scopes():
    cfg = dataclasses.replace(CFG, remat=True)
    params = MODEL.init_params(cfg, True)
    tokens = nemo.fake_batch(cfg, 1, 32)
    text = jax.jit(nemo.loss_fn, static_argnums=2).lower(
        params, tokens, cfg).as_text(debug_info=True)
    for scope in ("ssd_in", "ssd_conv", "ssd_rule", "ssd_norm_out",
                  "attn_qkv", "attn_core", "attn_out", "moe_router",
                  "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
                  "part_mixer", "part_moe", "tepdist_ssd_fwd",
                  "tepdist_flash_fwd"):
        assert scope in text, scope
    assert "part_mlp" not in text and "attn_rope" not in text


def test_two_planned_steps_are_a_plain_grad_and_optimizer_loop(devices):
    """``plan_training`` with 2 micro batches accumulated in one program
    against ``jax.grad`` of the whole batch and the optimizer by hand: the
    same losses, the same parameters."""
    cfg = MODEL.variant(True)
    # The plain loop runs over the ``l{i}`` dicts (its step compiled for the
    # walk's test above already).
    got, p = two_planned_steps(MODEL, True, devices, plain_stacked=False)
    # Where Adam's sign-like step meets a gradient next to nothing, the
    # order of the accumulation's sums is the leaf's third digit.
    tree_close(_in_units(got, cfg), nemo.in_units(p, cfg), 2e-3)
