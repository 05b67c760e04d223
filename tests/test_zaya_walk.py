"""ZAYA's walk: the pair ``(x, r)`` through ``scan_blocks`` by the
checkpointed scan and by the written-out backward equal to the ``l{i}`` Python
loop, what the walk keeps and the gauges say, and two steps through
``plan_training`` against a plain ``jax.grad`` and optimizer loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from kernel_checks import kernel_counts
from model_checks import KEY, tree_close, two_planned_steps
from test_zaya import MODEL, WIDE

from benchmark.reference import zaya as ref
from tepdist_tpu.models import zaya
from tepdist_tpu.ops.grouped_matmul import layout_rows
from tepdist_tpu.telemetry import metrics


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("micro", [1, 2], ids=["plain-scan", "accumulating"])
def test_the_stacked_walk_is_the_python_loop(micro):
    """Forward and gradients of the pair ``(x, r)`` through
    ``scan_blocks``, by the checkpointed scan (one micro batch) and by the
    written-out backward that carries ``(dx, dr)`` (two): one optimizer step
    from the same weights lands where the ``l{i}`` loop's does."""
    cfg = MODEL.variant(True)
    tokens = zaya.fake_batch(cfg, 4, 32, seed=9)
    tx, step = MODEL.ga_step(cfg, micro)
    (loss_loop, p_loop, _), (loss_stack, p_stack, _) = (
        step(params, tx.init(params), tokens)
        for params in map(MODEL.uneven_params, (False, True)))
    assert float(loss_stack) == pytest.approx(float(loss_loop), rel=2e-6)
    tree_close(p_stack, MODEL.stack(p_loop, cfg), 1e-4, skip=())


def test_a_walk_keeps_one_forward_a_layer_and_the_gauges_say_so():
    """Two micro batches of 2 x 32 tokens, three layers in one walk: the
    flash forward runs once a layer and micro batch (``attn_kept_calls`` 3),
    the mixing twice (a walked block recomputes it: ``cca_mix_calls`` 6), and
    the noted gauges hold one layer's latents, the router's carry and the
    layout's rows."""
    cfg = WIDE
    params = zaya.stacked_init_params(cfg, KEY)
    tokens = zaya.fake_batch(cfg, 4, 32, seed=8)
    tx, step = MODEL.step_fn(cfg, 2)
    found = kernel_counts(step, params, tx.init(params), tokens)
    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    assert gauge("cca_mix_calls") == 6 and gauge("attn_kept_calls") == 3
    assert gauge("attn_kept_bytes") == 3 * 2 * 8 * 32 * (128 * 4 + 4)
    assert gauge("cca_latent_bytes") == 2 * 32 * (8 + 2 + 2) * 128 * 4
    assert gauge("router_carry_bytes") == 2 * 32 * 16 * 4
    assert gauge("moe_top1_rows") == 64 + 16 * 8 \
        == layout_rows(64, 1, 16, 16, 8)[0]
    assert gauge("moe_rows_sum_calls") == 0     # every expert is resident
    stack = sum(a.nbytes for a in jax.tree_util.tree_leaves(params["blocks"]))
    assert gauge("ga_fused_bytes") == stack
    # One trace of the walk's body: the mixing's forward stands in the
    # forward loop and in the backward loop's recomputation, its backward
    # once; the flash forward in the forward loop alone.
    assert found["tepdist_cca_mix_fwd"] == 2 \
        and found["tepdist_cca_mix_bwd"] == 1
    flash = {name.split("__")[0]: n for name, n in found.items()
             if name.startswith("tepdist_flash_")}
    assert flash == {"tepdist_flash_fwd": 1, "tepdist_flash_dkv": 1}, found
    assert [name for name in found if "__h8" in name and "__kv2" in name]


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["unstacked", "stacked"])
def test_two_planned_steps_are_a_plain_grad_and_optimizer_loop(stacked,
                                                               devices):
    """``plan_training`` with 2 micro batches accumulated in one program
    against ``jax.grad`` of the whole batch and the optimizer by hand: the
    same losses, the same parameters, the selection bias moved by the
    reference's update of each step's counts."""
    biases = lambda p: p["blocks"]["router_bias"] if stacked \
        else jnp.stack([p[f"l{i}"]["router_bias"] for i in range(3)])  # noqa: E731,E501
    bias = [np.asarray(biases(MODEL.uneven_params(stacked)))]

    def the_references_update(p, tokens, cfg):
        counts = MODEL.ref_expert_counts(MODEL.to_reference(p), tokens,
                                         MODEL.hyper(cfg))
        bias[0] = ref.bias_update(bias[0], counts, MODEL.opt["bias_rate"])

    got, p = two_planned_steps(MODEL, stacked, devices, uneven=True,
                               each=the_references_update)
    # Adam's first steps are sign-like: where a gradient is next to nothing
    # the order of the accumulation's sums shows in the update.
    tree_close(got, p, 1e-4, skip=())
    np.testing.assert_allclose(np.asarray(biases(got)), np.asarray(bias[0]),
                               rtol=0, atol=1e-7)
