"""Expert stacks read and summed where they lie: a walk through
``models/decoder.py:walk_layers`` that accumulates gradients hands the
grouped-matmul kernels the ``[L, E, K, N]`` stack with the layer's index
(``ops/pallas/grouped_matmul.py:ExpertStack``) and makes no copy of a
layer's experts. Against the same walk on slices: the step bit for bit, no
``[E, K, N]`` slice or update left in its program, and the gauge
``moe_stack_in_place_calls``. OLMoE (its own ``scan_blocks`` call) and sarvam
(its expert layer inside ``over_sequence``'s chunks) stay on slices."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from kernel_checks import equations

from tepdist_tpu.models import afmoe, layers, mellum, olmoe, sarvam_mla, zaya
from tepdist_tpu.optim import make_optimizer
from tepdist_tpu.parallel.sync_free import build_ga_step
from tepdist_tpu.telemetry import metrics

OPT = {"name": "adamw_bf16_router_bias", "learning_rate": 1e-3,
       "bias_rate": 0.001}
# model -> (module, its tiny configuration in bf16, expert layers)
MODELS = {
    "zaya": (zaya, zaya.CONFIGS["test-bf16"], 3),
    "afmoe": (afmoe, afmoe.CONFIGS["test"], 2),
    "mellum": (mellum, mellum.CONFIGS["test"], 3),
    "olmoe": (olmoe, olmoe.CONFIGS["test"], 0),
    "sarvam": (sarvam_mla, sarvam_mla.CONFIGS["test"], 0),
}
IN_PLACE = ("zaya", "afmoe", "mellum")


def _step(name, micro):
    """(the jitted step of ``micro`` micro batches, its arguments)."""
    model, cfg, _ = MODELS[name]
    cfg = dataclasses.replace(cfg, remat=True, dtype=jnp.bfloat16)
    tx = make_optimizer(dict(OPT))
    loss = lambda p, t: model.loss_fn(p, t, cfg)            # noqa: E731

    def apply_fn(p, s, g):
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    params = model.stacked_init_params(cfg, jax.random.PRNGKey(0))
    step = jax.jit(build_ga_step(
        lambda p, t: jax.value_and_grad(loss)(p, t), apply_fn, micro,
        loss_fn=loss))
    return step, (params, tx.init(params),
                  model.fake_batch(cfg, 2 * micro, 32, seed=9))


def _on_slices(monkeypatch):
    """Every walk as the parent made it: no leaf stays whole."""
    walk = layers._walk_accumulating
    monkeypatch.setattr(
        layers, "_walk_accumulating",
        lambda body, x, blocks, acc, kinds, in_place=():
        walk(body, x, blocks, acc, kinds))


def _expert_leaf_moves(step, args):
    """The slices out of and the updates into a stacked expert leaf in the
    lowered step: ``dynamic_slice`` results and ``dynamic_update_slice``
    updates of one layer ``[1, E, K, N]`` (a kernel's interpreted blocks
    are ``[1, 1, K, n]`` at most)."""
    layer = {"x".join(map(str, (1,) + a.shape[1:]))
             for k, a in args[0]["blocks"].items()
             if k in ("w_gate", "w_up", "w_down")}
    assert all(one.count("x") == 3 for one in layer), layer
    found = []
    for line in step.lower(*args).as_text().splitlines():
        if "stablehlo.dynamic_slice" in line and any(
                f"-> tensor<{one}x" in line for one in layer):
            found.append("dynamic_slice")
        if "stablehlo.dynamic_update_slice" in line and any(
                f">, tensor<{one}x" in line for one in layer):
            found.append("dynamic_update_slice")
    return found


def _stack_calls(step, args):
    """The grouped-matmul kernel calls of the step's program by the rank of
    their weight operand or result: (over a stack, over a slice)."""
    ranks = [max(v.aval.ndim for v in (*e.invars, *e.outvars))
             for e in equations(step, *args)
             if e.primitive.name == "pallas_call"
             and e.params["name"].startswith("tepdist_gmm_")]
    assert set(ranks) <= {3, 4}
    return ranks.count(4), ranks.count(3)


@pytest.mark.parametrize("micro", [2, 3])
@pytest.mark.parametrize("name", IN_PLACE)
def test_the_step_is_the_sliced_walks_bit_for_bit(name, micro, monkeypatch):
    """Loss, every parameter and the optimizer's state after one step of
    2 and of 3 micro batches: the walk that hands the kernels the stack
    against the walk that hands them slices."""
    step, args = _step(name, micro)
    got = step(*args)
    assert metrics().gauge("moe_stack_in_place_calls").value \
        == 12 * MODELS[name][2]
    _on_slices(monkeypatch)
    step, args = _step(name, micro)
    want = step(*args)
    assert metrics().gauge("moe_stack_in_place_calls").value == 0
    got, want = (jax.tree_util.tree_leaves_with_path(t) for t in (got, want))
    assert len(got) == len(want)
    for (path, a), (_, b) in zip(got, want):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("name", IN_PLACE)
def test_no_layers_experts_are_sliced_out_or_updated_in(name, monkeypatch):
    """The step's program holds no ``dynamic_slice`` whose result and no
    ``dynamic_update_slice`` whose update is one layer of an expert leaf,
    and every grouped-matmul call in it takes a stack; on slices it holds
    nine and three a trace of the walk's body and more."""
    step, args = _step(name, 2)
    assert _expert_leaf_moves(step, args) == []
    over_stack, over_slice = _stack_calls(step, args)
    assert over_stack and not over_slice
    _on_slices(monkeypatch)
    step, args = _step(name, 2)
    moves = _expert_leaf_moves(step, args)
    assert moves.count("dynamic_slice") >= 9 \
        and moves.count("dynamic_update_slice") >= 3, moves
    assert _stack_calls(step, args)[0] == 0


def test_the_gauge_counts_every_grouped_matmul_call_of_the_zaya_walk():
    """One trace of the walk's body stands for its three layers: the
    kernels' calls in the step's program (forward loop, recomputation, the
    two gradients) times the layers are what the gauge reads."""
    step, args = _step("zaya", 2)
    over_stack, over_slice = _stack_calls(step, args)
    assert (over_stack, over_slice) == (12, 0)
    assert metrics().gauge("moe_stack_in_place_calls").value == 3 * 12


@pytest.mark.parametrize("name", ["olmoe", "sarvam"])
def test_the_walks_that_stay_on_slices_hold_no_stack_form(name):
    """OLMoE calls ``scan_blocks`` itself and sarvam's expert layer runs
    inside ``over_sequence``'s chunks (an accumulator handed back as a
    cotangent would be summed once a chunk): no kernel of their steps takes
    a stack and the gauge reads 0."""
    step, args = _step(name, 2)
    over_stack, over_slice = _stack_calls(step, args)
    assert over_stack == 0 and over_slice > 0
    assert metrics().gauge("moe_stack_in_place_calls").value == 0
