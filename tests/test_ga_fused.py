"""Gradient accumulation inside the backward layer loop
(``models/layers.py:scan_blocks`` under ``parallel/sync_free.py:
build_ga_step``): where it engages the step is bit for bit the step of the
tree-wide ``acc + g`` (``build_ga_step`` without ``loss_fn``, the body it
keeps as its fallback), no micro batch builds a stacked gradient, and the
gauges ``ga_fused_bytes`` / ``ga_unfused_bytes`` say how the parameter bytes
split; everywhere else the step is the one it was.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from tepdist_tpu.models import gpt2, olmoe
from tepdist_tpu.parallel import sync_free
from tepdist_tpu.parallel.sync_free import build_ga_step, zero_pad_params
from tepdist_tpu.telemetry import metrics

MICRO = 4
GPT2 = dataclasses.replace(gpt2.CONFIGS["test"], n_layer=4, remat=True,
                           dtype=jnp.bfloat16)
OLMOE = dataclasses.replace(olmoe.CONFIGS["test"], remat=True)


def _bf16_matrices(params):
    """Weights in bf16, LayerNorm gains in float32, as the 1.5B cell's."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, params)


def _gpt2_stacked(cfg=GPT2, batch=8):
    params = _bf16_matrices(
        gpt2.stacked_init_params(cfg, jax.random.PRNGKey(0)))
    return (lambda p, t: gpt2.loss_fn_stacked(p, t, cfg), params,
            gpt2.fake_batch(cfg, batch, 32))


def _gpt2_unstacked():
    params = _bf16_matrices(gpt2.init_params(GPT2, jax.random.PRNGKey(0)))
    return (lambda p, t: gpt2.loss_fn(p, t, GPT2), params,
            gpt2.fake_batch(GPT2, 8, 32))


def _olmoe():
    params = olmoe.stacked_init_params(OLMOE, jax.random.PRNGKey(0), std=0.1)
    return (lambda p, t: olmoe.loss_fn(p, t, OLMOE), params,
            olmoe.fake_batch(OLMOE, 8, 16, seed=1))


def _own_scan():
    """A loss that scans its stacked layers itself, with no helper."""
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16)) * .3,
              "out": jax.random.normal(jax.random.PRNGKey(1), (16, 4))}

    def loss(p, x):
        h, _ = jax.lax.scan(
            jax.checkpoint(lambda h, w: (jnp.tanh(h @ w), None)), x, p["w"])
        return jnp.mean((h @ p["out"]) ** 2)

    return loss, params, jax.random.normal(jax.random.PRNGKey(2), (8, 16))


def _with(**changes):
    return lambda: _gpt2_stacked(dataclasses.replace(GPT2, **changes))


# name -> (model, build_ga_step's further arguments, fused?)
CASES = {
    "gpt2-stacked": (_gpt2_stacked, {}, True),
    "olmoe": (_olmoe, {}, True),
    "zero-on-four-devices": (lambda: _gpt2_stacked(batch=16),
                             {"zero_dp": 4}, True),
    "comm-bf16": (_gpt2_stacked, {"comm_dtype": "bfloat16"}, False),
    "comm-int8": (_gpt2_stacked, {"comm_dtype": "int8"}, False),
    "remat-dots": (_with(remat_policy="dots"), {}, False),
    "remat-save-attn": (_with(remat_policy="save_attn"), {}, True),
    "no-remat": (_with(remat=False), {}, False),
    "unstacked-loss": (_gpt2_unstacked, {}, False),
    "loss-without-the-helper": (_own_scan, {}, False),
    "one-micro-batch": (_gpt2_stacked, {"num_micro_batches": 1}, False),
}


def _steps(loss, params, num_micro_batches=MICRO, zero_dp=0, **kwargs):
    """(the step as ``plan_training`` builds it, the same without
    ``loss_fn``: the old body), jitted, and the optimizer's state."""
    opt = optax.adamw(1e-2)

    def grad_fn(p, *batch):
        return jax.value_and_grad(loss)(p, *batch)

    def apply_fn(p, s, g):
        updates, s = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s

    if not zero_dp:
        def build(**more):
            return jax.jit(build_ga_step(
                grad_fn, apply_fn, num_micro_batches, **kwargs, **more))
        return build(loss_fn=loss), build(), opt.init(params)

    # The explicit ZeRO-1 update: the accumulator is finished before the
    # reduce-scatter reads it.
    mesh = Mesh(np.array(jax.devices()[:zero_dp]), ("data",))
    state = opt.init(zero_pad_params(params, zero_dp))
    specs = jax.tree_util.tree_map(
        lambda v: P("data") if getattr(v, "ndim", 0) >= 1 else P(), state)

    def build(**more):
        inner = build_ga_step(grad_fn, apply_fn, num_micro_batches,
                              zero_dp=zero_dp, zero_axis_name="data",
                              **kwargs, **more)
        return jax.jit(jax.shard_map(
            inner, mesh=mesh, in_specs=(P(), specs, P("data")),
            out_specs=(P(), P(), specs), check_vma=False))
    return build(loss_fn=loss), build(), state


def _gauges():
    return (metrics().gauge("ga_fused_bytes").value,
            metrics().gauge("ga_unfused_bytes").value)


def _nbytes(tree):
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


def _whole_stack_adds(step, stacked_shapes, *args):
    """``add`` equations inside the accumulation scan's body (nested
    jaxprs included) whose two operands both have a stacked leaf's shape."""
    def sub_jaxprs(eqn):
        for v in eqn.params.values():
            for j in v if isinstance(v, (list, tuple)) else (v,):
                if hasattr(j, "eqns"):
                    yield j
                elif hasattr(j, "jaxpr") and hasattr(j.jaxpr, "eqns"):
                    yield j.jaxpr

    def adds(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "add" and all(
                    getattr(v.aval, "shape", None) in stacked_shapes
                    for v in eqn.invars):
                yield eqn
            for sub in sub_jaxprs(eqn):
                yield from adds(sub)

    def ga_scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan" \
                    and eqn.params["length"] == MICRO:
                yield eqn
            else:
                for sub in sub_jaxprs(eqn):
                    yield from ga_scans(sub)

    scans = list(ga_scans(jax.make_jaxpr(step)(*args).jaxpr))
    assert len(scans) == 1
    return list(adds(scans[0].params["jaxpr"].jaxpr))


@pytest.mark.parametrize("case", list(CASES))
def test_step_is_bitwise_the_tree_add_step(case):
    model, kwargs, fused = CASES[case]
    loss, params, batch = model()
    metrics().gauge("ga_fused_bytes").set(-1)
    step, old_step, state = _steps(loss, params, **kwargs)
    got = step(params, state, batch)
    fused_bytes, unfused_bytes = _gauges()
    want = old_step(params, state, batch)
    assert _gauges()[0] == 0          # the old body accumulates no leaf so
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(float(got[0]))

    if not fused:
        assert fused_bytes == 0
        accumulated = kwargs.get("num_micro_batches", MICRO) > 1
        assert unfused_bytes == (_nbytes(params) if accumulated else 0)
        return
    assert fused_bytes == _nbytes(params["blocks"])
    assert fused_bytes + unfused_bytes == _nbytes(params)
    if kwargs.get("zero_dp"):
        return
    stacked = {a.shape for a in jax.tree_util.tree_leaves(params["blocks"])}
    assert not _whole_stack_adds(step, stacked, params, state, batch)
    assert len(_whole_stack_adds(old_step, stacked, params, state, batch)) \
        == len(jax.tree_util.tree_leaves(params["blocks"]))


def test_no_stacked_gradient_among_the_steps_temporaries():
    """The compiled step's temporaries fall by most of one stacked gradient
    (0.86 read; the layer loop still holds one layer's): XLA updates the
    carried accumulator in place. At four layers: from eight on the CPU
    backend splits the update over threads and copies the carry for it
    (0.27 of a stack), which the TPU compiler does not (PERF.md, PR 27)."""
    loss, params, batch = _gpt2_stacked()
    step, old_step, state = _steps(loss, params)
    temps = [s.lower(params, state, batch).compile().memory_analysis()
             .temp_size_in_bytes for s in (step, old_step)]
    assert temps[1] - temps[0] >= 0.7 * _nbytes(params["blocks"]), temps


def test_walked_leaves_refuses_a_leaf_used_elsewhere():
    """A stacked leaf the loss also reads outside the walk has a second
    gradient contribution: that walk keeps the tree add."""
    loss, params, batch = _gpt2_stacked()
    n_blocks = len(jax.tree_util.tree_leaves(params["blocks"]))
    assert len(sync_free.walked_leaves(loss, params, batch[:2])) == n_blocks

    def tied(p, t):
        return loss(p, t) + 1e-3 * jnp.sum(
            p["blocks"]["mlp_fc_b"].astype(jnp.float32) ** 2)

    assert sync_free.walked_leaves(tied, params, batch[:2]) == ()
    step, old_step, state = _steps(tied, params)
    for a, b in zip(jax.tree_util.tree_leaves(step(params, state, batch)),
                    jax.tree_util.tree_leaves(
                        old_step(params, state, batch)), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert _gauges() == (0, _nbytes(params))


@pytest.mark.parametrize("remat_policy_env", ["", "full"])
def test_plan_training_hands_its_loss_to_the_step(remat_policy_env, caplog):
    """``plan_training`` with several micro batches: the fused step, bit for
    bit the one with the discovery forced off; a ``REMAT_POLICY`` wrap of
    the whole loss hides the walk and keeps the tree add."""
    from tepdist_tpu.core.service_env import ServiceEnv
    from tepdist_tpu.train import plan_training

    loss, params, batch = _gpt2_stacked()

    def run():
        plan = plan_training(
            loss, optax.adamw(1e-2), jax.tree_util.tree_map(jnp.array, params),
            batch, devices=jax.devices()[:1], explore=False,
            num_micro_batches=MICRO)
        return plan.step(batch), plan.variables(), _gauges()

    try:
        ServiceEnv.reset({"REMAT_POLICY": remat_policy_env, "OPT_LEVEL": "1"})
        with caplog.at_level("INFO", logger="tepdist_tpu.train"):
            got_loss, got, gauges = run()
        assert "the traced step" in caplog.text \
            and "ga_unfused_bytes=" in caplog.text
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(sync_free, "walked_leaves", lambda *a: ())
            want_loss, want, off = run()
    finally:
        ServiceEnv.reset()
    assert off == (0, _nbytes(params))
    assert gauges == (off if remat_policy_env
                      else (_nbytes(params["blocks"]),
                            _nbytes(params) - _nbytes(params["blocks"])))
    assert got_loss == want_loss
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
