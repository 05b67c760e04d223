"""``telemetry/traced.py``: the group of gauges that are set while a step is
traced. A walk says how many layers one trace stands for, a count inside
counts once a layer, who builds a step zeroes the whole group and who plans
one reports it, neither naming a gauge."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tepdist_tpu.models.layers import scan_blocks
from tepdist_tpu.parallel.sync_free import build_ga_step
from tepdist_tpu.telemetry import metrics, traced


@pytest.fixture()
def group():
    """The group as the imports left it, given back after the test."""
    before = dict(traced.GROUP)
    yield traced.GROUP
    traced.GROUP.clear()
    traced.GROUP.update(before)


def test_a_count_inside_a_walk_counts_once_a_layer(group):
    traced.note("t_calls", 0)
    traced.count("t_calls")
    with traced.stands_for(13):
        assert traced.stood_for() == 13
        traced.count("t_calls")
        traced.count("t_calls", 2)
    assert traced.stood_for() == 1
    assert traced.values()["t_calls"] == 1 + 13 + 26
    assert metrics().gauge("t_calls").value == 40       # a gauge of metrics()


def test_nested_walks_multiply(group):
    traced.note("t_nested", 0)
    with traced.stands_for(3):
        with traced.stands_for(4):
            traced.count("t_nested")
            # What a custom_vjp read where it was called, handed to a rule
            # that is traced elsewhere: not multiplied again.
            traced.count("t_nested", layers=traced.stood_for())
        assert traced.stood_for() == 3
    assert traced.values()["t_nested"] == 24


def test_reset_zeroes_the_group_and_nothing_else(group):
    metrics().gauge("t_outside").set(7)
    traced.count("t_inside", 5)
    traced.note("t_noted", 2.5)
    traced.reset()
    values = traced.values()
    assert values["t_inside"] == values["t_noted"] == 0
    assert metrics().gauge("t_inside").value == 0       # 0, not None
    assert metrics().gauge("t_outside").value == 7
    assert "t_outside" not in values


def test_values_keep_the_order_the_names_joined_in(group):
    traced.declare("t_b", "declared first")
    traced.count("t_a")
    traced.note("t_c", 1)
    traced.declare("t_b", "declared again: its place stays")
    assert [n for n in traced.values() if n.startswith("t_")] \
        == ["t_b", "t_a", "t_c"]
    assert traced.values()["t_b"] == 0                  # never set


def test_every_declared_gauge_says_what_it_counts():
    import tepdist_tpu.models.minicpm_sala  # noqa: F401 (its gauges join)
    import tepdist_tpu.models.jamba  # noqa: F401
    for name in ("attn_kept_calls", "attn_kept_bytes", "ssm_scan_calls",
                 "ssm_boundary_bytes", "ssm_conv_calls",
                 "moe_rows_sum_calls", "moe_stack_in_place_calls",
                 "moe_epilogue_calls", "lin_attn_calls", "topk_attn_calls",
                 "topk_attn_keys_per_query", "topk_attn_dense_calls",
                 "ce_fused_chunks", "ga_fused_bytes", "ga_unfused_bytes",
                 "flash_bwd_calls"):
        assert len(traced.GROUP[name]) > 20, name


def _walking_loss(name):
    def loss(p, x):
        def body(h, w):
            traced.count(name)
            return jnp.tanh(h @ w), None
        return jnp.mean(scan_blocks(body, x, p["blocks"])[0] ** 2)
    return loss


@pytest.mark.parametrize("remat", [True, False])
def test_the_walk_says_how_many_layers_a_trace_stands_for(group, remat):
    def body(h, w, kind):
        traced.count("t_walked")
        return h @ w * kind, None

    traced.note("t_walked", 0)
    w = jnp.ones((5, 4, 4))
    jax.make_jaxpr(lambda x: scan_blocks(
        body, x, w, np.arange(5.0), remat=remat))(jnp.ones((2, 4)))
    assert traced.values()["t_walked"] == 5


def test_a_plan_reports_a_gauge_nobody_above_the_loss_names(group, caplog):
    """A loss whose walked body counts a gauge that no file under
    ``parallel/`` or ``train.py`` names: the plan's log line has it (3
    layers, traced in the walk's forward and in its recomputation), and the
    next ``build_ga_step`` zeroes it."""
    from tepdist_tpu.train import plan_training
    name = "t_planned_walk_calls"
    loss = _walking_loss(name)
    params = {"blocks": 0.1 * jnp.ones((3, 8, 8)), "bias": jnp.zeros((8,))}
    x = jnp.ones((4, 8))
    with caplog.at_level("INFO", logger="tepdist_tpu.train"):
        plan = plan_training(
            lambda p, x: loss(p, x + p["bias"]), optax.sgd(1e-2), params, x,
            devices=jax.devices()[:1], explore=False, num_micro_batches=2)
    line = next(r.getMessage() for r in caplog.records
                if "the traced step" in r.getMessage())
    assert re.search(rf"\b{name}=6\b", line), line
    assert "ga_fused_bytes=768" in line and "ga_unfused_bytes=32" in line
    assert "=0" not in line                     # the ones that were counted
    assert traced.values()[name] == 6
    assert plan.step(x) > 0

    build_ga_step(lambda p, x: jax.value_and_grad(loss)(p, x),
                  lambda p, s, g: (p, s), 1)
    assert traced.values()[name] == 0
    assert metrics().gauge(name).value == 0


def test_a_plan_logs_how_many_flash_calls_it_differentiates(group, caplog):
    """``flash_bwd_calls`` (declared beside the kernel): a walked stack of 3
    layers, each one flash call, differentiated once a micro batch's trace:
    3 in the plan's log line, whose backward pass is one kernel a call."""
    from tepdist_tpu.ops.pallas.flash_attention import flash_attention
    from tepdist_tpu.train import plan_training

    def loss(p, x):
        def body(h, w):
            q = (h @ w).reshape(-1, 16, 2, 8).transpose(0, 2, 1, 3)
            o = flash_attention(q, q, q, block_q=8, block_k=8)
            return h + o.transpose(0, 2, 1, 3).reshape(h.shape), None
        return jnp.mean(scan_blocks(body, x, p["blocks"])[0] ** 2)

    params = {"blocks": 0.1 * jnp.ones((3, 16, 16)), "bias": jnp.zeros((16,))}
    x = jnp.ones((4, 16, 16))
    with caplog.at_level("INFO", logger="tepdist_tpu.train"):
        plan_training(lambda p, x: loss(p, x + p["bias"]), optax.sgd(1e-2),
                      params, x, devices=jax.devices()[:1], explore=False,
                      num_micro_batches=2)
    line = next(r.getMessage() for r in caplog.records
                if "the traced step" in r.getMessage())
    assert re.search(r"\bflash_bwd_calls=3\b", line), line
    assert re.search(r"\battn_kept_calls=3\b", line), line
