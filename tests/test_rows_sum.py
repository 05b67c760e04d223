"""The row-copy kernel out of an expert layer's layout
(``ops/pallas/rows_sum.py``, interpret mode) against the XLA gathers it
stands in for (``ops/grouped_matmul.py:_sum_of_rows``): the same bits, and
the same gradients through the layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tepdist_tpu.ops import grouped_matmul as gm
from tepdist_tpu.ops.pallas.rows_sum import rows_sum

S, K, E, TILE = 64, 4, 8, 16
HELD = (2, 2)


def _choices(kind, key):
    """[S, K] expert ids over ``E`` for a layer that holds ``HELD``."""
    first, count = HELD
    if kind == "elsewhere":                  # no choice of a held expert
        return jax.random.randint(key, (S, K), first + count, E)
    if kind == "one_token":                  # token 5 alone, all K of its
        ids = jax.random.randint(key, (S, K), first + count, E)
        return ids.at[5].set(first + jnp.arange(K) % count)
    return jax.random.randint(key, (S, K), 0, E)


# The layer's holdings, the router's choices, and which of ``layout_rows``'
# sizes the layout is cut to (a whole layer has the one).
LAYOUTS = {
    "whole_layer": (None, "any", -1),
    "share_first_size": (HELD, "any", 0),
    "share_worst_case": (HELD, "any", -1),
    "every_choice_elsewhere": (HELD, "elsewhere", 0),
    "one_token_holds_all_k": (HELD, "one_token", 0),
}


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("d", [1024, 1152])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernel_is_the_gathers_bit_for_bit(layout, d, dtype):
    held, kind, size = LAYOUTS[layout]
    ids = _choices(kind, jax.random.PRNGKey(0))
    sizes = gm.layout_rows(S, K, gm._held(E, held)[1], E, TILE)
    r = gm.route(ids, E, TILE, held)
    assert size == -1 or int(gm.layout_index(r.n_tiles, sizes, TILE)) == 0
    r = gm.at_rows(r, sizes[size], TILE)
    M, bound = r.row_token.shape[0], r.n_tiles * TILE
    # Rows as the grouped-matmul kernels leave them: zeros from the live
    # bound on; signed zeros among the live ones.
    y = jax.random.normal(jax.random.PRNGKey(1), (M, d), jnp.float32)
    y = jnp.where(jnp.arange(M)[:, None] < bound[0], y, 0.0)
    y = y.at[::3, ::5].set(-0.0).astype(dtype)
    live = np.asarray(r.dest) < int(bound[0])
    if held is None:
        assert live.all()
    elif kind == "elsewhere":
        assert not live.any()
    elif kind == "one_token":
        assert live[5].all() and live.sum() == K
    else:
        assert live.any() and not live.all()
    want = jax.jit(gm._sum_of_rows)(y, r.dest)
    got = jax.jit(rows_sum)(y, r.dest, bound)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("held", [None, HELD], ids=["whole_layer", "share"])
def test_gradients_through_the_layer_are_the_gathers(held, monkeypatch):
    """``jax.grad`` through ``routed_experts`` with the kernel where the
    layer takes it, against the same layer on the XLA gathers alone."""
    d, f = 128, 64
    count = gm._held(E, held)[1]
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    h = jax.random.normal(ks[0], (S, d), jnp.float32)
    ids = _choices("any", ks[1])
    weights = jax.nn.softmax(jax.random.normal(ks[2], (S, K)), axis=-1)
    if held is not None:
        weights = jnp.where((ids >= held[0]) & (ids < held[0] + count),
                            weights, 0.0)
    w_gate, w_up = (jax.random.normal(k, (count, d, f)) * 0.1
                    for k in ks[3:5])
    w_down = jax.random.normal(ks[5], (count, f, d)) * 0.1

    def loss(h, weights, w_gate, w_up, w_down):
        y = gm.routed_experts(h, weights, ids, w_gate, w_up, w_down, E,
                              TILE, held=held)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape)))

    args = (h, weights, w_gate, w_up, w_down)
    grad = jax.value_and_grad(loss, argnums=tuple(range(5)))
    got = grad(*args)
    monkeypatch.setattr(gm, "rows_sum",
                        lambda y, dest, bound: gm._sum_of_rows(y, dest))
    gm._branch.clear_cache()
    try:
        want = grad(*args)
    finally:
        gm._branch.clear_cache()
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _ga_step(module, micro):
    """(``plan_training``'s accumulation step over ``module``'s test model
    with rematerialised blocks, its arguments)."""
    import dataclasses

    import optax

    from tepdist_tpu.parallel.sync_free import build_ga_step
    cfg = dataclasses.replace(module.CONFIGS["test"], remat=True)
    params = module.stacked_init_params(cfg, jax.random.PRNGKey(0), std=0.1)
    opt = optax.sgd(1e-2)

    def loss(p, t):
        return module.loss_fn(p, t, cfg)

    def apply_fn(p, s, g):
        updates, s = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s

    step = build_ga_step(lambda p, t: jax.value_and_grad(loss)(p, t),
                         apply_fn, micro, loss_fn=loss)
    return step, (params, opt.init(params), module.fake_batch(cfg, 4, 32))


# model -> (micro batches, routed layers that hold a share of the experts)
@pytest.mark.parametrize("model,micro,share_layers", [
    ("afmoe", 2, 2), ("mellum", 2, 3), ("olmoe", 2, 0), ("mellum", 1, 0)],
    ids=["afmoe", "mellum", "olmoe_whole_layer", "mellum_one_micro_batch"])
def test_the_gauge_counts_the_kernels_calls_a_micro_batch(model, micro,
                                                          share_layers):
    """``moe_rows_sum_calls``: 2 a walked layer that holds a share (its
    ``combine`` and its ``dispatch``'s backward), 0 for a whole layer (the
    XLA gathers stay) and outside a walk; and the kernel is in the traced
    step exactly where the gauge says."""
    import importlib

    from tepdist_tpu.telemetry import metrics
    module = importlib.import_module(f"tepdist_tpu.models.{model}")
    metrics().gauge("moe_rows_sum_calls").set(-1)
    step, args = _ga_step(module, micro)
    text = str(jax.make_jaxpr(step)(*args))
    assert metrics().gauge("moe_rows_sum_calls").value == 2 * share_layers
    held_share = model != "olmoe"
    assert ("tepdist_rows_sum" in text) == held_share
    assert ("tepdist_rows_tiled" in text) == held_share


def test_the_share_of_rows_the_kernel_fetches_is_the_held_assignments():
    from tepdist_tpu.models.layers import held_routing_stats
    from tepdist_tpu.telemetry import metrics
    ids = jnp.stack([_choices("any", jax.random.PRNGKey(i)) for i in (5, 6)])
    stats = held_routing_stats(ids, E, TILE, HELD)
    held = np.asarray((ids >= HELD[0]) & (ids < HELD[0] + HELD[1])).mean()
    assert 0 < held < 1
    assert stats["moe_rows_fetched_share"] == pytest.approx(held)
    assert metrics().gauge("moe_rows_fetched_share").value \
        == stats["moe_rows_fetched_share"]
    none = held_routing_stats(
        _choices("elsewhere", jax.random.PRNGKey(7))[None], E, TILE, HELD)
    assert none["moe_rows_fetched_share"] == 0
