"""A grouped-matmul call that carries an epilogue
(``ops/pallas/grouped_matmul.py:gmm(add=, act=)``) and the experts' first
half built on it (``ops/grouped_matmul.py:activation``): the addend and the
activation against the two operations they replace and against a float32
loop over the tiles, ``jax.grad`` of ``routed_experts`` against a float32
loop over the experts, and a walk's traced step, which sums no two
``[rows, d]`` cotangents and sets the gauge ``moe_epilogue_calls``.
Interpret mode, small shapes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from kernel_checks import equations_of

from tepdist_tpu.models import layers
from tepdist_tpu.ops import grouped_matmul as gm
from tepdist_tpu.ops.pallas import grouped_matmul as gmk
from tepdist_tpu.ops.pallas.grouped_matmul import ExpertStack
from tepdist_tpu.parallel.sync_free import build_ga_step
from tepdist_tpu.telemetry import metrics

ROUNDING = 2.0 ** -8            # one rounding to bf16, relative
E, K, N, TILE, TILES = 3, 32, 128, 8, 6
LAYERS, LAYER = 3, 1            # a stack, and the layer the calls read
# The group of each row tile; the tile of zero rows is group 1's only one
# (an empty group keeps one tile of pads).
TILE_GROUP = np.array([0, 0, 1, 2, 2, 2], np.int32)
PAD_TILE = 2


def _operands(live, stacked, seed=0):
    """bf16 rows ``[M, K]`` (a tile of zero rows among them) with the
    weights ``[E, K, N]``, or a stack of them and the layer's index, and
    ``live`` of the grid's tiles."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    M = TILES * TILE
    rows = np.arange(M) // TILE != PAD_TILE
    x = jnp.where(rows[:, None], jax.random.normal(keys[0], (M, K)), 0.0)
    w = 0.3 * jax.random.normal(keys[1], (LAYERS, E, K, N))
    weight = jnp.where(rows[:, None], jax.random.uniform(
        keys[2], (M, 1), jnp.float32, 0.1, 1.0), 0.0)
    wide = [jax.random.normal(key, (M, n)).astype(jnp.bfloat16)
            for key, n in zip(keys[3:], (N, N, K))]
    x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    tiles = (jnp.asarray(TILE_GROUP), jnp.full((1,), live, jnp.int32))
    at = (w, *tiles, jnp.full((1,), LAYER, jnp.int32)) if stacked \
        else (w[LAYER], *tiles)
    return x, at, w[LAYER], weight, wide, rows


def _loop(x, w, live, transpose=False):
    """float32, a tile at a time; zeros past the ``live`` tiles."""
    x, w = np.asarray(x, np.float32), np.asarray(w, np.float32)
    out = np.zeros((x.shape[0], w.shape[1 if transpose else 2]), np.float32)
    for i in range(live):
        rows = slice(i * TILE, (i + 1) * TILE)
        wi = w[TILE_GROUP[i]]
        out[rows] = x[rows] @ (wi.T if transpose else wi)
    return out


def _within_a_rounding(got, want, err_msg, of=None):
    """``got`` within one rounding to bf16 of ``want``; ``of``: of what was
    rounded, where that is larger than the result (a sum's terms)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_array_less(
        np.abs(got - want), ROUNDING * np.abs(want if of is None else of)
        + 1e-5 * np.abs(want).max() + 1e-30, err_msg=err_msg)


@pytest.mark.parametrize("stacked", [False, True], ids=["slice", "stack"])
@pytest.mark.parametrize("live", [TILES, TILES - 2], ids=["whole", "tail"])
def test_an_addend_is_summed_in_float32_and_rounded_once(live, stacked):
    """``gmm(dy, w, add=first)`` (the input gradient's form) against
    ``gmm(dy, w) + first`` and against the float32 loop plus ``first``; on
    the tile of zero rows it is ``first``, and past the live tiles zeros
    whatever ``first`` holds there."""
    _, at, w, _, (dy, _, first), rows = _operands(live, stacked)
    kw = dict(tile_m=TILE, transpose_rhs=True, name="tepdist_gmm_dx")
    apart = gmk.gmm(dy, *at, **kw)
    got = gmk.gmm(dy, *at, add=jnp.copy(first), **kw)
    alive = np.arange(TILES * TILE) < live * TILE
    want = np.where(alive[:, None], _loop(dy, w, live, True)
                    + np.asarray(first, np.float32), 0.0)
    _within_a_rounding(got, want, "the float32 loop")
    _within_a_rounding(
        got, np.where(alive[:, None], np.asarray(apart + first, np.float32),
                      0.0), "the two operations",    # each a rounding off
        of=2 * (np.abs(np.asarray(apart, np.float32)) + np.abs(want)))
    # One rounding, not two: no further from the float32 sum than the pair.
    assert np.abs(np.asarray(got, np.float32) - want).sum() <= np.abs(
        np.where(alive[:, None], np.asarray(apart + first, np.float32), 0.0)
        - want).sum()
    assert not np.asarray(got, np.float32)[~alive].any()
    assert live == TILES or np.asarray(first, np.float32)[~alive].any()


@pytest.mark.parametrize("stacked", [False, True], ids=["slice", "stack"])
@pytest.mark.parametrize("live", [TILES, TILES - 2], ids=["whole", "tail"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "two-matrix"])
def test_the_activation_is_the_up_projections_epilogue(gated, live, stacked):
    """``gmm(x, w_up, act=(gate, row_weight))`` against ``gated(gate, gmm(x,
    w_up), row_weight)``, and ``act=(row_weight,)`` against ``relu2``: within
    the rounding of ``up`` the epilogue leaves out, both a rounding from the
    float32 loop's; a pad row (zero row, zero weight) and the rows past the
    live tiles exactly zero."""
    x, at, w, weight, (gate, _, _), rows = _operands(live, stacked, seed=1)
    up = gmk.gmm(x, *at, tile_m=TILE)
    if gated:
        got = gmk.gmm(x, *at, act=(gate, weight), tile_m=TILE)
        apart = gm.gated(gate, up, weight)
        g = np.asarray(gate, np.float32)
        want = g / (1.0 + np.exp(-g)) * _loop(x, w, live) * np.asarray(weight)
    else:
        got = gmk.gmm(x, *at, act=(weight,), tile_m=TILE)
        apart = gm.relu2(up, weight)
        want = np.maximum(_loop(x, w, live), 0.0) ** 2 * np.asarray(weight)
    alive = np.arange(TILES * TILE) < live * TILE
    want = np.where(alive[:, None], want, 0.0)
    _within_a_rounding(got, want, "the float32 loop")
    # ``apart`` has rounded ``up`` first (twice over in relu(up)^2).
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.where(
            alive[:, None], np.asarray(apart, np.float32), 0.0),
        rtol=4 * ROUNDING, atol=1e-3 * np.abs(want).max())
    assert got.dtype == x.dtype and got.shape == up.shape
    assert not np.asarray(got, np.float32)[~(alive & rows)].any()
    assert np.asarray(got, np.float32)[alive & rows].any()


def test_an_epilogue_takes_one_form_and_the_results_shape():
    x, at, _, weight, (gate, _, first), _ = _operands(TILES, False)
    for bad in (dict(add=gate, act=(weight,)),              # both
                dict(act=(gate, gate, weight)),             # a third operand
                dict(act=(weight, gate)),                   # the weight last
                dict(add=first),                            # [M, K] on [M, N]
                dict(add=gate.astype(jnp.float32))):        # not x's dtype
        with pytest.raises(ValueError, match="gmm: add"):
            gmk.gmm(x, *at, tile_m=TILE, **bad)


# -- ``routed_experts`` against a float32 loop over the experts -------------

S, TOP, D, F = 24, 2, 16, 32
EXPERTS = 4


def _layer(gated, held, seed=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    G = held[1] if held else EXPERTS
    ids = jax.random.randint(keys[0], (S, TOP), 0, EXPERTS - 1)   # one empty
    here = (ids >= (held or (0, 0))[0]) & (ids < sum(held or (0, EXPERTS)))
    weights = jnp.where(here, jax.random.uniform(
        keys[1], (S, TOP), jnp.float32, 0.1, 1.0), 0.0)
    h = jax.random.normal(keys[2], (S, D))
    w = [0.3 * jax.random.normal(key, (LAYERS, G, *kn))
         for key, kn in zip(keys[3:6], ((D, F), (D, F), (F, D)))]
    if not gated:
        w[0] = None
    return h, weights, ids, w, jax.random.normal(keys[6], (S, D))


def _expert_loop(h, weights, ids, w_gate, w_up, w_down, first):
    """float32, an expert at a time over the tokens that chose it."""
    y = jnp.zeros_like(h)
    for e in range(w_up.shape[0]):
        up = h @ w_up[e]
        act = jnp.maximum(up, 0.0) ** 2 if w_gate is None \
            else jax.nn.silu(h @ w_gate[e]) * up
        chose = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=1)
        y = y + chose[:, None] * (act @ w_down[e])
    return y


def _apart(h, weights, r, w_gate, w_up, w_down, tile_m):
    """``routed_experts_at`` as it stood: a ``grouped_matmul`` a product
    and XLA's activation between them, autodiff's sum of ``x``'s two
    cotangents."""
    def mm(a, w):
        return gmk.grouped_matmul(a, w, r.tile_group, r.n_tiles, tile_m)

    x = gm.dispatch(h, r.row_token, r.dest, r.live_rows)
    row_weight = gm.dispatch_values(weights, r)
    act = gm.relu2(mm(x, w_up), row_weight) if w_gate is None else gm.gated(
        mm(x, w_gate), mm(x, w_up), row_weight)
    return gm.combine(mm(act, w_down), r.row_token, r.dest, r.live_rows)


# Every pair of (gated or not, whole layer or share, slices or stacks) once:
# a case lowers a score of interpreted kernels, which is where its time goes.
@pytest.mark.parametrize("gated,held,stacked", [
    (True, None, False), (True, (1, 2), True),
    (False, None, True), (False, (1, 2), False)],
    ids=["gated-whole-slice", "gated-share-stack",
         "two-matrix-whole-stack", "two-matrix-share-slice"])
def test_the_layers_gradient_is_the_float32_expert_loops(gated, held,
                                                         stacked, monkeypatch):
    """``jax.grad`` of ``routed_experts`` in float32, its rows, the
    router's weights and every expert matrix: the loop's; handed
    ``ExpertStack``s, each accumulator comes back with the layer's gradient
    added into its slice, **bit for bit what the three ``grouped_matmul``s
    apart give it** (the weight gradients' operands are theirs; only the
    rows' cotangent is summed elsewhere), every other slice as it was."""
    h, weights, ids, ws, cot = _layer(gated, held)
    into = [None if w is None else jnp.ones_like(w) for w in ws]
    layer = jnp.full((1,), LAYER, jnp.int32)

    def loss(h, weights, moving, at=gm.routed_experts_at):
        handed = [None if w is None else ExpertStack(w, layer, m) if stacked
                  else m for w, m in zip(ws, moving)]
        with monkeypatch.context() as patch:
            patch.setattr(gm, "routed_experts_at", at)
            patch.setattr(gm, "_branch", jax.jit(
                at, inline=True, static_argnames="tile_m"))
            y = gm.routed_experts(h, weights, ids, *handed, EXPERTS, TILE,
                                  held=held)
        return jnp.sum(y * cot)

    moving = into if stacked else [None if w is None else w[LAYER]
                                   for w in ws]
    got = jax.jit(jax.grad(loss, (0, 1, 2)))(h, weights, moving)
    want = jax.jit(jax.grad(lambda h, weights, w: jnp.sum(_expert_loop(
        h, weights, ids, *w, (held or (0,))[0]) * cot), (0, 1, 2)))(
            h, weights, [None if w is None else w[LAYER] for w in ws])
    for name, g, w in zip(("h", "weights"), got, want):
        np.testing.assert_allclose(g, w, rtol=0, err_msg=name,
                                   atol=1e-5 * float(jnp.abs(w).max()))
    apart = jax.jit(jax.grad(functools.partial(loss, at=_apart), 2))(
        h, weights, moving) if stacked else got[2]
    for name, g, w, a, m in zip(("w_gate", "w_up", "w_down"), got[2],
                                want[2], apart, into):
        if g is None:
            assert not gated and name == "w_gate"
            continue
        if stacked:
            np.testing.assert_array_equal(g, a, err_msg=name)
            for other in set(range(LAYERS)) - {LAYER}:
                np.testing.assert_array_equal(g[other], m[other])
            g = g[LAYER] - m[LAYER]
        np.testing.assert_allclose(g, w, rtol=0, err_msg=name,
                                   atol=1e-5 * float(jnp.abs(w).max()))


# -- a walk's traced step ----------------------------------------------------

def _walked(gated, in_place, at=None):
    """The accumulation step of two micro batches over a walk of two
    expert layers (float32, a whole layer each): its jaxpr's equations,
    a call that runs it, the gauge its trace set and the layout's rows."""
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    B, T, layers_ = 4, 16, 2
    shapes = {"router": (D, EXPERTS), "w_gate": (EXPERTS, D, F),
              "w_up": (EXPERTS, D, F), "w_down": (EXPERTS, F, D)}
    if not gated:
        del shapes["w_gate"]
    blocks = {k: 0.3 * jax.random.normal(key, (layers_,) + s)
              for key, (k, s) in zip(keys, shapes.items())}
    params = {"blocks": blocks}
    batch = jax.random.normal(keys[5], (B, T, D))
    experts = tuple(k for k in shapes if k != "router") if in_place else ()

    def body(x, blk):
        h = x.reshape(-1, D)
        scores = jax.nn.softmax((h @ blk["router"]).astype(jnp.float32))
        weights, ids = jax.lax.top_k(scores, TOP)
        y = gm.routed_experts(h, weights, ids, blk.get("w_gate"),
                              blk["w_up"], blk["w_down"], EXPERTS, TILE)
        return x + y.reshape(x.shape), None

    def loss(params, x):
        x, _ = layers.scan_blocks(body, x, params["blocks"], in_place=experts)
        return jnp.mean(x.astype(jnp.float32) ** 2)

    step = jax.jit(build_ga_step(
        lambda p, x: jax.value_and_grad(loss)(p, x),
        lambda p, s, g: (jax.tree_util.tree_map(
            lambda a, b: a - 0.1 * b.astype(a.dtype), p, g), s),
        2, loss_fn=loss))
    with pytest.MonkeyPatch.context() as patch:
        if at is not None:
            patch.setattr(gm, "routed_experts_at", at)
        traced = step.trace(params, (), batch)
    gauge = metrics().gauge("moe_epilogue_calls").value
    return (list(equations_of(traced.jaxpr.jaxpr)),
            lambda: traced.lower().compile()(params, (), batch), gauge,
            (-(-B // 2 * T * TOP // TILE) + EXPERTS) * TILE)


@pytest.mark.parametrize("gated,in_place", [
    (True, False), (True, True), (False, True)],
    ids=["gated-slice", "gated-stack", "two-matrix-stack"])
def test_a_walk_sums_no_two_input_gradients_and_counts_its_epilogues(
        gated, in_place):
    """The step's program: no ``add_any`` over the layout's ``[rows, d]``
    (the composition apart has one a gated layer's backward), the walk's
    first forward runs the activation as an epilogue and its differentiated
    recomputation does not, the second input gradient carries the first,
    and the gauge reads 2 a gated layer and 1 a layer of two matrices; the
    step's results (run where the walk hands stacks to a gated layer) are
    the composition's to float32's rounding."""
    eqns, run, gauge, rows = _walked(gated, in_place)
    eqns_apart, run_apart, _, _ = _walked(gated, in_place, at=_apart)

    def sums(eqns):
        return [e for e in eqns if e.primitive.name == "add_any"
                and e.outvars[0].aval.shape == (rows, D)]

    def kernels(eqns, name, operands):
        return [e for e in eqns if e.primitive.name == "pallas_call"
                and e.params["name"] == name
                and len(e.invars) == operands + 2 + in_place]

    assert not sums(eqns) and len(sums(eqns_apart)) == gated
    # x, w and the epilogue's (gate, weight) or (weight,), behind the
    # scalars: one such forward (the walk's), the plain ones beside it.
    assert len(kernels(eqns, "tepdist_gmm_fwd", 3 + gated)) == 1
    assert len(kernels(eqns, "tepdist_gmm_fwd", 2)) == 3 + 2 * gated
    assert len(kernels(eqns, "tepdist_gmm_dx", 3)) == gated
    assert len(kernels(eqns, "tepdist_gmm_dx", 2)) == 2
    assert not kernels(eqns_apart, "tepdist_gmm_fwd", 3 + gated)
    assert gauge == 2 * (1 + gated)             # two layers
    if not (gated and in_place):
        return
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(run()),
                            jax.tree_util.tree_leaves(run_apart())):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(
            a, b, rtol=0, atol=1e-5 * np.abs(b).max(),
            err_msg=jax.tree_util.keystr(path))
