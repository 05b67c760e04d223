"""OLMoE (``models/olmoe.py``) against the plain float32 reference
(``benchmark/reference/olmoe.py``), the dropless dispatch under skew, the
grouped matmul kernels against a per-expert loop, gradient accumulation
through ``plan_training``, and GPT-2's loss after the move of the chunked
cross entropy into ``models/layers.py``.

Tolerance. Model and reference both run in float32 here (the CPU's matmuls
are full float32; the Pallas kernels run in interpret mode), and differ in
the order of their sums only: relative L2 errors read 3e-7 to 7e-7 on every
leaf. The limit is 2e-5: a bf16 matmul anywhere (relative rounding 4e-3)
fails it by two orders of magnitude.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import olmoe as ref
from tepdist_tpu.models import gpt2, olmoe
from tepdist_tpu.ops import grouped_matmul as gm
from tepdist_tpu.ops.pallas import grouped_matmul as gmk

CFG = olmoe.CONFIGS["test"]
HP = ref.Hyper(n_head=CFG.num_attention_heads,
               top_k=CFG.num_experts_per_tok, rope_theta=CFG.rope_theta,
               eps=CFG.rms_norm_eps, lb_coef=CFG.lb_coef, z_coef=CFG.z_coef)
TOL = 2e-5
ONE_LAYER = dataclasses.replace(CFG, num_hidden_layers=1)


def rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.fixture(scope="module")
def params():
    # std 0.1: at 0.02 and width 64 the router's logits are nearly equal
    # and the expert layer's output nearly nothing.
    return olmoe.stacked_init_params(CFG, jax.random.PRNGKey(0), std=0.1)


@pytest.fixture(scope="module")
def tokens():
    return olmoe.fake_batch(CFG, 2, 16, seed=1)


def compare(params, tokens):
    """Logits, the three loss terms and every leaf's gradient."""
    compare_gradients(params, tokens)
    got = jax.jit(lambda p: olmoe.forward(p, tokens[:, :-1], CFG))(params)
    want = jax.jit(lambda p: ref.logits(p, tokens[:, :-1], HP))(params)
    assert rel(got, want) < TOL
    for a, b in zip(
            jax.jit(lambda p: olmoe.loss_terms(p, tokens, CFG))(params),
            jax.jit(lambda p: ref.loss_terms(p, tokens, HP))(params)):
        assert abs(float(a) - float(b)) < TOL * abs(float(b))


def compare_gradients(params, tokens, cfg=CFG):
    g_got = jax.jit(jax.grad(lambda p: olmoe.loss_fn(p, tokens, cfg)))(params)
    g_want = jax.jit(jax.grad(lambda p: ref.loss(p, tokens, HP)))(params)
    errors = jax.tree_util.tree_map(rel, g_got, g_want)
    assert set(errors["blocks"]) >= {"router", "w_gate", "w_up", "w_down"}
    worst = max(jax.tree_util.tree_leaves(errors))
    assert worst < TOL, errors


def test_model_matches_reference(params, tokens):
    compare(params, tokens)


def test_no_token_dropped_when_every_token_picks_the_same_experts(
        params, tokens):
    """A zero router ties every logit, so every token's top k are experts
    0..k-1: two experts get every token, six get none. A capacity scheme
    would drop most of them; here model and reference still agree, and
    the layout holds every assignment."""
    first = jax.tree_util.tree_map(lambda a: a[:1], params["blocks"])
    skewed = {**params, "blocks": {
        **first, "router": jnp.zeros_like(first["router"])}}
    compare_gradients(skewed, tokens, ONE_LAYER)
    stats = olmoe.routing_stats(skewed, tokens, ONE_LAYER)
    S = tokens.shape[0] * (tokens.shape[1] - 1)
    assert stats["moe_tokens_dropped"] == 0
    assert stats["moe_expert_rows_max"] == S
    assert stats["moe_assignments"] == S * CFG.num_experts_per_tok
    assert np.all(np.asarray(stats["experts"]) < CFG.num_experts_per_tok)


def test_unstacked_params_give_the_same_loss(params, tokens):
    unstacked = {k: v for k, v in params.items() if k != "blocks"}
    for i in range(CFG.num_hidden_layers):
        unstacked[f"l{i}"] = jax.tree_util.tree_map(
            lambda a, i=i: a[i], params["blocks"])
    assert np.allclose(olmoe.loss_fn(unstacked, tokens, CFG),
                       olmoe.loss_fn(params, tokens, CFG), rtol=1e-6)


# --------------------------------------------------------------------------
# The layout and the kernels
# --------------------------------------------------------------------------

@pytest.mark.parametrize("sizes", [(5, 0, 9, 2), (0, 0, 16, 0), (4, 4, 4, 4),
                                   (0, 7, 0, 9)])
def test_grouped_matmul_against_a_per_expert_loop(sizes):
    """Forward, input gradient and weight gradient, groups of size 0 and
    groups that fill a tile exactly included."""
    E, tile, K, N, k = len(sizes), 4, 16, 8, 2
    flat = np.repeat(np.arange(E), sizes)
    ids = jnp.asarray(np.random.default_rng(0).permutation(flat)
                      .reshape(-1, k), jnp.int32)
    S = ids.shape[0]
    r = gm.route(ids, E, tile)
    assert list(r.group_sizes) == list(sizes)
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (S, K), jnp.float32)
    w = jax.random.normal(kw, (E, K, N), jnp.float32)

    def kernels(x, w):
        rows = gm.dispatch(x, r.row_token, r.dest)
        out = gmk.grouped_matmul(rows, w, r.tile_group, r.n_tiles, tile)
        return gm.combine(out, r.row_token, r.dest)

    def loop(x, w):
        return sum(jnp.where((ids[:, j] == e)[:, None], x @ w[e], 0.0)
                   for e in range(E) for j in range(k))

    assert np.allclose(kernels(x, w), loop(x, w), atol=1e-5)
    cot = jax.random.normal(jax.random.PRNGKey(2), (S, N), jnp.float32)
    for got, want in zip(
            jax.grad(lambda x, w: jnp.sum(kernels(x, w) * cot), (0, 1))(x, w),
            jax.grad(lambda x, w: jnp.sum(loop(x, w) * cot), (0, 1))(x, w)):
        assert np.allclose(got, want, atol=1e-5)


def _stack_case(sizes, dtype, acc_dtype=None, layers=3):
    """A layout over ``sizes`` (its static size holds tiles past the live
    ones), rows, cotangents, a stack of ``layers`` layers' weights and an
    accumulator of random values."""
    E, tile, K, N, k = len(sizes), 4, 16, 128, 2
    flat = np.repeat(np.arange(E), sizes)
    ids = jnp.asarray(np.random.default_rng(0).permutation(flat)
                      .reshape(-1, k), jnp.int32)
    r = gm.route(ids, E, tile)
    assert int(r.n_tiles[0]) < r.tile_group.shape[0]
    M = r.row_token.shape[0]
    kx, kd, kw, ka = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(kx, (M, K), dtype)
    dy = jax.random.normal(kd, (M, N), dtype)
    w = jax.random.normal(kw, (layers, E, K, N), dtype)
    acc = jax.random.normal(ka, (layers, E, K, N), acc_dtype or dtype)
    return r, tile, x, dy, w, acc


def _bits(a):
    return np.asarray(a).view(np.uint16 if a.dtype == jnp.bfloat16
                              else np.uint32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("sizes", [(5, 0, 9, 2), (0, 0, 16, 0), (4, 4, 4, 4),
                                   (0, 7, 0, 9)])
def test_the_stack_forms_are_the_sliced_calls_bit_for_bit(sizes, dtype):
    """``gmm``, ``gmm(transpose_rhs)`` and ``tgmm`` over ``[L, E, K, N]``
    and a layer's index against the rank-3 call on ``stack[l]``, for every
    layer, with empty groups and tiles past ``n_tiles``; the weight
    gradient into an accumulator is ``into[l] + tgmm(...)`` in the
    accumulator's dtype and every other slice is left as it was."""
    r, tile, x, dy, w, acc = _stack_case(sizes, dtype)
    E = len(sizes)
    for l in range(w.shape[0]):
        layer = jnp.asarray([l], jnp.int32)
        kw = dict(tile_m=tile)
        for a, t in ((x, False), (dy, True)):
            got = gmk.gmm(a, w, r.tile_group, r.n_tiles, layer,
                          transpose_rhs=t, **kw)
            want = gmk.gmm(a, w[l], r.tile_group, r.n_tiles,
                           transpose_rhs=t, **kw)
            np.testing.assert_array_equal(_bits(got), _bits(want))
        got = gmk.tgmm(x, dy, r.tile_group, r.n_tiles, E, acc, layer, **kw)
        dw = gmk.tgmm(x, dy, r.tile_group, r.n_tiles, E, **kw)
        np.testing.assert_array_equal(_bits(got),
                                      _bits(acc.at[l].set(acc[l] + dw)))


def test_an_accumulator_of_another_dtype_takes_both_roundings():
    """bf16 rows into a float32 accumulator: the group's float32 sum is
    rounded to bf16 (the weight gradient as the sliced walk makes it), then
    added in float32."""
    sizes = (5, 0, 9, 2)
    r, tile, x, dy, _, acc = _stack_case(sizes, jnp.bfloat16, jnp.float32)
    layer = jnp.asarray([1], jnp.int32)
    got = gmk.tgmm(x, dy, r.tile_group, r.n_tiles, len(sizes), acc, layer,
                   tile_m=tile)
    dw = gmk.tgmm(x, dy, r.tile_group, r.n_tiles, len(sizes), tile_m=tile)
    assert got.dtype == jnp.float32 and dw.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        _bits(got), _bits(acc.at[1].set(acc[1] + dw.astype(jnp.float32))))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_grouped_matmul_over_an_expert_stack(dtype):
    """The custom VJP's form over ``(stack, layer, accumulator)``: the value
    and the input gradient are the sliced form's, the accumulator's
    cotangent is the accumulator with the weight gradient added into the
    layer's slice, and the stack gets none."""
    r, tile, x, dy, w, acc = _stack_case((5, 0, 9, 2), dtype)
    layer = jnp.asarray([2], jnp.int32)

    def sliced(x, w_l):
        return gmk.grouped_matmul(x, w_l, r.tile_group, r.n_tiles, tile)

    def in_place(x, into):
        return gmk.grouped_matmul(x, gmk.ExpertStack(w, layer, into),
                                  r.tile_group, r.n_tiles, tile)

    want, pull = jax.vjp(sliced, x, w[2])
    got, pull_in_place = jax.vjp(in_place, x, acc)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    (dx, dw), (dx_in_place, into) = pull(dy), pull_in_place(dy)
    np.testing.assert_array_equal(_bits(dx_in_place), _bits(dx))
    np.testing.assert_array_equal(_bits(into),
                                  _bits(acc.at[2].set(acc[2] + dw)))
    stack_ct = jax.grad(lambda w: jnp.sum(gmk.grouped_matmul(
        x, gmk.ExpertStack(w, layer, acc), r.tile_group, r.n_tiles,
        tile).astype(jnp.float32)))(w)
    assert not np.asarray(stack_ct, np.float32).any()


def test_a_stack_wants_its_layer_and_a_slice_takes_none():
    r, tile, x, dy, w, acc = _stack_case((4, 4, 4, 4), jnp.float32)
    layer = jnp.asarray([0], jnp.int32)
    with pytest.raises(ValueError, match="layer"):
        gmk.gmm(x, w, r.tile_group, r.n_tiles, tile_m=tile)
    with pytest.raises(ValueError, match="layer"):
        gmk.gmm(x, w[0], r.tile_group, r.n_tiles, layer, tile_m=tile)
    with pytest.raises(ValueError, match="into"):
        gmk.tgmm(x, dy, r.tile_group, r.n_tiles, 4, acc, tile_m=tile)
    with pytest.raises(ValueError, match="into"):
        gmk.tgmm(x, dy, r.tile_group, r.n_tiles, 4, acc[:, :2], layer,
                 tile_m=tile)


@pytest.mark.parametrize("spec", ["balanced", "skewed"])
def test_gmm_bench_check_reference_is_the_kernels_answer(spec):
    """``tools/gmm_bench.py --check`` on the chip compares the compiled
    kernels with this loop over these group sizes; here the kernels run in
    interpret mode, so the loop and the sizes are held to them."""
    from tools import gmm_bench
    E, tile, K, N, R = 8, 8, 16, 8, 200
    (label, sizes), = gmm_bench.size_sets(spec, R, E,
                                          np.random.default_rng(3))
    assert label == spec and sizes.sum() == R
    assert (sizes[-2:] == 0).all() == (spec == "skewed")
    ids = np.random.default_rng(4).permutation(
        np.repeat(np.arange(E), sizes)).astype(np.int32)
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(kx, (R, K), jnp.float32)
    dy = jax.random.normal(ky, (R, N), jnp.float32)
    w = jax.random.normal(kw, (E, K, N), jnp.float32)
    r = gm.route(jnp.asarray(ids)[:, None], E, tile)
    xp, dyp = (gm.dispatch(a, r.row_token, r.dest) for a in (x, dy))
    kw_ = dict(tile_m=tile)
    got = (gmk.gmm(xp, w, r.tile_group, r.n_tiles, **kw_)[r.dest[:, 0]],
           gmk.gmm(dyp, w, r.tile_group, r.n_tiles, transpose_rhs=True,
                   **kw_)[r.dest[:, 0]],
           gmk.tgmm(xp, dyp, r.tile_group, r.n_tiles, E, **kw_))
    for g, want in zip(got, gmm_bench.loop_reference(x, dy, w, ids)):
        assert gmm_bench.rel_l2(g, want) < 1e-6


def test_cost_model_reads_the_kernels_own_cost_estimate():
    """``graph/cost.py`` prices a ``pallas_call`` that states its cost by
    that, not as one operation an output element."""
    from tepdist_tpu.graph.cost import jaxpr_flops
    M, K, N, E, tile = 16, 8, 128, 2, 8
    tile_group = jnp.zeros((M // tile,), jnp.int32)
    n_tiles = jnp.asarray([M // tile], jnp.int32)
    jaxpr = jax.make_jaxpr(lambda x, w: gmk.gmm(
        x, w, tile_group, n_tiles, tile_m=tile))(
        jnp.ones((M, K)), jnp.ones((E, K, N)))
    assert jaxpr_flops(jaxpr.jaxpr) == 2 * M * K * N


def test_layout_places_every_assignment_once_and_pads_with_zero_rows():
    ids = jnp.asarray([[0, 3], [3, 1], [3, 0], [3, 2], [3, 1]], jnp.int32)
    r = gm.route(ids, 5, 2)
    rows = np.asarray(r.row_token)
    dest = np.asarray(r.dest)
    assert len(set(dest.ravel())) == dest.size          # no row used twice
    assert np.all(rows[dest] == np.arange(5)[:, None])  # row holds its token
    assert np.all(np.asarray(r.row_assignment)[dest]
                  == np.arange(10).reshape(5, 2))
    live = np.zeros(rows.size, bool)
    live[dest.ravel()] = True
    assert np.all(rows[~live] == 5)
    assert np.all(np.asarray(r.row_assignment)[~live] == 10)
    # Every row tile belongs to the expert of the assignments in it; the
    # expert nobody chose (4) keeps one tile of pads.
    groups = np.asarray(r.tile_group)[dest // 2]
    assert np.all(groups == np.asarray(ids))
    assert int(r.n_tiles[0]) == 1 + 1 + 1 + 3 + 1
    assert np.asarray(r.tile_group)[int(r.n_tiles[0]) - 1] == 4


@pytest.mark.parametrize("sizes, k", [
    ((4, 8, 4), 2),          # every live tile full: no pad among them
    ((7, 8), 3),             # one pad
    ((5, 0, 9, 0), 2),       # experts nobody chose: whole tiles of pads
])
def test_pads_contribute_nothing(sizes, k):
    """A pad row reads the zero row ``dispatch`` appends (nothing is masked,
    and an index clamped into range would read the last token's row, large
    here) and the router's weight of a pad is exactly 0: value and every
    gradient of dispatch -> gate, up, gated activation, down -> combine are
    those of a loop over each token's k experts."""
    E, tile, K, N = len(sizes), 4, 16, 8
    ids = jnp.asarray(np.random.default_rng(0).permutation(
        np.repeat(np.arange(E), sizes)).reshape(-1, k), jnp.int32)
    S = ids.shape[0]
    r = gm.route(ids, E, tile)
    pad = np.asarray(r.row_token) == S
    assert pad[:int(r.n_tiles[0]) * tile].sum() == sum(
        max(tile, -(-n // tile) * tile) - n for n in sizes)
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(keys[0], (S, K), jnp.float32).at[-1].mul(100.0)
    weights = jax.random.uniform(keys[1], (S, k), jnp.float32, 0.1, 1.0)
    w_gate, w_up = (jax.random.normal(key, (E, K, N), jnp.float32) * 0.3
                    for key in keys[2:4])
    w_down = jax.random.normal(keys[4], (E, N, K), jnp.float32) * 0.3
    cot = jax.random.normal(keys[5], (S, K), jnp.float32)
    args = (x, weights, w_gate, w_up, w_down)

    def gmm(a, w):
        return gmk.grouped_matmul(a, w, r.tile_group, r.n_tiles, tile)

    def layer(x, weights, w_gate, w_up, w_down):
        rows = gm.dispatch(x, r.row_token, r.dest)
        act = olmoe.gated(gmm(rows, w_gate), gmm(rows, w_up),
                          gm.dispatch_values(weights, r))
        return gm.combine(gmm(act, w_down), r.row_token, r.dest)

    def loop(x, weights, w_gate, w_up, w_down):
        out = []
        for s in range(S):
            out.append(sum(
                weights[s, j] * (jax.nn.silu(x[s] @ w_gate[ids[s, j]])
                                 * (x[s] @ w_up[ids[s, j]]))
                @ w_down[ids[s, j]] for j in range(k)))
        return jnp.stack(out)

    rows = np.asarray(gm.dispatch(x, r.row_token, r.dest))
    assert not rows[pad].any() and rows[~pad].all()
    assert not np.asarray(gm.dispatch_values(weights, r))[pad].any()
    assert rel(layer(*args), loop(*args)) < TOL
    for got, want in zip(
            jax.grad(lambda *a: jnp.sum(layer(*a) * cot), range(5))(*args),
            jax.grad(lambda *a: jnp.sum(loop(*a) * cot), range(5))(*args)):
        assert rel(got, want) < TOL


def _equations(jaxpr, skip=()):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold
    (not those of a primitive named in ``skip``)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in skip:
            continue
        for value in eqn.params.values():
            for held in value if isinstance(value, (list, tuple)) else [
                    value]:
                held = getattr(held, "jaxpr", held)
                if hasattr(held, "eqns"):
                    yield from _equations(held, skip)


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_moe_has_no_wide_select_and_no_look_up_a_row(params, what):
    """The expert layer masks no ``[rows, d]`` array, by a select or by a
    gather that fills (a pad reads an appended zero row), and looks nothing
    up row by row in a table of one entry an expert or a row tile."""
    cfg = ONE_LAYER
    blk = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    x = jnp.ones((2, 16, cfg.hidden_size), jnp.float32)
    E, tile, d = cfg.num_experts, cfg.moe_tile_m, cfg.hidden_size
    rows = (-(-x.shape[0] * x.shape[1] * cfg.num_experts_per_tok // tile)
            + E) * tile

    def out(blk, x):
        y, lb, zl = olmoe.moe(blk, x, cfg)
        return jnp.sum(y * y) + lb + zl

    fn = out if what == "forward" else jax.grad(out, (0, 1))
    eqns = [e for e in _equations(jax.make_jaxpr(fn)(blk, x).jaxpr)
            if e.outvars]
    found = [(e.primitive.name, e.outvars[0].aval.shape) for e in eqns]
    wide = [e for e in eqns if e.primitive.name == "gather"
            and e.outvars[0].aval.shape == (rows, d)]
    assert wide                                # the walk reaches the layer
    # A gather that fills is a gather and a select once lowered.
    assert not [e for e in wide if e.params["mode"]
                == jax.lax.GatherScatterMode.FILL_OR_DROP]
    assert ("select_n", (rows, d)) not in found
    look_ups = [
        e for e in eqns if e.primitive.name == "gather"
        and e.invars[0].aval.shape in ((E,), (rows // tile,))
        and e.outvars[0].aval.size >= rows]
    assert not look_ups, look_ups


def _one_by_one(h, weights, experts, w_gate, w_up, w_down, num_experts,
                tile_m, held=None):
    """``routed_experts`` as it stood before a share's layout took its size
    on the device: the layout at its one size and the pieces called one by
    one (what the whole layer is held to, operation for operation)."""
    r = gm.route(experts, num_experts, tile_m, held)
    rows = gm.dispatch(h, r.row_token, r.dest)
    row_weight = gm.dispatch_values(weights, r)

    act = gm.activation(rows, w_gate, w_up, row_weight, r.tile_group,
                        r.n_tiles, tile_m)
    return gm.combine(gmk.grouped_matmul(
        act, w_down, r.tile_group, r.n_tiles, tile_m), r.row_token, r.dest)


@pytest.mark.parametrize("held", [None, "whole"])
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_the_whole_layer_has_one_size_and_no_conditional(params, what, held):
    """A layer that holds every expert (``held=None`` or ``(0, E)``) has
    nothing to choose: no ``cond`` is traced, and its operations are those
    of ``route`` + ``dispatch`` + kernels + ``combine`` called one by one,
    in their order."""
    cfg = ONE_LAYER
    blk = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    h = jnp.ones((32, cfg.hidden_size), jnp.float32)
    E, tile = cfg.num_experts, cfg.moe_tile_m
    held = (0, E) if held else None
    assert len(gm.layout_rows(32, cfg.num_experts_per_tok, E, E, tile)) == 1

    def primitives(layer):
        def out(blk, h):
            _, _, weights, experts = olmoe.router(blk, h, cfg)
            y = layer(h, weights, experts, blk["w_gate"], blk["w_up"],
                      blk["w_down"], E, tile, held=held)
            return jnp.sum(y * y)

        fn = out if what == "forward" else jax.grad(out, (0, 1))
        return [e.primitive.name for e in _equations(
            jax.make_jaxpr(fn)(blk, h).jaxpr, skip=("pallas_call",))]

    got = primitives(gm.routed_experts)
    assert "cond" not in got and got.count("pallas_call") == (
        3 if what == "forward" else 9)
    assert got == primitives(_one_by_one)


def test_two_steps_of_the_whole_layer_are_the_one_by_one_layers(
        params, monkeypatch):
    """The state after two accumulated steps at the test preset, with the
    expert layer as it is and with the pieces called one by one: bit for
    bit."""
    from tepdist_tpu.optim import make_optimizer
    from tepdist_tpu.parallel.sync_free import build_ga_step
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    batches = [olmoe.fake_batch(cfg, 4, 16, seed=s) for s in (2, 3)]

    def two_steps():
        tx = make_optimizer({"name": "adamw_bf16", "learning_rate": 1e-3})

        def loss(p, t):
            return olmoe.loss_fn(p, t, cfg)

        def apply_fn(p, s, g):
            updates, s = tx.update(g, s, p)
            return optax.apply_updates(p, updates), s

        step = jax.jit(build_ga_step(
            lambda p, t: jax.value_and_grad(loss)(p, t), apply_fn, 2,
            loss_fn=loss))
        p, state, losses = params, tx.init(params), []
        for tokens in batches:
            value, p, state = step(p, state, tokens)
            losses.append(float(value))
        return losses, p, state

    got = two_steps()
    monkeypatch.setattr(olmoe, "routed_experts", _one_by_one)
    want = two_steps()
    assert got[0] == want[0]
    for a, b in zip(jax.tree_util.tree_leaves(got[1:]),
                    jax.tree_util.tree_leaves(want[1:])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# Through plan_training
# --------------------------------------------------------------------------

def test_plan_training_accumulates_to_the_unaccumulated_step(params):
    """``num_micro_batches=2`` on one device gives the loss and the update
    of the whole batch in one go: the loss is a mean of per-sequence
    losses, so the split does not show."""
    from tepdist_tpu.train import plan_training
    cfg = ONE_LAYER
    params = {**params, "blocks": jax.tree_util.tree_map(
        lambda a: a[:1], params["blocks"])}
    tokens = olmoe.fake_batch(cfg, 4, 16, seed=2)
    opt = optax.sgd(0.1)
    want_loss, grads = jax.jit(jax.value_and_grad(
        lambda p: olmoe.loss_fn(p, tokens, cfg)))(params)
    want = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
    copy = jax.tree_util.tree_map(jnp.array, params)
    plan = plan_training(lambda p, t: olmoe.loss_fn(p, t, cfg), opt, copy,
                         tokens, devices=jax.devices()[:1], explore=False,
                         num_micro_batches=2)
    loss = plan.step(tokens)
    assert abs(loss - float(want_loss)) < 1e-5 * float(want_loss)
    got, _ = plan.variables()
    moved = jax.tree_util.tree_map(
        lambda a, b, p: rel(a - p, b - p), got, want, params)
    assert max(jax.tree_util.tree_leaves(moved)) < 1e-4, moved


# --------------------------------------------------------------------------
# GPT-2 after the move of the chunked cross entropy
# --------------------------------------------------------------------------

def _ce_before_the_move(x, wte, targets, chunk):
    """``gpt2._ce_from_hidden`` as it stood before ``layers.cross_entropy``
    took it over (the loop the new one is held to, bit for bit)."""
    B, T, D = x.shape
    n_tokens = B * T
    if chunk <= 0:
        logits = (x @ wte.T).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)
    n_chunks = -(-n_tokens // chunk)
    pad = n_chunks * chunk - n_tokens
    xf = x.reshape(n_tokens, D)
    tf = targets.reshape(n_tokens)
    valid = jnp.ones((n_tokens,), jnp.float32)
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad, D), x.dtype)])
        tf = jnp.concatenate([tf, jnp.zeros((pad,), targets.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.float32)])
    xf = xf.reshape(n_chunks, chunk, D)
    tf = tf.reshape(n_chunks, chunk)
    valid = valid.reshape(n_chunks, chunk)

    @jax.checkpoint
    def body(acc, inp):
        xc, tc, mc = inp
        logits = (xc @ wte.T).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return acc + jnp.sum((logz - gold) * mc), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (xf, tf, valid))
    return total / n_tokens


@pytest.mark.parametrize("chunk", [0, 16, 24])
def test_gpt2_loss_is_bit_identical(chunk):
    import dataclasses
    cfg = dataclasses.replace(gpt2.CONFIGS["test"], loss_chunk=chunk)
    p = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, 2, 32)

    def before(p):
        x = gpt2.hidden_states(p, tokens[:, :-1], cfg)
        return _ce_before_the_move(x, p["wte"], tokens[:, 1:], chunk)

    want, g_want = jax.value_and_grad(before)(p)
    got, g_got = jax.value_and_grad(gpt2.loss_fn)(p, tokens, cfg)
    assert float(got) == float(want)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        if chunk == 0:
            assert np.array_equal(a, b)
        else:
            # The chunked loss makes its gradients in the forward chunk
            # loop: the chunks reach the head's gradient first to last
            # where autodiff's backward scan went last to first.
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
