"""AFMoE (arcee-ai Trinity): the program against the plain float32
reference at a tiny preset, the expert layer that holds a share of the
experts, the router bias's once-a-step update, and the flash kernels'
window and grouped-query heads."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from model_checks import KEY, Model, match_the_reference

from benchmark.reference import afmoe as ref
from tepdist_tpu.models import afmoe, decoder, olmoe
from tepdist_tpu.ops import grouped_matmul as gm
from tepdist_tpu.ops.pallas import flash_attention as fa
from tepdist_tpu.optim import make_optimizer
from tepdist_tpu.telemetry import metrics

CFG = afmoe.CONFIGS["test"]          # 16-wide router, experts 4..7 held;
#                                      a dense window layer, then a global
#                                      and a window expert layer
LAYERS = range(CFG.num_dense_layers, CFG.num_hidden_layers)
OPT = {"name": "adamw_bf16_router_bias", "learning_rate": 1e-3,
       "bias_rate": 0.001}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def hyper(cfg):
    return ref.Hyper(
        n_head=cfg.num_attention_heads, n_kv_head=cfg.num_key_value_heads,
        top_k=cfg.num_experts_per_tok, layer_types=cfg.layer_types,
        window=cfg.sliding_window, held=cfg.experts_held,
        route_scale=cfg.route_scale, rope_theta=cfg.rope_theta,
        eps=cfg.rms_norm_eps)


# The row, and the file's compiled programs.
MODEL = Model(
    afmoe, ref, CFG, hyper, ("tok_emb", "norm_f", "lm_head"),
    stack=lambda tree, cfg: decoder.stack_layers(
        tree, afmoe._stacks(cfg), ("tok_emb", "norm_f", "lm_head")),
    opt=OPT)
loss_and_grads, to_reference = MODEL.loss_and_grads, MODEL.to_reference


@pytest.mark.parametrize("stacked,remat", [(False, False), (True, True)],
                         ids=["unstacked-plain", "stacked-remat"])
def test_loss_and_every_gradient_match_the_reference(stacked, remat):
    grads = match_the_reference(MODEL, stacked, remat, logits=False)
    # Where a gradient would be, the bias holds its layer's counts.
    counts = MODEL.reference("counts")
    got = grads["blocks"]["router_bias"] if stacked else jnp.stack(
        [grads[f"l{i}"]["router_bias"] for i in LAYERS])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(counts))
    assert float(counts.sum()) == len(LAYERS) * 2 * 32 \
        * CFG.num_experts_per_tok


def _two_steps(cfg, params, batches, micro):
    tx, step = MODEL.ga_step(cfg, micro)
    state, losses, after = tx.init(params), [], []
    for tokens in batches:
        loss_value, params, state = step(params, state, tokens)
        losses.append(float(loss_value))
        after.append(params)
    return losses, after


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["unstacked", "stacked"])
def test_two_steps_do_not_depend_on_the_accumulation_split(stacked):
    """Two optimizer steps with and without gradient accumulation: the same
    losses, and the selection bias moved by the reference's update of the
    whole batch's counts, whatever the split (the second step routes with
    the first step's bias)."""
    cfg = dataclasses.replace(CFG, remat=True)
    params = MODEL.init_params(cfg, stacked)
    batches = [afmoe.fake_batch(cfg, 4, 32, seed=s) for s in (2, 3)]
    one, p_one = _two_steps(cfg, params, batches, 1)
    four, p_four = _two_steps(cfg, params, batches, 4)
    np.testing.assert_allclose(one, four, rtol=2e-6)

    def biases(p):
        return np.asarray(p["blocks"]["router_bias"] if stacked else
                          jnp.stack([p[f"l{i}"]["router_bias"]
                                     for i in LAYERS]))

    for a, b in zip(p_one, p_four):
        np.testing.assert_array_equal(biases(a), biases(b))
    # Each step's update is the reference's, from that step's own counts:
    # the second routes with the first's bias and weights.
    bias = np.zeros_like(biases(params))
    for before, tokens, after in zip([params] + p_one, batches, p_one):
        counts = MODEL.ref_expert_counts(to_reference(before, cfg), tokens,
                                         hyper(cfg))
        bias = np.asarray(ref.bias_update(bias, counts, OPT["bias_rate"]))
        np.testing.assert_allclose(biases(after), bias, atol=1e-9)
    assert np.abs(bias).max() > 0
    np.testing.assert_allclose(bias.sum(-1), 0, atol=1e-7)
    if stacked:                      # the in-loop accumulation took the bias
        fused = metrics().gauge("ga_fused_bytes").value
        unfused = metrics().gauge("ga_unfused_bytes").value
        assert fused / (fused + unfused) > 0.5


def test_bias_update_is_the_references():
    counts = jnp.asarray([[5., 0., 3., 8.], [2., 2., 2., 2.]])
    tx = make_optimizer(dict(OPT))
    params = {"l1": {"router_bias": jnp.zeros((2, 4)),
                     "router": jnp.ones((3, 4))}}
    grads = {"l1": {"router_bias": counts, "router": jnp.ones((3, 4))}}
    updates, _ = tx.update(grads, tx.init(params), params)
    want = ref.bias_update(jnp.zeros((2, 4)), counts, OPT["bias_rate"])
    np.testing.assert_allclose(np.asarray(updates["l1"]["router_bias"]),
                               np.asarray(want), atol=1e-9)
    assert np.asarray(want)[1].tolist() == [0, 0, 0, 0]
    # A state built for a sub-tree has the whole tree's paths.
    whole = {jax.tree_util.keystr(p) for p, leaf in
             jax.tree_util.tree_flatten_with_path(
                 jax.eval_shape(tx.init, params))[0]}
    part = {jax.tree_util.keystr(p) for p, leaf in
            jax.tree_util.tree_flatten_with_path(jax.eval_shape(
                tx.init, {"l1": {"router": params["l1"]["router"]}}))[0]}
    assert part <= whole


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts that shares (0,4) .. (12,4) of a 16-wide router
    give, with the shared expert counted once, are the uncut reference's
    whole layer."""
    E, G = CFG.num_experts, 4
    whole = dataclasses.replace(CFG, experts_held=(0, E))
    blk = afmoe.init_params(whole, KEY)["l1"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, CFG.hidden_size))
    blk["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(6),
                                                  (E,))
    h = x.reshape(-1, CFG.hidden_size)
    shared = afmoe.swiglu(h, blk["shared_gate"], blk["shared_up"],
                          blk["shared_down"]).reshape(x.shape)
    total = shared
    for first in range(0, E, G):
        cfg = dataclasses.replace(CFG, experts_held=(first, G))
        part = {**blk, **{k: blk[k][first:first + G]
                          for k in ("w_gate", "w_up", "w_down")}}
        total = total + afmoe.moe(part, x, cfg) - shared
    hp = hyper(whole)
    want = jnp.stack([ref._moe(blk, s, hp, ref.identity)[0] for s in x])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=0, atol=2e-6)
    np.testing.assert_allclose(np.asarray(afmoe.moe(blk, x, whole)),
                               np.asarray(want), rtol=0, atol=2e-6)


def test_the_whole_share_is_olmoes_layer_bit_for_bit():
    """``held=(0, E)`` of E: the layout, the routed experts' output and
    every gradient are what ``models/olmoe.py:moe`` computes."""
    cfg = olmoe.CONFIGS["test"]
    E, k, tile = cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_tile_m
    blk = olmoe.init_params(cfg, KEY)["l0"]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 16, cfg.hidden_size))
    experts = olmoe.router(blk, x.reshape(32, -1), cfg)[3]
    for a, b in zip(gm.route(experts, E, tile),
                    gm.route(experts, E, tile, held=(0, E))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def through(held):
        def f(blk, x):
            h = x.reshape(32, -1)
            _, _, weights, experts = olmoe.router(blk, h, cfg)
            return jnp.sum(gm.routed_experts(
                h, weights, experts, blk["w_gate"], blk["w_up"],
                blk["w_down"], E, tile, held=held) ** 2)
        return jax.value_and_grad(f, argnums=(0, 1))(blk, x)

    def olmoes(blk, x):
        return jnp.sum(olmoe.moe(blk, x, cfg)[0] ** 2)

    want = jax.value_and_grad(olmoes, argnums=(0, 1))(blk, x)
    for got in (through(None), through((0, E))):
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("send", ["all_held", "none_held", "mixed"])
def test_no_assignment_to_a_held_expert_is_dropped(send):
    """A router forced to send every token to held experts, one that sends
    none, and the seed's: every assignment to a held expert has a row, and
    the static row count is the worst case, ``min(k, count)`` a token."""
    cfg = CFG
    params = afmoe.init_params(cfg, KEY)
    first, count = cfg.experts_held
    bias = {"all_held": 10.0, "none_held": -10.0, "mixed": 0.0}[send]
    for i in LAYERS:
        params[f"l{i}"]["router_bias"] = params[f"l{i}"]["router_bias"] \
            .at[first:first + count].set(bias)
    tokens = afmoe.fake_batch(cfg, 2, 32, seed=4)
    stats = afmoe.routing_stats(params, tokens, cfg)
    S, k, layers = 64, cfg.num_experts_per_tok, len(LAYERS)
    assert stats["moe_tokens_dropped"] == 0
    assert stats["moe_assignments_held"] \
        + stats["moe_assignments_elsewhere"] == layers * S * k
    if send == "all_held":
        assert stats["moe_assignments_elsewhere"] == 0
    if send == "none_held":
        assert stats["moe_assignments_held"] == 0
    held = np.asarray(decoder.held_mask(stats["experts"], cfg.experts_held)).sum()
    assert held == stats["moe_assignments_held"]
    assert metrics().gauge("moe_held_rows_max").value \
        == stats["moe_held_rows_max"]
    # The size each layer's layout takes: the worst case only where the
    # routing fills it.
    assert stats["moe_layout_worst_case"] == {"all_held": layers}.get(send, 0)
    assert (stats["moe_layout_rows_share"] < 1) == (send != "all_held")
    assert metrics().gauge("moe_layout_rows_share").value \
        == stats["moe_layout_rows_share"]
    # The gradient runs whatever the routing (no live tile, or all of them).
    loss, grads = loss_and_grads(params, tokens, cfg)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))
    want_loss = MODEL.ref_loss(to_reference(params, cfg), tokens, hyper(cfg))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)


@pytest.mark.parametrize("held,k", [((0, 16), 2), ((4, 4), 2), ((12, 4), 8),
                                    ((3, 2), 4), ((0, 1), 2)])
def test_route_over_a_share_places_every_held_assignment(held, k):
    """Every skew, the worst included (every token's choices all held)."""
    E, tile, S = 16, 8, 40
    first, count = held
    rng = np.random.default_rng(0)
    for trial in range(4):
        if trial == 0:       # as many held choices a token as can be
            base = (first + np.arange(k)) % E if count >= k else \
                np.concatenate([first + np.arange(count),
                                (first + count + np.arange(k - count)) % E])
            ids = np.tile(base, (S, 1))
        else:
            ids = np.stack([rng.permutation(E)[:k] for _ in range(S)])
        r = gm.route(jnp.asarray(ids, jnp.int32), E, tile, held=held)
        M = r.row_token.shape[0]
        share = count < E
        assert M == (-(-S * min(k, count) // tile) + count + share) * tile
        is_held = (ids >= first) & (ids < first + count)
        dest = np.asarray(r.dest)
        row_token = np.asarray(r.row_token)
        n_live = int(r.n_tiles[0]) * tile
        assert n_live <= M - share * tile
        # A held assignment's row is live, its own, and holds its token.
        rows = dest[is_held]
        assert len(set(rows.tolist())) == rows.size and (rows < n_live).all()
        np.testing.assert_array_equal(
            row_token[rows], np.nonzero(is_held)[0])
        assert (row_token < S).sum() == is_held.sum()
        # A choice elsewhere names a row past every live tile.
        assert (dest[~is_held] >= n_live).all() and (dest < M).all()
        tile_group = np.asarray(r.tile_group)
        for row in rows[:50]:
            t, j = np.argwhere(dest == row)[0]
            assert tile_group[row // tile] == ids[t, j] - first
        # Values ride in and their gradients ride back.
        v = jnp.asarray(rng.normal(size=ids.shape), jnp.float32)
        v = jnp.where(is_held, v, 0.0)
        out, pull = jax.vjp(lambda v: gm.dispatch_values(v, r), v)
        np.testing.assert_array_equal(np.asarray(out)[rows, 0],
                                      np.asarray(v)[is_held])
        assert float(jnp.abs(out).sum()) == pytest.approx(
            float(jnp.abs(v).sum()), rel=1e-6)
        g = jnp.asarray(rng.normal(size=out.shape), jnp.float32)
        back = np.asarray(pull(g)[0])
        np.testing.assert_array_equal(back[is_held], np.asarray(g)[rows, 0])


# -- the size a share's layout takes, chosen on the device -------------------

@pytest.mark.parametrize("S,k,count,E,tile,want", [
    (8192, 8, 32, 128, 256, (33024, 73984)),            # the Trinity cell
    (16384, 8, 16, 64, 256, (53504, 135424)),           # the Mellum2 cell
    (8192, 8, 64, 64, 256, (81920,)),                   # OLMoE: every expert
    (64, 2, 4, 16, 8, (88, 168)),                       # the test presets
    (64, 2, 8, 16, 8, (168, 200)),
    (64, 8, 1, 16, 8, (64, 80)),        # one expert held: a row a token
    (40, 4, 2, 16, 8, (64, 104)),
    (4, 2, 2, 4, 8, (32,)),             # half held, one tile: nothing below
])
def test_the_layouts_sizes_follow_from_the_shapes_alone(S, k, count, E, tile,
                                                        want):
    """1.5 times the balanced share's tiles where that is under the worst
    case, then the worst case, which is ``route``'s own size; each with a
    pad tile a group and the spare tile. No size is another dimension of
    either cell's program (its operations are found by ``[rows,`` in a
    trace)."""
    sizes = gm.layout_rows(S, k, count, E, tile)
    assert sizes == want and list(sizes) == sorted(set(sizes))
    ids = jnp.zeros((S, k), jnp.int32)
    held = (0, count)
    assert gm.route(ids, E, tile, held=held).row_token.shape == (sizes[-1],)
    for rows in sizes:
        assert rows % tile == 0
        assert gm.route(ids, E, tile, held=held,
                        rows=rows).row_token.shape == (rows,)
        assert rows not in (8192, 16384, 65536, 131072, 25024, 12288)
    with pytest.raises(ValueError):
        gm.route(ids, E, tile, held=held, rows=sizes[-1] + tile)
    with pytest.raises(ValueError):
        gm.route(ids, E, tile, held=held, rows=sizes[0] + 1)


def _ids_filling(tiles, S, k, held, E, tile):
    """Expert ids [S, k] whose held assignments fill exactly ``tiles`` live
    tiles: whole tiles of first choices for the first held expert, of second
    choices for the second, one (empty) tile each for the others; every
    other choice goes to an expert elsewhere."""
    first, count = held
    elsewhere = [e for e in range(E) if not first <= e < first + count]
    ids = np.asarray(elsewhere)[
        np.arange(S * k).reshape(S, k) % len(elsewhere)]
    both = tiles - (count - 2)
    a = min(S // tile, both - 1)
    assert a >= 1 and 1 <= both - a <= S // tile
    ids[:a * tile, 0] = first
    ids[:(both - a) * tile, 1] = first + 1
    return jnp.asarray(ids, jnp.int32)


def _layer_and_gradients(layer, h, weights, experts, w, held, E, tile):
    def out(h, weights, w_gate, w_up, w_down):
        y = layer(h, jnp.where((experts >= held[0])
                               & (experts < sum(held)), weights, 0.0),
                  experts, w_gate, w_up, w_down, num_experts=E, tile_m=tile,
                  held=held)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        out, argnums=(0, 1, 2, 3, 4), has_aux=True))(h, weights, *w)
    return (y,) + grads


@pytest.mark.parametrize("fill", [
    "no_assignment", "first_size_full", "worst_case_by_a_tile",
    "every_choice_held"])
def test_every_size_gives_the_worst_case_layouts_values_bit_for_bit(fill):
    """The routed experts' output and every gradient (rows in, router
    weights, the three expert weights) under a routing that fills the first
    size to its last tile and one past it, and at both ends: what the
    worst-case layout gives."""
    S, k, E, tile, d, f = 64, 2, 16, 8, 16, 24
    held = (4, 4)
    sizes = gm.layout_rows(S, k, held[1], E, tile)          # 88, 168
    spare = 1
    tiles, rung = {
        "no_assignment": (None, 0),
        "first_size_full": (sizes[0] // tile - spare, 0),
        "worst_case_by_a_tile": (sizes[0] // tile - spare + 1, 1),
        "every_choice_held": (None, 1)}[fill]
    if fill == "no_assignment":
        experts = jnp.zeros((S, k), jnp.int32)
    elif fill == "every_choice_held":
        experts = jnp.asarray(
            4 + (np.arange(S * k).reshape(S, k) % 2) * 2, jnp.int32)
    else:
        experts = _ids_filling(tiles, S, k, held, E, tile)
    n_tiles = gm.route(experts, E, tile, held).n_tiles
    if tiles is not None:
        assert int(n_tiles[0]) == tiles
    assert int(gm.layout_index(n_tiles, sizes, tile)) == rung
    keys = jax.random.split(KEY, 5)
    h = jax.random.normal(keys[0], (S, d))
    weights = jax.random.uniform(keys[1], (S, k))
    w = (jax.random.normal(keys[2], (held[1], d, f)) * 0.3,
         jax.random.normal(keys[3], (held[1], d, f)) * 0.3,
         jax.random.normal(keys[4], (held[1], f, d)) * 0.3)

    def chosen(*args, num_experts, tile_m, held):
        return gm.routed_experts(*args, num_experts, tile_m, held=held)

    got = _layer_and_gradients(chosen, h, weights, experts, w, held, E, tile)
    def worst_case(h, weights, experts, *w, num_experts, tile_m, held):
        return gm.routed_experts_at(
            h, weights, gm.route(experts, num_experts, tile_m, held), *w,
            tile_m)

    want = _layer_and_gradients(worst_case, h, weights, experts, w, held, E,
                                tile)
    if fill != "no_assignment":
        assert float(jnp.abs(want[0]).max()) > 1e-3
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("send", ["all_held", "none_held", "mixed"])
def test_the_layers_values_do_not_depend_on_the_size_taken(send, monkeypatch):
    """``afmoe.moe`` under a router forced to each end and the seed's: the
    output and the gradient of every leaf of the block (router, bias,
    experts, shared expert) and of the input are those of the program whose
    ladder is the worst case alone, and the program holds one ``cond`` of
    two branches each way."""
    cfg = CFG
    first, count = cfg.experts_held
    blk = afmoe.init_params(cfg, KEY)[f"l{LAYERS[0]}"]
    bias = {"all_held": 10.0, "none_held": -10.0, "mixed": 0.0}[send]
    blk["router_bias"] = blk["router_bias"].at[first:first + count].set(bias)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, cfg.hidden_size))
    experts = afmoe.router(blk, x.reshape(64, -1), cfg)[2]
    sizes = gm.layout_rows(64, cfg.num_experts_per_tok, count,
                           cfg.num_experts, cfg.moe_tile_m)
    taken = int(gm.layout_index(gm.route(
        experts, cfg.num_experts, cfg.moe_tile_m, cfg.experts_held).n_tiles,
        sizes, cfg.moe_tile_m))
    assert len(sizes) == 2
    assert {"all_held": taken == 1, "none_held": taken == 0,
            "mixed": True}[send]

    def values():
        def out(blk, x):
            y = afmoe.moe(blk, x, cfg)
            return jnp.sum(y * jnp.sin(jnp.arange(y.size).reshape(y.shape))), y
        fn = jax.value_and_grad(out, argnums=(0, 1), has_aux=True)
        conds = [e for e in jax.make_jaxpr(fn)(blk, x).jaxpr.eqns
                 if e.primitive.name == "cond"]
        return jax.jit(fn)(blk, x), conds

    got, conds = values()
    assert [len(e.params["branches"]) for e in conds] == [2, 2]
    whole = gm.layout_rows
    monkeypatch.setattr(gm, "layout_rows", lambda *a: whole(*a)[-1:])
    want, conds = values()
    assert not conds
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the flash kernels' window and grouped-query heads ----------------------

def dense_attention(q, k, v, window):
    B, H, T, D = q.shape
    group = H // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / np.sqrt(D)
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    seen = ahead >= 0
    if window is not None:
        seen = seen & (ahead < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


@pytest.mark.parametrize("T,bq,bk,window,H,Hkv", [
    (64, 16, 16, 32, 4, 4),       # equal tiles divide the window
    (64, 16, 16, 24, 4, 2),       # they do not
    (64, 16, 8, 32, 8, 1),        # unequal tiles
    (64, 8, 16, 5, 4, 2),
    (64, 16, 16, 16, 4, 2),       # the window is one tile
    (64, 16, 16, 1, 2, 1),        # a query sees itself alone
    (64, 16, 16, 64, 4, 1),       # window == T: no window
    (64, 16, 16, 100, 4, 4),      # window > T
    (64, 16, 16, None, 32, 4),    # 32 query heads over 4, no window
    (128, 32, 32, 64, 32, 4),     # 32 over 4 with a window
])
def test_windowed_grouped_flash_matches_dense_attention(T, bq, bk, window,
                                                        H, Hkv):
    """Forward, dQ and dK/dV against dense masked float32 attention."""
    ks = jax.random.split(jax.random.PRNGKey(T + H), 4)
    q = jax.random.normal(ks[0], (2, H, T, 16))
    k = jax.random.normal(ks[1], (2, Hkv, T, 16))
    v = jax.random.normal(ks[2], (2, Hkv, T, 16))
    ct = jax.random.normal(ks[3], (2, H, T, 16))

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, block_q=bq, block_k=bk,
                                  window=window)

    out, pull = jax.vjp(flash, q, k, v)
    want, want_pull = jax.vjp(
        lambda q, k, v: dense_attention(q, k, v, window), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=0, atol=5e-6)
    for got, ref_g in zip(pull(ct), want_pull(ct)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref_g),
                                   rtol=0, atol=2e-5)


def _kernel_names(fn, *args):
    return sorted(set(re.findall(r"tepdist_flash_\w+?__c[01]__s[\d.e-]+__h\d+"
                                 r"(?:__w\d+)?(?:__kv\d+)?",
                                 str(jax.make_jaxpr(fn)(*args)))))


def test_kernel_names_are_unchanged_without_a_window_and_carry_one_with():
    """The names the benchmark's readers match (``_moe.py:_FLASH``,
    ``_flash.py``): letter for letter as before where the call has no
    window and equal head counts; the window and the key/value heads after
    the fields every call has where it has them."""
    q = jnp.zeros((1, 4, 64, 16))
    kv = jnp.zeros((1, 2, 64, 16))

    def grad_of(**kw):
        def f(q, k, v):
            return jnp.sum(fa.flash_attention(q, k, v, block_q=16,
                                              block_k=16, **kw))
        return jax.grad(f, argnums=(0, 1, 2))

    assert _kernel_names(grad_of(), q, q, q) == [
        f"tepdist_flash_{w}__c1__s0.25__h4" for w in ("dkv", "fwd")]
    assert _kernel_names(grad_of(window=64), q, q, q) == [
        f"tepdist_flash_{w}__c1__s0.25__h4" for w in ("dkv", "fwd")]
    assert _kernel_names(grad_of(window=32), q, kv, kv) == [
        f"tepdist_flash_{w}__c1__s0.25__h4__w32__kv2"
        for w in ("dkv", "fwd")]
    assert _kernel_names(grad_of(window=32), q, q, q) == [
        f"tepdist_flash_{w}__c1__s0.25__h4__w32" for w in ("dkv", "fwd")]
    # The plain call's program is the old one: three operands forward, six
    # backward, no window in any kernel's parameters.
    text = str(jax.make_jaxpr(grad_of())(q, q, q))
    assert "window" not in text.replace("window=None", "")
    with pytest.raises(ValueError):
        fa.flash_attention(q, q, q, causal=False, window=8)
    with pytest.raises(ValueError):
        fa.flash_attention(q, jnp.zeros((1, 3, 64, 16)),
                           jnp.zeros((1, 3, 64, 16)))


@pytest.mark.parametrize("T,window,H,Hkv,dtype", [
    (64, None, 4, 4, jnp.float32),       # plain causal
    (64, 24, 4, 4, jnp.bfloat16),        # a window
    (64, None, 8, 2, jnp.bfloat16),      # fewer key/value heads
    (128, 32, 32, 4, jnp.bfloat16),      # both
    (50, None, 4, 2, jnp.float32),       # no tile divides T: padded to 128
])
def test_attention_from_a_saved_forward_is_flash_attention(T, window, H, Hkv,
                                                           dtype):
    """``flash_attention_kept``: the forward kernel alone gives what
    ``flash_attention`` returns; attention from that ``(o, lse)`` returns
    ``o`` without any kernel and has ``flash_attention``'s gradients bit for
    bit, from the same two backward kernels."""
    ks = jax.random.split(jax.random.PRNGKey(T + H), 4)
    q = jax.random.normal(ks[0], (2, H, T, 16)).astype(dtype)
    k = jax.random.normal(ks[1], (2, Hkv, T, 16)).astype(dtype)
    v = jax.random.normal(ks[2], (2, Hkv, T, 16)).astype(dtype)
    ct = jax.random.normal(ks[3], (2, H, T, 16)).astype(dtype)
    tiles = {"block_q": 16, "block_k": 16} if T % 16 == 0 else {}

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, window=window, **tiles)

    def part(forward):
        return lambda q, k, v: fa.flash_attention_kept(
            q, k, v, forward, window=window, **tiles)

    want, want_pull = jax.vjp(flash, q, k, v)
    o, lse = part(())(q, k, v)
    assert lse.shape == (2, H, T) and lse.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want))
    got, pull = jax.vjp(part((o, lse)), q, k, v)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for a, b in zip(pull(ct), want_pull(ct), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    assert "pallas_call" not in str(jax.make_jaxpr(part((o, lse)))(q, k, v))
    grads = jax.grad(lambda *a: jnp.sum(part((o, lse))(*a)), argnums=(0, 1, 2))
    assert [n.split("__")[0] for n in _kernel_names(grads, q, k, v)] == [
        "tepdist_flash_dkv"]
    assert set(_kernel_names(grads, q, k, v)) < set(_kernel_names(
        jax.grad(lambda *a: jnp.sum(flash(*a)), argnums=(0, 1, 2)), q, k, v))


def test_a_flash_call_in_an_inner_trace_hands_nothing_to_the_walk():
    """A ``KeptForward`` takes part in the calls traced where it was
    entered: one inside a ``lax.cond`` or an inner loop can hand no array
    out and runs whole, and ``nothing_kept`` switches the hand-over off."""
    q = jnp.ones((1, 2, 64, 16))

    def flash(q):
        return fa.flash_attention(q, q, q, block_q=16, block_k=16)

    with fa.KeptForward() as keep:
        here = flash(q)
        inner = jax.lax.cond(q[0, 0, 0, 0] > 0, flash, lambda q: q, q)
        with fa.nothing_kept():
            off = flash(q)
    assert len(keep.kept) == 1
    for o in (inner, off, keep.kept[0][0]):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(here))
    with fa.KeptForward(keep.kept):
        np.testing.assert_array_equal(np.asarray(flash(q)), np.asarray(here))
    with pytest.raises(ValueError):
        fa.flash_attention_kept(jnp.ones((1, 2, 50, 16)), q[:, :, :50],
                                q[:, :, :50], (), causal=False)


def test_the_seq_planner_leaves_a_windowed_kernel_alone():
    from tepdist_tpu.graph.jaxpr_graph import trace_graph
    from tepdist_tpu.parallel.attention_motif import detect_motifs
    q = jnp.zeros((1, 4, 64, 16))

    def f(window):
        return lambda q: fa.flash_attention(q, q, q, block_q=16, block_k=16,
                                            window=window)

    plain = trace_graph(f(None), q)[0]
    windowed = trace_graph(f(32), q)[0]
    assert len(detect_motifs(plain)) == 1
    assert detect_motifs(windowed) == []
