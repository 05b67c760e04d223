"""Dropless token-choice dispatch for a routed expert layer.

``route`` lays the ``S x k`` (token, expert) assignments out **tile-aligned**
by expert: every expert's rows start at a multiple of ``tile_m`` and are
padded to whole tiles (an expert nobody chose keeps one tile), which is the
layout ``ops/pallas/grouped_matmul.py`` multiplies without masks. No token is
dropped whatever the skew; the static row count
``(ceil(S*k / tile_m) + E) * tile_m`` is the worst case, and the tiles past
the live ones are skipped by the kernels. There is no ``[S, E, C]`` tensor
and no capacity.

``dispatch`` gathers each row's token; ``combine`` gathers each token's k
rows and sums them. Each is the other's transpose, and each is the other's
custom VJP: the transpose of a permutation is the inverse permutation, which
``route`` already holds, while autodiff's scatter-adds of 2048-wide rows are
the slow way around on a TPU. ``dispatch_values`` carries one value an
assignment (the router's weights) into the layout.

**What a pad row holds: zeros, and nothing selects them.** ``dispatch`` (and
``combine``'s backward) gather from the array with one zero row appended,
and a pad's index names that row. A select over the gathered ``[rows, d]``
array (``jnp.take(..., mode="fill")``) costs twice the gather it follows
(1.02 ms against 0.52 at the OLMoE cell's sizes; PERF.md section 5), while
the appended row costs a copy of the ``[S, d]`` source (0.014 ms). The copy
also earns its keep: it is produced where it is used, so the compiler holds
it in fast memory and the gather runs at 6.3 ns a row; the cotangent that
the backward layer loop carries in HBM, gathered as it stands, took 34 ns a
row (2.7 ms a call for 0.52). ``dispatch_values`` gives a pad row exactly 0
too, so with the caller's ``act = silu(gate) * up * row_weight``
(``models/olmoe.py``) a pad is zero on both sides of every sum over rows:
the kernels' outputs on pads, each weight gradient's products, and the rows
``combine`` and ``dispatch``'s backward never read (they go by ``dest``).

The index path is sorts, not gathers: a gather of single 4-byte elements
runs at 8 ns an element on a v5e (0.67 ms for the layout's 81,920 rows,
whatever the size of the table) where a stable sort of 81,920 pairs takes
0.08 ms (0.13 with two more operands riding).
So the layout is one stable sort of the assignments together with
``E x tile_m`` pad candidates, of which each expert uses as many as fill its
last tile; its inverse is a second sort; and values ride into the layout, and
their gradients out of it, through a sort by the same keys.

**A layer that holds a share of the experts** (expert parallelism's rank:
``held=(first, count)`` of the router's ``num_experts``) lays out only the
assignments to its own experts, over ``count`` groups. The router still
chooses among all experts; a choice of an expert elsewhere sorts past the
live tiles, gets no row, and its ``dest`` names the last row of a spare
tile the layout keeps for it, which no group ever reaches: the kernels
write zeros past the live tiles, so ``combine`` adds nothing for it and
``dispatch``'s backward nothing either. Dropless stays the guarantee: the
static row count is the worst case, ``min(k, count)`` rows a token plus one
tile a group, so every assignment to a held expert reaches a row whatever
the routing. ``(0, num_experts)`` is the whole layer, the same operations as
without ``held``. Nothing here stands in for the other ranks or for the
exchange with them.

``routed_experts`` is the layer around the layout: rows in, the three
grouped matmuls with the gated activation between them, rows out.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from tepdist_tpu.ops.pallas.grouped_matmul import grouped_matmul


class Routing(NamedTuple):
    # A pad row names no token and no assignment: one past the last, which
    # is the zero row ``dispatch`` appends to what it gathers from, and the
    # zeros ``dispatch_values`` appends to what it sorts. So a pad holds
    # zeros in the rows and in the router's weight, and no ``[M, d]`` array
    # is ever masked (the module docstring's invariant).
    row_token: jax.Array        # [M] token of each row; S (out of range) = pad
    row_assignment: jax.Array   # [M] assignment (token * k + slot); S * k = pad
    #                             (a share of the experts: the sorted order
    #                             whole, a choice elsewhere past the live
    #                             tiles; the values' way back)
    dest: jax.Array             # [S, k] row of each assignment (a choice of
    #                             an expert elsewhere: a row no tile reaches)
    tile_group: jax.Array       # [M / tile_m] expert of each row tile
    n_tiles: jax.Array          # [1] live row tiles
    group_sizes: jax.Array      # [E] assignments per expert
    sort_key: jax.Array         # [M] what sorts assignments, then pad
    #                             candidates, into the layout (stable)


def _sorted_by(key, value):
    return jax.lax.sort((key, value), num_keys=1, is_stable=True)[1]


def route(expert_ids, num_experts: int, tile_m: int, held=None) -> Routing:
    """The tile-aligned layout of ``expert_ids`` [S, k]; with ``held =
    (first, count)`` that of the assignments to experts ``first .. first +
    count - 1`` alone, over ``count`` groups (the module docstring)."""
    S, k = expert_ids.shape
    first, E = held or (0, num_experts)
    if first < 0 or E < 1 or first + E > num_experts:
        raise ValueError(f"route: held={held} of {num_experts} experts")
    share = E < num_experts
    A = S * k
    # Rows: min(k, E) a token at most, a tile's pads a group, and under a
    # share the spare tile; sorted are all A choices with the candidates.
    M = (-(-S * min(k, E) // tile_m) + E + share) * tile_m
    L = max(M, A + E * tile_m)
    flat = expert_ids.reshape(A).astype(jnp.int32)
    if first:
        flat = flat - first
    group_sizes = jnp.sum(
        flat[:, None] == jnp.arange(E, dtype=flat.dtype), axis=0,
        dtype=jnp.int32)
    tiles = jnp.maximum(1, -(-group_sizes // tile_m))
    tile_end = jnp.cumsum(tiles)
    # Every tile against every expert's last tile at once: a binary search
    # is a loop of seven tiny operations on the device.
    tile_group = jnp.minimum(jnp.searchsorted(
        tile_end, jnp.arange(M // tile_m, dtype=jnp.int32), side="right",
        method="compare_all"), E - 1).astype(jnp.int32)

    # Expert e's assignments sort under key 2e, the pads that fill its last
    # tile under 2e + 1, and the candidates nobody needs (with the rows past
    # the live tiles) under 2E: per-expert values meet the [E, tile_m]
    # candidates by broadcast, never by a look-up a row.
    pads = tiles * tile_m - group_sizes                       # 0 .. tile_m
    experts = jnp.arange(E, dtype=jnp.int32)[:, None]
    pad_key = jnp.where(
        jnp.arange(tile_m, dtype=jnp.int32)[None, :] < pads[:, None],
        2 * experts + 1, 2 * E)
    # A choice of an expert elsewhere sorts with the unused candidates.
    choice_key = jnp.where((flat >= 0) & (flat < E), 2 * flat, 2 * E) \
        if share else 2 * flat
    sort_key = jnp.concatenate([
        choice_key, pad_key.reshape(E * tile_m),
        jnp.full((L - A - E * tile_m,), 2 * E, jnp.int32)])
    row_assignment = _sorted_by(sort_key, jnp.minimum(
        jnp.arange(L, dtype=jnp.int32), A))
    # The inverse permutation: the pads (all A) sort past the assignments.
    dest = _sorted_by(row_assignment, jnp.arange(L, dtype=jnp.int32))[:A]
    row_token = (row_assignment[:M] if L > M else row_assignment) // k
    if share:
        live = jnp.arange(M, dtype=jnp.int32) < tile_end[-1] * tile_m
        row_token = jnp.where(live, row_token, S)
        dest = jnp.minimum(dest, M - 1)
    return Routing(
        row_token=row_token, row_assignment=row_assignment,
        dest=dest.reshape(S, k), tile_group=tile_group,
        n_tiles=tile_end[-1:].astype(jnp.int32), group_sizes=group_sizes,
        sort_key=sort_key)


def _rows(x, source):
    """x [S, d] -> [M, d]: row r is x[source[r]], zeros where the row is a
    pad (its source S: the zero row appended here)."""
    return jnp.pad(x, ((0, 1), (0, 0))).at[source].get(
        mode="promise_in_bounds")


def _sum_of_rows(y, dest):
    """y [M, d] -> [S, d]: the float32 sum of each token's rows, one of the
    k gathers at a time. Fewer, wider gathers buy nothing: a row out of the
    layout costs 34 ns however many a gather fetches (its source, 320 MiB,
    stays in HBM; the rows into the layout come from a 32 MiB source the
    compiler holds in fast memory, 6.3 ns a row), and the gathered
    ``[k, S, d]`` array has to be read again to be summed: at the OLMoE
    cell's sizes 2 gathers of 4 slots made the step 39.7 ms longer and 1 of
    8 (256 MiB in bf16) 34.0 ms, of 1,940.6 (PERF.md section 6, PR 30)."""
    total = jnp.zeros((dest.shape[0], y.shape[1]), jnp.float32)
    for j in range(dest.shape[1]):
        total = total + y[dest[:, j]].astype(jnp.float32)
    return total.astype(y.dtype)


@jax.custom_vjp
def dispatch(x, source, dest):
    """Into the tile-aligned layout: x [S, d] -> [M, d] by ``source`` [M]
    (``route``'s ``row_token``); ``dest`` [S, k] holds the rows that name
    each x row. Pad rows are zero."""
    return _rows(x, source)


def _dispatch_fwd(x, source, dest):
    return _rows(x, source), dest


def _dispatch_bwd(dest, g):
    return _sum_of_rows(g, dest), None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, source, dest):
    """Out of the layout: each token's k rows summed, y [M, d] -> [S, d];
    ``dispatch``'s transpose."""
    return _sum_of_rows(y, dest)


def _combine_fwd(y, source, dest):
    return _sum_of_rows(y, dest), source


def _combine_bwd(source, g):
    return _rows(g, source), None, None


combine.defvjp(_combine_fwd, _combine_bwd)


@jax.custom_vjp
def dispatch_values(v, r: Routing):
    """One value an assignment into the layout: v [S, k] -> [M, 1] by
    ``r.sort_key``, **exactly 0 on every pad row**. The gradient of an
    assignment's value is its row's (``r.row_assignment`` sorts the rows
    back). Under a share of the experts the value of a choice elsewhere
    lands past the live tiles: the caller hands in 0 for it."""
    pads = jnp.zeros((r.sort_key.shape[0] - v.size,), v.dtype)
    rows = _sorted_by(r.sort_key, jnp.concatenate([v.reshape(-1), pads]))
    if rows.shape[0] > r.row_token.shape[0]:
        rows = rows[:r.row_token.shape[0]]
    return rows[:, None]


def _dispatch_values_fwd(v, r):
    return dispatch_values(v, r), (r.row_assignment, r.dest)


def _dispatch_values_bwd(res, g):
    row_assignment, dest = res
    g = g[:, 0]
    if row_assignment.shape[0] > g.shape[0]:
        g = jnp.pad(g, (0, row_assignment.shape[0] - g.shape[0]))
    back = _sorted_by(row_assignment, g)[:dest.size]
    return back.reshape(dest.shape), None


dispatch_values.defvjp(_dispatch_values_fwd, _dispatch_values_bwd)


def _gated(gate, up, row_weight):
    g = gate.astype(jnp.float32)
    return (jax.nn.silu(g) * up.astype(jnp.float32)
            * row_weight).astype(gate.dtype)


gated = jax.custom_vjp(_gated)
gated.__doc__ = """``silu(gate) * up * row_weight`` in float32, back in
gate's dtype. The backward recomputes from the three operands, which is all
it keeps: autodiff would keep the float32 intermediates, [rows, f] each."""


def _gated_fwd(gate, up, row_weight):
    return _gated(gate, up, row_weight), (gate, up, row_weight)


def _gated_bwd(res, ct):
    gate, up, row_weight = res
    g, u, ct = (t.astype(jnp.float32) for t in (gate, up, ct))
    sig = jax.nn.sigmoid(g)
    silu = g * sig
    d_gate = ct * u * row_weight * sig * (1.0 + g * (1.0 - sig))
    d_up = ct * silu * row_weight
    d_weight = jnp.sum(ct * silu * u, axis=-1, keepdims=True)
    return (d_gate.astype(gate.dtype), d_up.astype(up.dtype),
            d_weight.astype(row_weight.dtype))


gated.defvjp(_gated_fwd, _gated_bwd)


def routed_experts(h, weights, experts, w_gate, w_up, w_down,
                   num_experts: int, tile_m: int, held=None):
    """The routed SwiGLU experts' part of a layer: h [S, d], the router's
    ``weights`` and ``experts`` [S, k] over ``num_experts``, expert weights
    ``w_gate``, ``w_up`` [G, d, f] and ``w_down`` [G, f, d] -> [S, d], the
    sum over each token's choices j of ``weights_j . w_down[e_j] (silu(
    w_gate[e_j] h) * w_up[e_j] h)``. With ``held = (first, count)`` the
    layer holds experts ``first .. first + count - 1`` (``G = count``) and a
    choice of any other contributes nothing: the caller hands in weight 0
    for it. No token is dropped (``route``)."""
    with jax.named_scope("moe_dispatch"):
        r = route(experts, num_experts, tile_m, held)
        rows = dispatch(h, r.row_token, r.dest)
        row_weight = dispatch_values(weights, r)
    with jax.named_scope("moe_experts"):
        def gmm(a, w):
            return grouped_matmul(a, w, r.tile_group, r.n_tiles, tile_m)
        # The router's weight goes on the row before the down projection
        # (W (w a) = w (W a)): the projected rows then need no keeping for
        # the weight's gradient, 320 MiB a micro batch at the 1B-7B sizes.
        # On a pad row it is exactly 0, as the row itself is.
        act = gated(gmm(rows, w_gate), gmm(rows, w_up), row_weight)
        out_rows = gmm(act, w_down)
    with jax.named_scope("moe_combine"):
        return combine(out_rows, r.row_token, r.dest)
