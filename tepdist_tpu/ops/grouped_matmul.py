"""Dropless token-choice dispatch for a routed expert layer.

``route`` lays the ``S x k`` (token, expert) assignments out **tile-aligned**
by expert: every expert's rows start at a multiple of ``tile_m`` and are
padded to whole tiles (an expert nobody chose keeps one tile), which is the
layout ``ops/pallas/grouped_matmul.py`` multiplies without masks. No token is
dropped whatever the skew: the row count is static and large enough for the
routing at hand, ``(ceil(S*k / tile_m) + E) * tile_m`` for a layer that holds
every expert (at most a tile's pads a group are dead), one of a few sizes
chosen on the device for a layer that holds a share (below), and the tiles
past the live ones are skipped by the kernels. There is no ``[S, E, C]``
tensor and no capacity.

``dispatch`` gathers each row's token; ``combine`` gathers each token's k
rows and sums them. Each is the other's transpose, and each is the other's
custom VJP: the transpose of a permutation is the inverse permutation, which
``route`` already holds, while autodiff's scatter-adds of 2048-wide rows are
the slow way around on a TPU. ``dispatch_values`` carries one value an
assignment (the router's weights) into the layout. The rows out of the
layout (``combine``, and ``dispatch``'s backward) are ``k`` XLA gathers
for a whole layer and, under a share of the experts, a row-copy kernel that
fetches the rows under the layout's live bound and none for a choice
elsewhere (``_rows_out``, ``ops/pallas/rows_sum.py``).

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
``dispatch``'s backward nothing either. ``(0, num_experts)`` is the whole
layer, the same operations as without ``held``. Nothing here stands in for
the other ranks or for the exchange with them.

**How many rows a share's layout has is chosen on the device.** Any token
*may* send all its choices to held experts, so the worst case is ``min(k,
count)`` rows a token plus one tile a group and the spare tile; a router
near balance fills ``count / num_experts`` of that, and every operation on a
``[rows, ...]`` array (the gathers, the gated activation, the input
gradients' sum, the kernels' zero blocks past the live tiles) pays for all
of it. ``layout_rows`` derives the static sizes a layout may take from ``(S,
k, count, num_experts, tile_m)`` alone: 1.5 times the balanced share's
tiles, then the worst case, each with the groups' pad tiles and the
spare tile. ``routed_experts`` lays the assignments out once (the sorts
are as long whatever the rows: they carry all ``S * k`` choices), reads the
live tile count ``n_tiles`` off the layout, and runs everything wide (the
gather into the layout, the three grouped matmuls with the gated activation
between them, ``combine``, and through autodiff their backward) on the
layout cut to the smallest size that holds the live tiles and the spare
tile (``layout_index``, ``at_rows``), inside that size's branch of a
``switch``. Every branch is in the one compiled program: no host sync, no
callback, no capacity. **Dropless stays the guarantee:** the last size is
the worst case, so a routing that overfills the smaller ones takes the
branch with a row for every assignment whatever the routing, and the values
are the worst case's bit for bit (a live row holds the same sums in the same
order; the rows that went held zeros). ``route`` takes the size as
``rows=``; alone it lays out the worst case.

``routed_experts`` is the layer around the layout: rows in, the three
grouped matmuls with the gated activation between them, rows out. An expert
with **no gate matrix** (``w_gate=None``: two stacks, ``relu(up)^2`` between
them) runs on the same routing, layout, dispatch and combine.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tepdist_tpu.ops.pallas import _interpret
from tepdist_tpu.ops.pallas.grouped_matmul import (
    ExpertStack,
    forward,
    grouped_matmul,
    input_grad,
    relu_squared,
    swiglu,
    weight_grad,
)
from tepdist_tpu.ops.pallas.rows_sum import rows_sum


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
    live_rows: Optional[jax.Array]  # [1] ``n_tiles * tile_m`` under a share
    #                             of the experts: a ``dest`` at or past it is
    #                             a choice elsewhere (``_rows_out`` skips
    #                             it); None for a whole layer, which has none
    group_sizes: jax.Array      # [E] assignments per expert
    sort_key: jax.Array         # [M] what sorts assignments, then pad
    #                             candidates, into the layout (stable)


def _sorted_by(key, value):
    return jax.lax.sort((key, value), num_keys=1, is_stable=True)[1]


def _held(num_experts: int, held):
    first, count = held or (0, num_experts)
    if first < 0 or count < 1 or first + count > num_experts:
        raise ValueError(f"route: held={held} of {num_experts} experts")
    return first, count


def layout_rows(S: int, k: int, count: int, num_experts: int,
                tile_m: int) -> Tuple[int, ...]:
    """The static row counts a layout of ``S x k`` choices over ``count``
    held of ``num_experts`` experts may take, ascending; the last is the
    worst case (``min(k, count)`` rows a token, a tile's pads a group and,
    under a share, the spare tile). A layer that holds every expert has that
    one size. A share has one more below it, where that is less: 1.5 times
    the tiles a balanced router's ``S * k * count / num_experts``
    assignments fill, with the same pad tiles and spare tile (the module
    docstring). One size below the worst case and not two: a second (2.25
    times the balanced share) was never taken at either cell's routing and
    cost 4.5 s of every set-up in tracing and 0.9e9 bytes of residual slots
    (PERF.md section 6, PR 34)."""
    worst = -(-S * min(k, count) // tile_m)
    if count == num_experts:
        return ((worst + count) * tile_m,)
    balanced = -(-S * k * count // (num_experts * tile_m))
    tiles = sorted({min(-(-3 * balanced // 2), worst), worst})
    return tuple((t + count + 1) * tile_m for t in tiles)


def layout_index(n_tiles, sizes, tile_m: int):
    """Which of ``sizes`` (``layout_rows``) a routing with ``n_tiles`` [1]
    live tiles takes: the smallest that holds them and the spare tile."""
    need = n_tiles[0] + 1
    return sum((need > m // tile_m).astype(jnp.int32) for m in sizes[:-1])


def route(expert_ids, num_experts: int, tile_m: int, held=None,
          rows=None) -> Routing:
    """The tile-aligned layout of ``expert_ids`` [S, k]; with ``held =
    (first, count)`` that of the assignments to experts ``first .. first +
    count - 1`` alone, over ``count`` groups (the module docstring).
    ``rows``: the layout's static row count, the worst case (its default and
    upper limit) or one of ``layout_rows``' smaller sizes, which the caller
    has chosen to hold ``n_tiles`` and the spare tile (``layout_index``):
    the worst case's layout cut to its first ``rows`` rows (``at_rows``)."""
    S, k = expert_ids.shape
    first, E = _held(num_experts, held)
    share = E < num_experts
    A = S * k
    # Rows: min(k, E) a token at most, a tile's pads a group, and under a
    # share the spare tile; sorted are all A choices with the candidates.
    M = (-(-S * min(k, E) // tile_m) + E + share) * tile_m
    if rows is not None and (
            rows % tile_m or not (E + share) * tile_m <= rows <= M):
        raise ValueError(f"route: rows={rows} of at most {M}, "
                         f"tile_m={tile_m}, held={held}")
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
    r = Routing(
        row_token=row_token, row_assignment=row_assignment,
        dest=dest.reshape(S, k), tile_group=tile_group,
        n_tiles=tile_end[-1:].astype(jnp.int32),
        live_rows=(tile_end[-1:] * tile_m).astype(jnp.int32) if share
        else None,
        group_sizes=group_sizes, sort_key=sort_key)
    return r if rows is None else at_rows(r, rows, tile_m)


def at_rows(r: Routing, rows: int, tile_m: int) -> Routing:
    """A share's layout cut to its first ``rows`` rows, a whole number of
    tiles that holds ``r.n_tiles`` live tiles and one more: the rows and
    tiles past them go, and a choice of an expert elsewhere names the last
    row that is left, which is in a tile no group reaches. The sorted order
    (``row_assignment``, ``sort_key``) stays whole: it is how values ride in
    and out, whatever the rows."""
    if rows == r.row_token.shape[0]:
        return r
    return r._replace(row_token=r.row_token[:rows],
                      dest=jnp.minimum(r.dest, rows - 1),
                      tile_group=r.tile_group[:rows // tile_m])


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


_KERNEL_CALLS: contextvars.ContextVar[Optional[dict]] = \
    contextvars.ContextVar("tepdist_expert_kernel_calls", default=None)


@contextlib.contextmanager
def counting_kernel_calls():
    """Yields a dict to which every :func:`routed_experts` traced inside
    adds the kernel calls it makes a micro batch once a walk differentiates
    it. ``rows_sum``: of the row-copy kernel, 2 (its ``combine``'s and its
    ``dispatch``'s backward's), 0 where the layer keeps the XLA gathers.
    ``stack_in_place``: of the grouped matmuls that read their weights out
    of the layers' stack (:class:`ExpertStack`), 12 (three matmuls, each
    forward, recomputed, its input's and its weight's gradient; 8 for an
    expert of two matrices), 0 where the layer was handed slices.
    ``epilogue``: of the grouped matmuls that carry an epilogue
    (:func:`activation`), 2 (the up projection's activation in the walk's
    first forward, which nothing differentiates, and its input gradient's
    addend; 1 for an expert of two matrices, which has one input gradient).
    Who walks a stack of layers multiplies by them
    (``models/layers.py:scan_blocks``: the gauges ``moe_rows_sum_calls``,
    ``moe_stack_in_place_calls`` and ``moe_epilogue_calls``)."""
    calls = {"rows_sum": 0, "stack_in_place": 0, "epilogue": 0}
    token = _KERNEL_CALLS.set(calls)
    try:
        yield calls
    finally:
        _KERNEL_CALLS.reset(token)


def _rows_out(y, dest, live_rows):
    """``_sum_of_rows(y, dest)``, bit for bit. Handed the layout's live
    bound (``Routing.live_rows``: a share of the experts) it is the row-copy
    kernel (``ops/pallas/rows_sum.py``), which fetches the rows under the
    bound and no row for a choice elsewhere, three choices in four of one
    expert-parallel rank of four; a whole layer (None) has no choice to
    skip and keeps the ``k`` XLA gathers: alone at OLMoE's shape the kernels
    take 36 ns a row with the relayout of ``y`` (335 MB more beside a peak
    of 14.5e9 bytes) and the gathers 39, 34 inside the step (PERF.md
    section 6, PR 39)."""
    if live_rows is None:
        return _sum_of_rows(y, dest)
    return rows_sum(y, dest, live_rows)


@jax.custom_vjp
def dispatch(x, source, dest, live_rows=None):
    """Into the tile-aligned layout: x [S, d] -> [M, d] by ``source`` [M]
    (``route``'s ``row_token``); ``dest`` [S, k] holds the rows that name
    each x row and ``live_rows`` the layout's live bound (``_rows_out``):
    the backward's. Pad rows are zero."""
    return _rows(x, source)


def _dispatch_fwd(x, source, dest, live_rows):
    return _rows(x, source), (dest, live_rows)


def _dispatch_bwd(res, g):
    return _rows_out(g, *res), None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, source, dest, live_rows=None):
    """Out of the layout: each token's k rows summed, y [M, d] -> [S, d]
    (``_rows_out``); ``dispatch``'s transpose."""
    return _rows_out(y, dest, live_rows)


def _combine_fwd(y, source, dest, live_rows):
    return _rows_out(y, dest, live_rows), source


def _combine_bwd(source, g):
    return _rows(g, source), None, None, None


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
    return swiglu(gate.astype(jnp.float32), up.astype(jnp.float32),
                  row_weight).astype(gate.dtype)


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


def _relu2(up, row_weight):
    return relu_squared(up.astype(jnp.float32), row_weight).astype(up.dtype)


relu2 = jax.custom_vjp(_relu2)
relu2.__doc__ = """``relu(up)^2 * row_weight`` in float32, back in up's
dtype: the activation of an expert without a gate matrix. As :func:`gated`,
the backward recomputes from the operands, which is all it keeps."""


def _relu2_fwd(up, row_weight):
    return _relu2(up, row_weight), (up, row_weight)


def _relu2_bwd(res, ct):
    up, row_weight = res
    u = jnp.maximum(up.astype(jnp.float32), 0.0)
    ct = ct.astype(jnp.float32)
    return ((ct * 2.0 * u * row_weight).astype(up.dtype),
            jnp.sum(ct * u * u, axis=-1, keepdims=True).astype(
                row_weight.dtype))


relu2.defvjp(_relu2_fwd, _relu2_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def activation(x, w_gate, w_up, row_weight, tile_group, n_tiles, tile_m):
    """The experts' first half over the layout's rows ``x`` [M, d]:
    ``gated(x w_gate, x w_up, row_weight)``, with ``w_gate`` None
    ``relu2(x w_up, row_weight)`` -> [M, f]; the weights ``[E, d, f]`` or
    ``ExpertStack``s, differentiable as ``grouped_matmul``'s are.

    **Not differentiated** (a rematerialised walk's first forward) the
    activation is the up projection's epilogue, from the product's float32
    tile: ``up`` is never written to HBM, read back and written again as
    ``act``. **Differentiated**, the forward is the composition above as
    it stands, two plain kernels and XLA's activation: the backward needs
    ``up``, and the compiler folds the recomputed activation into the
    backward's one fusion, where an epilogue would add a pass. The
    backward is written out for the sake of ``x``, which feeds both
    products: the up projection's input gradient adds the gate's inside
    the kernel (``input_grad(add=)``: the sum in float32, rounded once,
    over the first's buffer), where autodiff sums the two rounded
    cotangents in a pass of its own, five passes over ``[M, d]`` for
    three."""
    tiles = (tile_group, n_tiles, tile_m)
    if w_gate is None:
        return forward(x, w_up, *tiles, act=(row_weight,))
    return forward(x, w_up, *tiles,
                   act=(forward(x, w_gate, *tiles), row_weight))


def _activation_fwd(x, w_gate, w_up, row_weight, tile_group, n_tiles, tile_m):
    tiles = (tile_group, n_tiles, tile_m)
    gate = None if w_gate is None else forward(x, w_gate, *tiles)
    up = forward(x, w_up, *tiles)
    act = _relu2(up, row_weight) if w_gate is None \
        else _gated(gate, up, row_weight)
    return act, (x, w_gate, w_up, row_weight, gate, up, tile_group, n_tiles)


def _activation_bwd(tile_m, res, ct):
    x, w_gate, w_up, row_weight, gate, up, *tiles = res
    dx = dw_gate = None
    if w_gate is None:
        d_up, d_weight = _relu2_bwd((up, row_weight), ct)
    else:
        d_gate, d_up, d_weight = _gated_bwd((gate, up, row_weight), ct)
        dx = input_grad(d_gate, w_gate, *tiles, tile_m)
        dw_gate = weight_grad(x, d_gate, w_gate, *tiles, tile_m)
    return (input_grad(d_up, w_up, *tiles, tile_m, add=dx), dw_gate,
            weight_grad(x, d_up, w_up, *tiles, tile_m), d_weight, None, None)


activation.defvjp(_activation_fwd, _activation_bwd)


def routed_experts_at(h, weights, r: Routing, w_gate, w_up, w_down,
                      tile_m: int):
    """``routed_experts`` over the layout ``r`` as it is handed in: the
    worst case's, or that cut to a size that holds the routing
    (``at_rows``)."""
    with jax.named_scope("moe_dispatch"):
        x = dispatch(h, r.row_token, r.dest, r.live_rows)
        row_weight = dispatch_values(weights, r)
    with jax.named_scope("moe_experts"):
        # The router's weight goes on the row before the down projection
        # (W (w a) = w (W a)): the projected rows then need no keeping for
        # the weight's gradient, 320 MiB a micro batch at the 1B-7B sizes.
        # On a pad row it is exactly 0, as the row itself is.
        act = activation(x, w_gate, w_up, row_weight, r.tile_group,
                         r.n_tiles, tile_m)
        out_rows = grouped_matmul(act, w_down, r.tile_group, r.n_tiles,
                                  tile_m)
    with jax.named_scope("moe_combine"):
        return combine(out_rows, r.row_token, r.dest, r.live_rows)


def routed_experts(h, weights, experts, w_gate, w_up, w_down,
                   num_experts: int, tile_m: int, held=None):
    """The routed SwiGLU experts' part of a layer: h [S, d], the router's
    ``weights`` and ``experts`` [S, k] over ``num_experts``, expert weights
    ``w_gate``, ``w_up`` [G, d, f] and ``w_down`` [G, f, d] -> [S, d], the
    sum over each token's choices j of ``weights_j . w_down[e_j] (silu(
    w_gate[e_j] h) * w_up[e_j] h)``; with ``w_gate`` None, of ``weights_j .
    w_down[e_j] relu(w_up[e_j] h)^2`` (an expert of two matrices). With ``held = (first, count)`` the
    layer holds experts ``first .. first + count - 1`` (``G = count``) and a
    choice of any other contributes nothing: the caller hands in weight 0
    for it. No token is dropped (``route``). A share of the experts is laid
    out once and runs everything wide at the smallest of ``layout_rows``'
    sizes that its routing fits, chosen by a ``switch`` on the device; the
    whole layer has one size and no ``switch``."""
    with jax.named_scope("moe_dispatch"):
        r = route(experts, num_experts, tile_m, held)
    calls = _KERNEL_CALLS.get()
    if calls is not None:
        calls["rows_sum"] += 2 * (r.live_rows is not None)
        calls["stack_in_place"] += 4 * sum(
            isinstance(w, ExpertStack) for w in (w_gate, w_up, w_down))
        calls["epilogue"] += 1 + (w_gate is not None)
    sizes = layout_rows(*experts.shape, _held(num_experts, held)[1],
                        num_experts, tile_m)
    if len(sizes) == 1:
        return routed_experts_at(h, weights, r, w_gate, w_up, w_down, tile_m)
    return _switch(
        layout_index(r.n_tiles, sizes, tile_m),
        [lambda r, h, weights, *w, m=m: _branch(
            h, weights, at_rows(r, m, tile_m), *w, tile_m=tile_m)
         for m in sizes],
        r, h, weights, w_gate, w_up, w_down)


def _switch(index, branches, r: Routing, *args):
    """``jax.lax.switch(index, branches, r, *args)`` (each branch
    ``routed_experts_at`` at one size; differentiable in ``args``) whose
    backward hands each size's residuals over in slots of their
    own and **leaves the untaken size's slots unwritten**. Autodiff's own
    rule for a conditional fills them with zeros: rows ``[m, d]`` and three
    ``[m, f]`` arrays for every size but the one taken, 1.35 GB a micro
    batch and layer at the Mellum2 cell's shapes, 2% of its step in writes
    nobody reads. Here the forward rule is a ``switch`` whose branch ``i``
    returns the leaves of its ``jax.vjp`` pullback (its residuals) in slot
    ``i`` and arrays no kernel wrote in the others; the backward rule is a
    ``switch`` whose branch ``i`` calls slot ``i``'s pullback. The gradients
    are autodiff's of the size taken; an :class:`ExpertStack` among ``args``
    gets its accumulator's cotangent and no other."""
    taken = range(len(branches))
    tree = jax.tree_util.tree_structure(args)
    # Of an ``ExpertStack`` among ``args`` no leaf goes in a slot (a slot
    # would hold a copy of every layer's experts), and its stack and layer
    # index are closed over by what is differentiated: a pullback hands back
    # zeros for an operand nothing reaches, as large.
    roles = [role for a in args for role in (
        ExpertStack("fixed", "fixed", "moving") if isinstance(a, ExpertStack)
        else (None,) * len(jax.tree_util.tree_leaves(a)))]
    handed = [n for n, role in enumerate(roles) if role]
    moving = [n for n, role in enumerate(roles) if role != "fixed"]

    @jax.custom_vjp
    def chosen(index, r, *args):
        return jax.lax.switch(index, branches, r, *args)

    # Slot i holds the leaves of size i's pullback; what puts them together
    # again is static, made while the forward rule is traced and read by
    # the backward rule, which is traced after it. A leaf that is one of an
    # ``ExpertStack``'s as it came goes in no slot and the backward rule
    # takes it from ``args``: a conditional returns no operand without
    # copying it, so a slot holds a copy for the size taken and an array as
    # large, unwritten, for every other. (Weights handed as a layer's own
    # ``[E, K, N]`` slices go in the slots with the rows: the form the
    # compiler schedules better, PERF.md section 6, PR 45.)
    trees, passed = {}, {}

    def fwd(index, r, *args):
        def residuals(i, r, *args):
            flat = jax.tree_util.tree_leaves(args)

            def branch(*moved):
                leaves = list(flat)
                for n, a in zip(moving, moved):
                    leaves[n] = a
                return branches[i](r, *tree.unflatten(leaves))

            out, pull = jax.vjp(branch, *(flat[n] for n in moving))
            leaves, trees[i] = jax.tree_util.tree_flatten(pull)
            passed[i] = [next((n for n in handed if flat[n] is leaf), None)
                         for leaf in leaves]
            return out, [leaf for leaf, n in zip(leaves, passed[i])
                         if n is None]

        # Traced once a size: the shapes first, the branch from the cache.
        sized = [jax.jit(functools.partial(residuals, i), inline=True)
                 for i in taken]
        avals = [jax.eval_shape(f, r, *args)[1] for f in sized]

        def branch(i, r, *args):
            out, leaves = sized[i](r, *args)
            return out, [leaves if j == i
                         else [_unwritten(a) for a in avals[j]]
                         for j in taken]

        out, slots = jax.lax.switch(
            index, [functools.partial(branch, i) for i in taken], r, *args)
        return out, (index, slots, [
            a if n in handed else None
            for n, a in enumerate(jax.tree_util.tree_leaves(args))])

    def bwd(residuals, g):
        index, slots, flat = residuals

        def pull(i, slots, flat, g):
            kept = iter(slots[i])
            return jax.tree_util.tree_unflatten(trees[i], [
                next(kept) if n is None else flat[n] for n in passed[i]])(g)

        cts = dict(zip(moving, jax.lax.switch(
            index, [functools.partial(pull, i) for i in taken], slots, flat,
            g)))
        return (None, None) + tuple(tree.unflatten(
            [cts.get(n) for n in range(len(flat))]))

    chosen.defvjp(fwd, bwd)
    return chosen(index, r, *args)


@functools.partial(jax.jit, static_argnums=0, inline=True)
def _unwritten(aval):
    """An array of ``aval``'s shape and dtype that nothing writes: what
    stands in a residual slot nobody will read. A kernel with no input and
    an empty body, whose result stays in HBM as it was allocated; zeros
    where a fill costs less than a kernel's start."""
    if aval.size * aval.dtype.itemsize < 1 << 20:
        return jnp.zeros(aval.shape, aval.dtype)
    return pl.pallas_call(
        lambda out: None, name="tepdist_unwritten",
        out_shape=jax.ShapeDtypeStruct(aval.shape, aval.dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        interpret=_interpret(None))()


# A branch is traced once a shape and a process, however many passes (the
# forward walk, the backward walk's recompute, the planner's trace and the
# lowering's) trace the ``switch`` around it: two sizes would else double
# what tracing the expert layer costs every set-up.
_branch = jax.jit(routed_experts_at, inline=True, static_argnames="tile_m")
