"""Pallas TPU grouped matmul over tile-aligned groups (forward + backward).

The expert layer's hot op: rows sorted by expert, each expert's rows times
that expert's weight matrix. The rows arrive in a **tile-aligned layout**
(``ops/grouped_matmul.py:route``): every group starts at a multiple of
``tile_m`` rows and is padded with zero rows to a whole number of tiles (an
empty group keeps one tile of zeros), so every row tile belongs to exactly
one group. The kernels then need no mask and no tile visited twice: a scalar
prefetched ``tile_group[i]`` picks the weight block of row tile ``i``, and
consecutive tiles of one group leave the weight block (or, in the weight
gradient, the accumulator) where it is in VMEM. Tiles past ``n_tiles[0]``
(the layout's static size holds more than the routing fills) are skipped:
their operands' block indices are clamped to the last live tile, so nothing
is fetched for them, and their output rows are written as zeros.

* ``gmm``:  ``out[rows of tile i] = x[rows] @ w[tile_group[i]]``; with
  ``transpose_rhs`` the weight is contracted over its last dim, which is the
  input gradient ``dy @ w[g].T`` taken from the weight as it is stored
  (XLA's ``ragged_dot`` copies all of ``w`` transposed first, 268 MB a call
  at the OLMoE shapes).
* ``tgmm``: ``dw[g] = x[rows of g].T @ dy[rows of g]``, float32 accumulation
  over the group's tiles in VMEM, written once a group.
* ``grouped_matmul``: ``gmm`` with a custom VJP made of the two.

**A call may carry an epilogue**, applied to its float32 tile before the one
rounding, where XLA would read the kernel's result back from HBM and write
a third array: ``gmm(add=)`` adds an ``[M, N]`` array whose buffer becomes
the result's (the second of two input gradients adds the first), and
``gmm(act=)`` turns the product into the experts' activation, ``silu(gate)
* product * row_weight`` for ``(gate, row_weight)`` and ``relu(product)^2 *
row_weight`` for ``(row_weight,)`` (:func:`swiglu`, :func:`relu_squared`:
the formulas ``ops/grouped_matmul.py`` composes from two calls' results
where a pass is differentiated). Still one product a call, under the
call's name; the extra operands are ``[M, .]`` like the rows.

**The weight is one layer's ``[E, K, N]``, or every layer's ``[L, E, K, N]``
with the layer's index.** A Pallas call's operand is a whole buffer, so a
walk over stacked layers that hands a kernel ``stack[l]`` makes XLA copy the
slice out before every call, and ``acc[l] += dw`` after the weight gradient
is a copy out, an add and a copy in: twelve copies of a layer's experts a
layer and micro batch, a fifth of the ZAYA1 cell's step (PERF.md section 6,
PR 49). Given the stack and ``layer`` (int32 [1], one more scalar-prefetch
operand that the weight's index map puts before the expert's) ``gmm`` reads
the layer's tiles where they lie, and ``tgmm`` given the accumulator stack
``into`` writes ``into[layer, g] + round(x_g^T dy_g)`` over slice ``layer``
of ``into``'s own buffer (``input_output_aliases``; no other slice is read
or written): the group's float32 sum rounded to the weight's dtype, then
added to the accumulator's block in float32 and rounded to the accumulator's
dtype, the two roundings of ``acc[l] + dw``, so the sums are the sliced
walk's bit for bit. The form follows the operand's rank; a rank-3 call is
the custom call it always was. ``grouped_matmul`` takes the stack as an
:class:`ExpertStack` ``(stack, layer, into)``: its backward hands the
updated accumulator back as ``into``'s cotangent and the stack gets none
(``models/layers.py:scan_blocks`` is who builds one).

Zero padding rows contribute nothing to ``tgmm``; their ``gmm`` outputs are
zero rows nobody gathers. Kernel names ``tepdist_gmm_fwd`` / ``_dx`` /
``_dw`` show in a device trace and in the compiled HLO. Runs in interpret
mode off-TPU (tests), compiled on TPU.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tepdist_tpu.ops.pallas import _interpret

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_VMEM_LIMIT = 64 * 1024 * 1024     # of 128 MiB; the default scope is 16 MiB
_TGMM_BLOCK_K = 1024               # rows of a weight-gradient block


def _dot(a, b, dims):
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _block_n(n: int, want: int) -> int:
    """Largest multiple of 128 that divides ``n`` and is at most ``want``
    (``n`` itself when it is smaller or has no such divisor)."""
    for b in range(min(want, n) // 128 * 128, 0, -128):
        if n % b == 0:
            return b
    return n


def _cost(M, K, N, groups, itemsize, more=0, transcendentals=0):
    """What the planner (graph/cost.py) and XLA's scheduler are told;
    ``more``: the ``[M, N]`` operands an epilogue reads."""
    return pl.CostEstimate(
        flops=2 * M * K * N, transcendentals=transcendentals,
        bytes_accessed=itemsize * (M * K + (1 + more) * M * N
                                   + groups * K * N))


def _live(i, n_tiles):
    """Row tile ``i``, or the last live one past it (nothing new is fetched
    for a skipped tile)."""
    return jnp.minimum(i, n_tiles[0] - 1)


def swiglu(gate, up, row_weight):
    """``silu(gate) * up * row_weight`` of float32 operands: a gated
    expert's activation with the router's weight on the row."""
    return jax.nn.silu(gate) * up * row_weight


def relu_squared(up, row_weight):
    """``relu(up)^2 * row_weight`` of float32 operands: the activation of
    an expert of two matrices."""
    u = jnp.maximum(up, 0.0)
    return u * u * row_weight


def _gmm_kernel(tile_group, n_tiles, *refs, dims, stacked, add):
    # After the layer's index, if any; ``more``: the epilogue's operands.
    x_ref, w_ref, *more, o_ref = refs[stacked:]
    i = pl.program_id(1)

    @pl.when(i < n_tiles[0])
    def _():
        y = _dot(x_ref[...], w_ref[0], dims)
        if add:
            y = y + more[0][...].astype(jnp.float32)
        elif more:      # the rows' weights come one to a lane, [1, tile_m]
            *gate, weight = (m[...].astype(jnp.float32) for m in more)
            y = swiglu(*gate, y, weight.T) if gate \
                else relu_squared(y, weight.T)
        o_ref[...] = y.astype(o_ref.dtype)

    @pl.when(i >= n_tiles[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


# ``gmm`` and ``tgmm`` are traced once a shape and a process (an inlined
# ``jit``: the same equations in the caller's program): a step's trace meets
# each of them in the forward walk, in the backward walk's recompute and in
# its backward, once for the planner and once for the lowering, and a kernel
# body's trace is the larger part of a call's.
@functools.partial(
    jax.jit, inline=True, static_argnames=(
        "tile_m", "transpose_rhs", "block_n", "name", "interpret"))
def gmm(x, w, tile_group, n_tiles, layer=None, add=None, act=None, *,
        tile_m: int, transpose_rhs=False, block_n: int = 1024,
        name: str = "tepdist_gmm_fwd", interpret=None):
    """x [M, K] in the tile-aligned layout times its tile's group's weight:
    w [E, K, N] -> [M, N], or with ``transpose_rhs`` w [E, N, K] -> [M, N]
    contracted over w's last dim. ``w`` [L, E, ...] with ``layer`` int32 [1]:
    the same over ``w[layer[0]]``, read where it lies.

    One epilogue at most, on the float32 tile before it is rounded (zeros
    past the live tiles either way): ``add`` [M, N] in x's dtype is added
    and **its buffer is the result's**; ``act = (gate [M, N], row_weight
    [M, 1])`` makes the result ``swiglu(gate, product, row_weight)`` and
    ``act = (row_weight,)`` makes it ``relu_squared(product, row_weight)``."""
    M, K = x.shape
    stacked = layer is not None
    E = w.shape[-3]
    N = w.shape[-2] if transpose_rhs else w.shape[-1]
    if w.ndim != 3 + stacked or M % tile_m \
            or w.shape[-1 if transpose_rhs else -2] != K:
        raise ValueError(f"gmm: x {x.shape}, w {w.shape} with layer "
                         f"{'given' if stacked else 'None'}, "
                         f"transpose_rhs={transpose_rhs}, tile_m={tile_m}")
    # The epilogue's operands: as wide as the result, then one a row.
    *wide, weight = (add, None) if act is None else act
    wide = [a for a in wide if a is not None]
    if (add is not None and (act is not None or add.dtype != x.dtype)) \
            or len(wide) > 1 or any(a.shape != (M, N) for a in wide) \
            or (weight is not None and weight.shape != (M, 1)):
        raise ValueError(
            f"gmm: add {getattr(add, 'shape', None)} "
            f"{getattr(add, 'dtype', None)}, act "
            f"{act and [a.shape for a in act]} on {(M, N)} {x.dtype}")
    bn = _block_n(N, block_n)

    # The weight's block and where it lies: (group of row tile i, column
    # block j), behind the layer's index where the weight is a stack (the
    # scalar operands follow the grid's indices: tile_group, n_tiles, layer).
    block = (1, bn, K) if transpose_rhs else (1, K, bn)

    def at(j, i, tg, n, *layer):
        g = tg[_live(i, n)]
        return tuple(l[0] for l in layer) + (
            (g, j, 0) if transpose_rhs else (g, 0, j))

    w_spec = pl.BlockSpec((None,) * stacked + block, at)
    scalars = (tile_group, n_tiles) + ((layer,) if stacked else ())
    # The epilogue's operands by the rows' own tiles: nothing new is fetched
    # for a skipped tile, whose result is zeros whatever they hold. One
    # value a row goes in as [1, M], a tile's along the lanes: an [M, 1]
    # operand is tiled (8, 128) in HBM, 128 times its size.
    more = [(a, pl.BlockSpec(
        (tile_m, bn), lambda j, i, tg, n, *_: (_live(i, n), j)))
        for a in wide]
    if weight is not None:
        more.append((weight.reshape(1, M), pl.BlockSpec(
            (1, tile_m), lambda j, i, tg, n, *_: (0, _live(i, n)))))
    # Row tiles innermost: consecutive tiles of one group keep their weight
    # block in VMEM, so each expert's weights cross HBM once a column block.
    return pl.pallas_call(
        functools.partial(_gmm_kernel, dims=_NT if transpose_rhs else _NN,
                          stacked=stacked, add=add is not None),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(N // bn, M // tile_m),
            in_specs=[pl.BlockSpec((tile_m, K),
                                   lambda j, i, tg, n, *_: (_live(i, n), 0)),
                      w_spec] + [spec for _, spec in more],
            out_specs=pl.BlockSpec((tile_m, bn),
                                   lambda j, i, tg, n, *_: (i, j))),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        # The addend (the last operand, behind the scalars, x and w) is
        # written over: a tile is read before its own result goes out.
        input_output_aliases={len(scalars) + 2: 0} if add is not None else {},
        cost_estimate=_cost(M, K, N, E, x.dtype.itemsize, len(wide),
                            M * N * (len(more) == 2)),      # silu's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(interpret),
    )(*scalars, x, w, *(a for a, _ in more))


def _tgmm_kernel(tile_group, n_tiles, *refs):
    if len(refs) == 4:
        (x_ref, dy_ref, o_ref, acc_ref), into_ref = refs, None
    else:       # behind the layer's index, with the accumulator's block
        _, x_ref, dy_ref, into_ref, o_ref, acc_ref = refs
    i = pl.program_id(2)
    last = n_tiles[0] - 1
    g = tile_group[jnp.minimum(i, last)]

    @pl.when(i <= last)
    def _():
        part = _dot(x_ref[...], dy_ref[...], _TN)
        first = jnp.logical_or(
            i == 0, tile_group[jnp.maximum(i, 1) - 1] != g)

        @pl.when(first)
        def _():
            acc_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _():
            acc_ref[...] += part

        closes = jnp.logical_or(
            i == last, tile_group[jnp.minimum(i + 1, last)] != g)

        @pl.when(closes)
        def _():
            dw = acc_ref[...].astype(x_ref.dtype)
            if into_ref is not None:
                # The two roundings of ``acc[layer] + dw``: the group's sum
                # to the weight's dtype, the new sum to the accumulator's.
                dw = into_ref[0].astype(jnp.float32) + dw.astype(jnp.float32)
            o_ref[0] = dw.astype(o_ref.dtype)


@functools.partial(
    jax.jit, inline=True, static_argnames=(
        "num_groups", "tile_m", "block_n", "name", "interpret"))
def tgmm(x, dy, tile_group, n_tiles, num_groups: int, into=None, layer=None,
         *, tile_m: int, block_n: int = 1024,
         name: str = "tepdist_gmm_dw", interpret=None):
    """Per-group ``x.T @ dy``: x [M, K], dy [M, N] in the tile-aligned
    layout -> [num_groups, K, N]. Every group owns at least one tile, so
    every output block is written. With ``into`` [L, num_groups, K, N] and
    ``layer`` int32 [1]: ``into`` with slice ``layer[0]`` plus that (rounded
    to x's dtype first, the sum to ``into``'s), written over ``into``'s own
    buffer; no other slice is read or written."""
    M, K = x.shape
    N = dy.shape[1]
    if dy.shape[0] != M or M % tile_m:
        raise ValueError(f"tgmm: x {x.shape}, dy {dy.shape}, tile_m={tile_m}")
    if (into is None) != (layer is None) or (
            into is not None and into.shape[1:] != (num_groups, K, N)):
        raise ValueError(f"tgmm: into {getattr(into, 'shape', None)} for "
                         f"{(num_groups, K, N)} with layer "
                         f"{'given' if layer is not None else 'None'}")
    stacked = into is not None
    bk, bn = _block_n(K, _TGMM_BLOCK_K), _block_n(N, block_n)

    def at(a, b, i, tg, n, *layer):
        return tuple(l[0] for l in layer) + (tg[_live(i, n)], a, b)

    dw_spec = pl.BlockSpec((None,) * stacked + (1, bk, bn), at)
    scalars = (tile_group, n_tiles) + ((layer,) if stacked else ())
    out = jax.ShapeDtypeStruct((num_groups, K, N), x.dtype) \
        if into is None else jax.ShapeDtypeStruct(into.shape, into.dtype)
    return pl.pallas_call(
        _tgmm_kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(K // bk, N // bn, M // tile_m),
            in_specs=[pl.BlockSpec(
                          (tile_m, bk),
                          lambda a, b, i, tg, n, *_: (_live(i, n), a)),
                      pl.BlockSpec(
                          (tile_m, bn),
                          lambda a, b, i, tg, n, *_: (_live(i, n), b))]
            + [dw_spec] * stacked,
            out_specs=dw_spec,
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)]),
        out_shape=out,
        # The accumulator (the last operand, behind the scalars, x and dy)
        # is the result: the slices this call does not visit stay as they
        # are.
        input_output_aliases={len(scalars) + 2: 0} if stacked else {},
        cost_estimate=_cost(M, K, N, num_groups * (1 + stacked),
                            x.dtype.itemsize),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(interpret),
    )(*scalars, x, dy, *((into,) if stacked else ()))


class ExpertStack(NamedTuple):
    """One layer's expert weights where they lie: what a walk over stacked
    layers hands a block in place of the slice ``stack[layer]``
    (``models/layers.py:scan_blocks``). ``into``: the stack's gradient
    accumulator, which the weight gradient is added into."""
    stack: jax.Array        # [L, E, K, N], every layer's
    layer: jax.Array        # int32 [1]
    into: jax.Array         # [L, E, K, N]

    @property
    def shape(self):
        """The layer's own ``[E, K, N]``."""
        return self.stack.shape[1:]

    @property
    def size(self) -> int:
        return math.prod(self.shape)


def _where(w):
    """(the array, the layer's index or None) of a layer's ``[E, K, N]`` or
    of an :class:`ExpertStack`: the form follows what ``w`` is."""
    return (w.stack, w.layer) if isinstance(w, ExpertStack) else (w, None)


def forward(x, w, tile_group, n_tiles, tile_m: int, act=None):
    """``gmm`` of ``x`` and ``w`` (an ``[E, K, N]`` or an
    :class:`ExpertStack`), ``act`` its epilogue."""
    stack, layer = _where(w)
    return gmm(x, stack, tile_group, n_tiles, layer, act=act, tile_m=tile_m)


def input_grad(dy, w, tile_group, n_tiles, tile_m: int, add=None):
    """``forward``'s cotangent of ``x``: ``dy @ w[group].T``, plus ``add``
    (another product's input gradient of the same ``x``, written over)."""
    stack, layer = _where(w)
    return gmm(dy, stack, tile_group, n_tiles, layer, add, tile_m=tile_m,
               transpose_rhs=True, name="tepdist_gmm_dx")


def weight_grad(x, dy, w, tile_group, n_tiles, tile_m: int):
    """``forward``'s cotangent of ``w``: the ``[E, K, N]`` gradient, or for
    an :class:`ExpertStack` **its accumulator with the gradient added into
    slice ``w.layer``** (the stack and the index get none)."""
    if not isinstance(w, ExpertStack):
        return tgmm(x, dy, tile_group, n_tiles, w.shape[0], tile_m=tile_m)
    return ExpertStack(None, None, tgmm(
        x, dy, tile_group, n_tiles, w.shape[0], w.into, w.layer,
        tile_m=tile_m))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_group, n_tiles, tile_m: int):
    """x [M, K] (tile-aligned layout) @ w[group of the row's tile] [E, K, N]
    -> [M, N]; differentiable in x and w. ``w`` an :class:`ExpertStack`: the
    same over ``w.stack[w.layer]`` read where it lies, differentiable in x
    and in ``w.into``, **whose cotangent is ``w.into`` with the weight's
    gradient added into slice ``w.layer``** (the stack's own is none)."""
    return forward(x, w, tile_group, n_tiles, tile_m)


def _grouped_fwd(x, w, tile_group, n_tiles, tile_m):
    return forward(x, w, tile_group, n_tiles, tile_m), \
        (x, w, tile_group, n_tiles)


def _grouped_bwd(tile_m, res, dy):
    x, w, *tiles = res
    return (input_grad(dy, w, *tiles, tile_m),
            weight_grad(x, dy, w, *tiles, tile_m), None, None)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)
