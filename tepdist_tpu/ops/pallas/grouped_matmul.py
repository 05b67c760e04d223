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

Zero padding rows contribute nothing to ``tgmm``; their ``gmm`` outputs are
zero rows nobody gathers. Kernel names ``tepdist_gmm_fwd`` / ``_dx`` /
``_dw`` show in a device trace and in the compiled HLO. Runs in interpret
mode off-TPU (tests), compiled on TPU.
"""

from __future__ import annotations

import functools

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


def _cost(M, K, N, groups, itemsize):
    """What the planner (graph/cost.py) and XLA's scheduler are told."""
    return pl.CostEstimate(
        flops=2 * M * K * N, transcendentals=0,
        bytes_accessed=itemsize * (M * K + M * N + groups * K * N))


def _live(i, n_tiles):
    """Row tile ``i``, or the last live one past it (nothing new is fetched
    for a skipped tile)."""
    return jnp.minimum(i, n_tiles[0] - 1)


def _gmm_kernel(tile_group, n_tiles, x_ref, w_ref, o_ref, *, dims):
    i = pl.program_id(1)

    @pl.when(i < n_tiles[0])
    def _():
        o_ref[...] = _dot(x_ref[...], w_ref[0], dims).astype(o_ref.dtype)

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
def gmm(x, w, tile_group, n_tiles, *, tile_m: int, transpose_rhs=False,
        block_n: int = 1024, name: str = "tepdist_gmm_fwd", interpret=None):
    """x [M, K] in the tile-aligned layout times its tile's group's weight:
    w [E, K, N] -> [M, N], or with ``transpose_rhs`` w [E, N, K] -> [M, N]
    contracted over w's last dim."""
    M, K = x.shape
    N = w.shape[1] if transpose_rhs else w.shape[2]
    if w.shape[2 if transpose_rhs else 1] != K or M % tile_m:
        raise ValueError(f"gmm: x {x.shape}, w {w.shape}, "
                         f"transpose_rhs={transpose_rhs}, tile_m={tile_m}")
    bn = _block_n(N, block_n)

    if transpose_rhs:
        w_spec = pl.BlockSpec(
            (1, bn, K), lambda j, i, tg, n: (tg[_live(i, n)], j, 0))
    else:
        w_spec = pl.BlockSpec(
            (1, K, bn), lambda j, i, tg, n: (tg[_live(i, n)], 0, j))
    # Row tiles innermost: consecutive tiles of one group keep their weight
    # block in VMEM, so each expert's weights cross HBM once a column block.
    return pl.pallas_call(
        functools.partial(_gmm_kernel, dims=_NT if transpose_rhs else _NN),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N // bn, M // tile_m),
            in_specs=[pl.BlockSpec((tile_m, K),
                                   lambda j, i, tg, n: (_live(i, n), 0)),
                      w_spec],
            out_specs=pl.BlockSpec((tile_m, bn),
                                   lambda j, i, tg, n: (i, j))),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        cost_estimate=_cost(M, K, N, w.shape[0], x.dtype.itemsize),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(interpret),
    )(tile_group, n_tiles, x, w)


def _tgmm_kernel(tile_group, n_tiles, x_ref, dy_ref, o_ref, acc_ref):
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
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, inline=True, static_argnames=(
        "num_groups", "tile_m", "block_n", "name", "interpret"))
def tgmm(x, dy, tile_group, n_tiles, num_groups: int, *, tile_m: int,
         block_n: int = 1024,
         name: str = "tepdist_gmm_dw", interpret=None):
    """Per-group ``x.T @ dy``: x [M, K], dy [M, N] in the tile-aligned
    layout -> [num_groups, K, N]. Every group owns at least one tile, so
    every output block is written."""
    M, K = x.shape
    N = dy.shape[1]
    if dy.shape[0] != M or M % tile_m:
        raise ValueError(f"tgmm: x {x.shape}, dy {dy.shape}, tile_m={tile_m}")
    bk, bn = _block_n(K, _TGMM_BLOCK_K), _block_n(N, block_n)

    return pl.pallas_call(
        _tgmm_kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(K // bk, N // bn, M // tile_m),
            in_specs=[pl.BlockSpec((tile_m, bk),
                                   lambda a, b, i, tg, n: (_live(i, n), a)),
                      pl.BlockSpec((tile_m, bn),
                                   lambda a, b, i, tg, n: (_live(i, n), b))],
            out_specs=pl.BlockSpec(
                (1, bk, bn), lambda a, b, i, tg, n: (tg[_live(i, n)], a, b)),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((num_groups, K, N), x.dtype),
        cost_estimate=_cost(M, K, N, num_groups, x.dtype.itemsize),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(interpret),
    )(tile_group, n_tiles, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_group, n_tiles, tile_m: int):
    """x [M, K] (tile-aligned layout) @ w[group of the row's tile] [E, K, N]
    -> [M, N]; differentiable in x and w."""
    return gmm(x, w, tile_group, n_tiles, tile_m=tile_m)


def _grouped_fwd(x, w, tile_group, n_tiles, tile_m):
    return gmm(x, w, tile_group, n_tiles, tile_m=tile_m), \
        (x, w, tile_group, n_tiles)


def _grouped_bwd(tile_m, res, dy):
    x, w, tile_group, n_tiles = res
    dx = gmm(dy, w, tile_group, n_tiles, tile_m=tile_m, transpose_rhs=True,
             name="tepdist_gmm_dx")
    dw = tgmm(x, dy, tile_group, n_tiles, w.shape[0], tile_m=tile_m)
    return dx, dw, None, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)
