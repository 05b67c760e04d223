"""Dropless token-choice dispatch for a routed expert layer.

``route`` sorts the ``S x k`` (token, expert) assignments by expert and lays
the sorted rows out **tile-aligned**: every expert's rows start at a
multiple of ``tile_m`` and are padded to whole tiles (an expert nobody chose
keeps one tile), which is the layout ``ops/pallas/grouped_matmul.py``
multiplies without masks. No token is dropped whatever the skew; the static
row count ``(ceil(S*k / tile_m) + E) * tile_m`` is the worst case, and the
tiles past the live ones are skipped by the kernels. There is no
``[S, E, C]`` tensor and no capacity.

``dispatch`` gathers each row's token (or, for the router's weights, each
row's assignment); ``combine`` gathers each token's k rows and sums them.
Each is the other's transpose, and each is the other's custom VJP: the
transpose of a permutation is the inverse permutation, which ``route``
already holds, while autodiff's scatter-adds of 2048-wide rows are the slow
way around on a TPU. The index arithmetic uses two small sorts and gathers,
no scatter.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Routing(NamedTuple):
    row_token: jax.Array        # [M] token of each row; S (out of range) = pad
    row_assignment: jax.Array   # [M] assignment (token * k + slot); S * k = pad
    dest: jax.Array             # [S, k] row of each assignment
    tile_group: jax.Array       # [M / tile_m] expert of each row tile
    n_tiles: jax.Array          # [1] live row tiles
    group_sizes: jax.Array      # [E] assignments per expert


def route(expert_ids, num_experts: int, tile_m: int) -> Routing:
    """The tile-aligned layout of ``expert_ids`` [S, k]."""
    S, k = expert_ids.shape
    A, E = S * k, num_experts
    flat = expert_ids.reshape(A)
    order = jnp.argsort(flat, stable=True)       # sorted place -> assignment
    rank = jnp.argsort(order)                    # assignment -> sorted place
    group_sizes = jnp.sum(
        flat[:, None] == jnp.arange(E, dtype=flat.dtype), axis=0,
        dtype=jnp.int32)
    tiles = jnp.maximum(1, -(-group_sizes // tile_m))
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tile_m      # first row of each group
    sort_start = jnp.cumsum(group_sizes) - group_sizes
    dest = (row_start[flat] + rank - sort_start[flat]).reshape(S, k)

    n_row_tiles = -(-A // tile_m) + E
    tile_group = jnp.minimum(jnp.searchsorted(
        tile_end, jnp.arange(n_row_tiles, dtype=jnp.int32), side="right"),
        E - 1).astype(jnp.int32)
    rows = jnp.arange(n_row_tiles * tile_m, dtype=jnp.int32)
    g = tile_group[rows // tile_m]
    offset = rows - row_start[g]
    live = jnp.logical_and(offset < group_sizes[g],
                           rows < tile_end[-1] * tile_m)
    assignment = jnp.where(
        live, order[jnp.clip(sort_start[g] + offset, 0, A - 1)], A)
    return Routing(
        row_token=(assignment // k).astype(jnp.int32),
        row_assignment=assignment.astype(jnp.int32),
        dest=dest.astype(jnp.int32), tile_group=tile_group,
        n_tiles=tile_end[-1:].astype(jnp.int32), group_sizes=group_sizes)


def _rows(x, source):
    """x [S, d] -> [M, d]: row r is x[source[r]], zeros where the row is a
    pad (its source out of range)."""
    return jnp.take(x, source, axis=0, mode="fill", fill_value=0)


def _sum_of_rows(y, dest):
    """y [M, d] -> [S, d]: the float32 sum of each token's rows, one of the
    k gathers at a time (all k at once are an [S, k, d] float32 array,
    512 MiB at the OLMoE cell's micro batch)."""
    total = jnp.zeros((dest.shape[0], y.shape[1]), jnp.float32)
    for j in range(dest.shape[1]):
        total = total + y[dest[:, j]].astype(jnp.float32)
    return total.astype(y.dtype)


@jax.custom_vjp
def dispatch(x, source, dest):
    """Into the tile-aligned layout: x [S, d] -> [M, d] by ``source`` [M];
    ``dest`` [S, k] holds the rows that name each x row (``route``'s
    ``row_token`` and ``dest`` for token rows; ``row_assignment`` and
    ``dest.reshape(S * k, 1)`` for one value an assignment)."""
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
