"""Rows shifted by whole rows inside a Pallas TPU kernel: what the kernels
that mix along the sequence share (``causal_conv.py``, ``cca_mix.py``).

A block ``[rows, lanes]`` is walked in strips of ``STRIP`` rows, a strip as
float32 ``[TILE, lanes]`` tiles in registers. A row shifted *down* by ``s``
(row ``t`` holds row ``t - s``) is a sublane roll of its tile by ``s`` with
the first ``s`` rows taken from the same roll of the tile before; shifted
*up* by ``s`` a roll by ``TILE - s`` with the last ``s`` rows from that roll
of the tile after. So what goes from tile to tile, from strip to strip and,
through a VMEM scratch, from one grid step to the next is the rolls of one
tile: the halo. Zeros in that scratch are the zeros before (after) the
sequence."""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 8                    # rows of a float32 tile
STRIP = 64                  # rows a loop trip; whole packed 16-bit tiles


def _rolls(tile, shifts):
    return tuple(pltpu.roll(tile, s, 0) for s in shifts)


def _tiles(x):
    return [x[i:i + TILE] for i in range(0, x.shape[0], TILE)]


def _strip(i):
    return pl.ds(pl.multiple_of(i * STRIP, STRIP), STRIP)


def _row(tile):
    """Each element's row in its tile."""
    return jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)


def _down(row, s, before, rolled):
    """A tile shifted down by ``s``: ``rolled`` its roll by ``s``,
    ``before`` the same roll of the tile before."""
    return jnp.where(row < s, before, rolled)


def _up(row, s, after, lifted):
    """A tile shifted up by ``s``: ``lifted`` its roll by ``TILE - s``,
    ``after`` the same roll of the tile after."""
    return jnp.where(row >= TILE - s, after, lifted)
