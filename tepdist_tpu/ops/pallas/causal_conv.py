"""Pallas TPU depth-wise causal convolution with its SiLU (Mamba's), forward
and backward.

For one sequence ``u_1..u_T`` of ``Di`` channels and ``K`` taps a channel:

    total_t = b + sum_j w[j] * u_{t-(K-1)+j}     (u before the sequence is 0)
    c_t = silu(total_t)

Every channel stands alone, so this is element-wise work on ``[T, Di]``
shifted by whole rows: by bytes a forward has to read ``u`` and write ``c``,
a backward to read ``u`` and ``dc`` and write ``du``. Written in ``jax.numpy``
(pad, widen, add ``K`` shifted slices) XLA keeps a padded float32 copy of
``u`` and the pre-activation for the backward, and sums each tap's gradient
over the sequence in a pass of its own.

**What crosses HBM.** ``tepdist_conv_fwd`` reads each ``[bt, bd]`` block of
``u`` once, in ``u``'s dtype, and writes the block of ``c``; nothing padded
and nothing float32 exists outside VMEM. ``tepdist_conv_bwd`` reads the blocks
of ``u`` and ``dc`` once (and 16 rows of ``u`` before each block a second
time), writes ``du`` once, and writes the ``K`` taps' and the bias's gradient
sums, ``[K + 1, bd]`` float32 a channel block, once: they are added up in a
VMEM scratch while the channel block's time blocks go by. The residuals of
the ``custom_vjp`` are the operands ``u``, ``w``, ``b``; the backward makes
the pre-activation again.

**How the halo is carried** (the tile helpers are ``_tiles.py``'s, shared
with ``cca_mix.py``). The grid is ``(batch, channel blocks, time
blocks)``, time innermost and sequential. Inside a grid step a ``fori_loop``
walks the block in strips of ``STRIP`` rows, a strip as float32 ``[8, bd]``
tiles in registers. A row shifted down by ``s`` is a sublane roll of its tile
by ``s`` with the first ``s`` rows taken from the same roll of the tile
before, so what goes from tile to tile, from strip to strip and, through a
VMEM scratch, from one time block to the next is the ``K - 1`` rolls of the
last tile: the causal padding is that scratch's initial zeros. The backward
walks the time blocks, the strips and the tiles last to first: the gradient
of the pre-activation ``g = dc * silu'(total)`` shifted *up* by ``s`` takes
its last ``s`` rows from the tile after, carried the same way (zeros after
the sequence); the rows of ``u`` before a strip are read again from the
block, and before the block's first strip from a second, 16-row
``BlockSpec`` on the same array (clamped at the first block and masked
there).

**Why tiles and a loop.** The same arithmetic written on whole ``[strip,
bd]`` arrays spills (strips of 32 / 256 rows: backward 577 / 716 us a call
where the tiles take 464-505); the strips unrolled in Python run 10% faster
and let XLA fuse an operand's producer into the call
(``allow_input_fusion``, which refuses a buffer read at a computed offset),
+0.4% on the step, but the six calls of a step then take 16 s more to trace
and lower (``PERF.md`` section 6, PR 43).

Precision: ``u``, ``w``, ``b``, ``dc`` are widened as they are read; every
product, sum and the SiLU are float32; ``c`` and ``du`` leave in ``u``'s
dtype; ``dw`` and ``db`` are summed in float32 over batch and sequence and
cast once.

Kernel names ``tepdist_conv_fwd`` / ``tepdist_conv_bwd`` show in a device
trace and in the compiled HLO. Runs in interpret mode off the TPU (tests),
compiled on it. ``tools/ssm_bench.py`` times both alone over the blocks.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tepdist_tpu.ops.pallas import _interpret
from tepdist_tpu.ops.pallas._tiles import (
    STRIP,
    TILE,
    _down,
    _rolls,
    _row,
    _strip,
    _tiles,
    _up,
)
from tepdist_tpu.ops.pallas.selective_scan import (
    LANES,
    _block_d,
    _pad,
    _padded,
)
from tepdist_tpu.telemetry import traced

HALO = 16                   # rows of the backward's second block of ``u``
BLOCK_T = 2048              # rows a grid step (tools/ssm_bench.py)
BLOCK_D = 256               # channels a grid step
_F32 = jnp.float32

# Operations an element, as ``cost_estimate`` tells the planner: forward K
# products and sums and the SiLU's four (the exp is a transcendental, counted
# apart); the backward makes the forward's total again, then the SiLU's
# slope, K products and sums for ``du`` and K + 1 for the weights' sums.
FWD_FLOPS, BWD_FLOPS = 10, 25


traced.declare(
    "ssm_conv_calls", "forward calls a micro batch of the short conv before "
    "a sequence mixer, the selective scan's or the delta rule's three (a "
    "rematerialised layer's second run counted)")


def _total(tile, rolled, before, taps, bias):
    """The pre-activation of one ``[8, bd]`` tile and the rows it was made
    of: ``rolled`` / ``before`` the rolls by 1..K-1 of this tile / of the
    tile before. ``xs[s]`` holds row ``t - s`` at row ``t``."""
    K = len(taps)
    row = _row(tile)
    xs = [tile] + [_down(row, s, before[s - 1], rolled[s - 1])
                   for s in range(1, K)]
    acc = taps[0] * xs[K - 1]
    for j in range(1, K):
        acc = acc + taps[j] * xs[K - 1 - j]
    return bias + acc, xs


def _fwd_kernel(u_ref, w_ref, *rest, K: int, bt: int, biased: bool):
    b_ref, c_ref, tail_scr = rest if biased else (None,) + rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        tail_scr[...] = jnp.zeros(tail_scr.shape, _F32)

    taps = [w_ref[j:j + 1, :] for j in range(K)]
    bias = b_ref[...] if biased else 0.0
    down = range(1, K)

    def trip(i, before):
        out = []
        for tile in _tiles(u_ref[0, _strip(i), :].astype(_F32)):
            rolled = _rolls(tile, down)
            total, _ = _total(tile, rolled, before, taps, bias)
            out.append(total * jax.nn.sigmoid(total))
            before = rolled
        c_ref[0, _strip(i), :] = jnp.concatenate(out, 0).astype(c_ref.dtype)
        return before

    before = jax.lax.fori_loop(
        0, bt // STRIP, trip, tuple(tail_scr[s] for s in range(K - 1)))
    for s in range(K - 1):
        tail_scr[s] = before[s]


def _bwd_kernel(u_ref, halo_ref, dc_ref, w_ref, *rest, K: int, bt: int,
                biased: bool):
    b_ref, du_ref, dwb_ref, g_scr, sum_scr = rest if biased \
        else (None,) + rest
    k = pl.program_id(2)                 # 0 is the sequence's last block
    first = pl.num_programs(2) - 1       # ... and this its first

    @pl.when(k == 0)
    def _():
        g_scr[...] = jnp.zeros(g_scr.shape, _F32)
        sum_scr[...] = jnp.zeros(sum_scr.shape, _F32)

    taps = [w_ref[j:j + 1, :] for j in range(K)]
    bias = b_ref[...] if biased else 0.0
    down = range(1, K)
    up = [TILE - s for s in down]        # a roll by 8 - s shifts up by s
    # The tile before the block: zeros before the sequence.
    halo = halo_ref[0].astype(_F32)[HALO - TILE:]
    halo = jnp.where(k == first, jnp.zeros_like(halo), halo)
    n = bt // STRIP

    def trip(i, after):
        at = n - 1 - i
        tiles = _tiles(u_ref[0, _strip(at), :].astype(_F32))
        dcs = _tiles(dc_ref[0, _strip(at), :].astype(_F32))
        behind = pl.multiple_of(jnp.maximum(at * STRIP - HALO, 0), HALO)
        before = u_ref[0, pl.ds(behind, HALO), :].astype(_F32)[HALO - TILE:]
        before = jnp.where(at == 0, halo, before)
        rolled = [_rolls(t, down) for t in [before] + tiles]
        row = _row(before)
        sums = [None] * (K + 1)
        dus = [None] * len(tiles)
        for t in reversed(range(len(tiles))):
            total, xs = _total(tiles[t], rolled[t + 1], rolled[t], taps, bias)
            sig = jax.nn.sigmoid(total)
            g = dcs[t] * (sig * (1.0 + total * (1.0 - sig)))
            for j in range(K):
                part = g * xs[K - 1 - j]
                sums[j] = part if sums[j] is None else sums[j] + part
            sums[K] = g if sums[K] is None else sums[K] + g
            lifted = _rolls(g, up)
            du = taps[K - 1] * g
            for s in down:               # row t + s at row t
                du = du + taps[K - 1 - s] * _up(
                    row, s, after[s - 1], lifted[s - 1])
            dus[t] = du
            after = lifted
        du_ref[0, _strip(at), :] = jnp.concatenate(dus, 0).astype(
            du_ref.dtype)
        for j in range(K + 1):
            sum_scr[j] += sums[j]
        return after

    after = jax.lax.fori_loop(
        0, n, trip, tuple(g_scr[s] for s in range(K - 1)))
    for s in range(K - 1):
        g_scr[s] = after[s]

    @pl.when(k == first)
    def _():
        for j in range(K + 1):
            dwb_ref[0, 0, j:j + 1, :] = jnp.sum(sum_scr[j], axis=0,
                                                keepdims=True)


def _blocks(T: int, Di: int, block_t: int, block_d: int):
    """Rows and channels a grid step, and the rows the sequence is padded
    to."""
    bt = min(max(block_t // STRIP, 1) * STRIP, _padded(T, STRIP))
    return bt, _block_d(Di, block_d), _padded(T, bt)


def _bias_spec(b, bd: int) -> list:
    """The bias row's block, where there is a bias."""
    return [] if b is None else [
        pl.BlockSpec((1, bd), lambda i, j, k: (0, j))]


def _bias_row(b) -> list:
    return [] if b is None else [b.astype(_F32)[None, :]]


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, inline=True, static_argnames=(
    "block_t", "block_d", "interpret"))
def _fwd_call(u, w, b, *, block_t, block_d, interpret):
    """``u`` [B, T, Di], ``w`` [K, Di], ``b`` [Di] or None -> ``c`` [B, T,
    Di]."""
    B, T, Di = u.shape
    K = w.shape[0]
    bt, bd, Tp = _blocks(T, Di, block_t, block_d)
    wide = pl.BlockSpec((1, bt, bd), lambda i, j, k: (i, k, j))
    c = pl.pallas_call(
        functools.partial(_fwd_kernel, K=K, bt=bt, biased=b is not None),
        name="tepdist_conv_fwd",
        grid=(B, Di // bd, Tp // bt),
        in_specs=[wide, pl.BlockSpec((K, bd), lambda i, j, k: (0, j))]
        + _bias_spec(b, bd),
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct((B, Tp, Di), u.dtype),
        scratch_shapes=[pltpu.VMEM((K - 1, TILE, bd), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=FWD_FLOPS * B * T * Di, transcendentals=B * T * Di,
            bytes_accessed=2 * B * T * Di * u.dtype.itemsize),
        compiler_params=_params(), interpret=interpret,
    )(_pad(u, Tp), w.astype(_F32), *_bias_row(b))
    return c[:, :T]


@functools.partial(jax.jit, inline=True, static_argnames=(
    "block_t", "block_d", "interpret"))
def _bwd_call(u, w, b, dc, *, block_t, block_d, interpret):
    """-> ``du`` [B, T, Di] and the float32 sums ``dw`` [K, Di], ``db``
    [Di] (the sum of the pre-activation's gradient, whether or not there is
    a bias to take it)."""
    B, T, Di = u.shape
    K = w.shape[0]
    bt, bd, Tp = _blocks(T, Di, block_t, block_d)
    nd, nt = Di // bd, Tp // bt
    wide = pl.BlockSpec((1, bt, bd), lambda i, j, k: (i, nt - 1 - k, j))
    halo = pl.BlockSpec((1, HALO, bd), lambda i, j, k: (
        i, jnp.maximum((nt - 1 - k) * (bt // HALO) - 1, 0), j))
    u = _pad(u, Tp)
    du, dwb = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K, bt=bt, biased=b is not None),
        name="tepdist_conv_bwd",
        grid=(B, nd, nt),
        in_specs=[wide, halo, wide,
                  pl.BlockSpec((K, bd), lambda i, j, k: (0, j))]
        + _bias_spec(b, bd),
        out_specs=[wide, pl.BlockSpec((1, 1, K + 1, bd),
                                      lambda i, j, k: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, Di), u.dtype),
                   jax.ShapeDtypeStruct((B, nd, K + 1, bd), _F32)],
        scratch_shapes=[pltpu.VMEM((K - 1, TILE, bd), _F32),
                        pltpu.VMEM((K + 1, TILE, bd), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=BWD_FLOPS * B * T * Di, transcendentals=B * T * Di,
            bytes_accessed=3 * B * T * Di * u.dtype.itemsize),
        compiler_params=_params(), interpret=interpret,
    )(u, u, _pad(dc, Tp), w.astype(_F32), *_bias_row(b))
    dwb = dwb.sum(0).transpose(1, 0, 2).reshape(K + 1, Di)
    return du[:, :T], dwb[:K], dwb[K]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _conv(u, w, b, block_t, block_d, interpret, layers):
    traced.count("ssm_conv_calls", layers=layers)
    return _fwd_call(u, w, b, block_t=block_t, block_d=block_d,
                     interpret=interpret)


def _conv_fwd(u, w, b, block_t, block_d, interpret, layers):
    traced.count("ssm_conv_calls", layers=layers)
    c = _fwd_call(u, w, b, block_t=block_t, block_d=block_d,
                  interpret=interpret)
    return c, (u, w, b)


def _conv_bwd(block_t, block_d, interpret, layers, res, dc):
    u, w, b = res
    du, dw, db = _bwd_call(u, w, b, dc, block_t=block_t, block_d=block_d,
                           interpret=interpret)
    return du, dw.astype(w.dtype), None if b is None else db.astype(b.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv(u, w, b=None, *, block_t: int = BLOCK_T,
                block_d: int = BLOCK_D, interpret: Optional[bool] = None):
    """``silu(b + sum_j w[j] * u[t - (K - 1) + j])``, zeros before the
    sequence: ``u`` [batch, T, Di], ``w`` [K, Di] with ``2 <= K <= 8``, ``b``
    [Di] or None (a conv without a bias: the kernels then take no such
    operand) -> [batch, T, Di] in ``u``'s dtype, ``Di`` a multiple of 128.
    Differentiable in all three. Any ``T``: the last block is padded with
    zero rows. ``block_t`` rows and ``block_d`` channels a grid step.

    Counts, while it is traced, each forward kernel call in
    ``ssm_conv_calls`` (``telemetry/traced.py``): the short convs of any
    sequence mixer, a state-space layer's or not."""
    if u.ndim != 3 or w.ndim != 2 or w.shape[1] != u.shape[2] \
            or (b is not None and b.shape != u.shape[2:]) \
            or u.shape[2] % LANES \
            or not 2 <= w.shape[0] <= TILE:
        raise ValueError(
            f"causal_conv: u {u.shape}, w {w.shape}, b "
            f"{None if b is None else b.shape}")
    return _conv(u, w, b, block_t, block_d, _interpret(interpret),
                 traced.stood_for())


def reference(u, w, b=None):
    """The same function in ``jax.numpy``, what the kernels are held to
    (tests, ``tools/ssm_bench.py``): pad, widen, add ``K`` shifted slices."""
    K, T = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0))).astype(_F32)
    total = (0.0 if b is None else b.astype(_F32)) + sum(
        w[j].astype(_F32) * padded[:, j:j + T] for j in range(K))
    return jax.nn.silu(total).astype(u.dtype)
