"""Pallas TPU sequence mixing of Compressed Convolutional Attention (CCA,
Zyphra, arXiv:2510.04476; ``models/zaya.py``), forward and backward.

The query latent ``q0`` [T, H heads of D] and the key latent ``k0`` [T, Hkv
heads of D] of one sequence, joined ``u = [q0 ; k0]`` (``N = H + Hkv`` heads),
go through two causal convolutions of 2 taps and the q-k mean:

    c1_t   = b1 + w1[0] * u_{t-1} + w1[1] * u_t            depth-wise
    c2_t,h = b2_h + c1_{t-1,h} W2[0,h] + c1_{t,h} W2[1,h]   a head's D channels
                                                           mixed: [D, D] a tap
    m_q,h  = (q0_h + k0_g(h)) / 2          g(h) = h // (H / Hkv)
    m_k,g  = (mean_{h in g} q0_h + k0_g) / 2
    q, k   = c2[:H] + m_q,  c2[H:] + m_k

with zeros before the sequence: ``u_{-1} = 0`` and ``c1_{-1} = 0`` (not
``b1``). Written in ``jax.numpy`` (:func:`reference`) that is pads, shifted
slices and a batched matmul over ``[T, N D]`` arrays four or five times a
layer, the first in float32.

**What crosses HBM.** ``tepdist_cca_mix_fwd`` reads each block of ``q0`` and
``k0`` once, in their dtype, and writes ``q`` and ``k`` once, **head-major**
(``[B, H, T, D]``, what the norm, the rotary embedding and the flash kernels
after it take: no transpose between). ``tepdist_cca_mix_bwd`` reads ``q0``,
``k0`` and the two cotangents once, writes ``dq0`` and ``dk0`` once (token-
major, what the projections' backward takes) and the float32 sums the weights'
gradients are made of once a (batch row, key/value group): they are added up
in VMEM while the group's time blocks go by. Nothing padded and nothing
float32 exists outside VMEM. The residuals of the ``custom_vjp`` are the
operands.

**Grid and blocks.** ``(batch, key/value groups, time blocks)``, time
innermost and sequential. A grid step holds a group's ``H / Hkv`` query heads
and its key head (the q-k mean needs them together: two ``BlockSpec``s a
latent, no joined copy of ``u``), and their ``2 (H / Hkv + 1)`` matrices
``[D, D]`` stay resident over the group's time blocks. Inside a step a
``fori_loop`` walks the block in strips of ``STRIP`` rows; a head's strip is
shifted as float32 ``[8, D]`` tiles (``_tiles.py``, ``causal_conv.py``'s
helpers) and meets the matrix unit as one ``[STRIP, D]`` operand in the
weights' dtype, accumulated in float32.

**How the halo is carried.** The forward shifts *down* by one row, twice
(``u_{t-1}``, ``c1_{t-1}``): the roll of the last tile of ``u`` and of ``c1``
goes from tile to tile, strip to strip and, through a VMEM scratch, block to
block; the scratch's initial zeros are the zeros before the sequence. **The
backward needs no row from before**: the map is linear, so with ``g`` the
cotangent and ``g+`` / ``g++`` its rows shifted *up* by one and two,

    dc1_t  = g_t W2[1]^T + g_{t+1} W2[0]^T
    du_t   = w1[1] * dc1_t + w1[0] * dc1_{t+1} + (the mean's transpose)
    dW2[1] = sum_t c1_t^T g_t      = w1[1] . U^T G  + w1[0] . U^T G+  + b1 (x) sum g
    dW2[0] = sum_t c1_t^T g_{t+1}  = w1[1] . U^T G+ + w1[0] . U^T G++ + b1 (x) sum g+
    dw1[1], dw1[0], db1 = sum_t dc1_t * u_t, sum_t dc1_{t+1} * u_t, sum_t dc1_t

so it walks blocks, strips and tiles last to first, carries the rolls of the
first tile of ``g`` (by one and two) and of ``dc1`` the same way (zeros after
the sequence), and the kernel sums ``U^T G``, ``U^T G+``, ``U^T G++`` (three
``[D, D]`` a head, operands exact in the latents' dtype) and four ``[D]`` rows
a head; the few products with ``w1`` and ``b1`` that turn them into ``dW2`` are
``jax.numpy`` on ``[N, D, D]``.

Precision: operands are widened as they are read; the depth-wise conv, the
mean and every sum are float32; ``c1`` and the cotangents meet the matrix unit
in the weights' dtype (bf16 in training: what a ``jax.numpy`` layer in bf16
does) with float32 accumulation; ``q``, ``k``, ``dq0``, ``dk0`` leave in the
latents' dtype.

Kernel names ``tepdist_cca_mix_fwd`` / ``tepdist_cca_mix_bwd`` show in a
device trace and in the compiled HLO. Runs in interpret mode off the TPU
(tests), compiled on it. ``tools/cca_bench.py`` times both alone.
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
from tepdist_tpu.ops.pallas.selective_scan import LANES, _pad, _padded
from tepdist_tpu.telemetry import traced

BLOCK_T = 1024              # rows a grid step (tools/cca_bench.py)
_VMEM_LIMIT = 48 * 1024 * 1024      # of 128 MiB; the default scope is 16 MiB
_F32 = jnp.float32

traced.declare(
    "cca_mix_calls", "forward calls a micro batch of the compressed "
    "attention's mixing kernel (a rematerialised layer's second run counted)")


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _dot_rows(a, b):
    """``a^T b``: the sum over rows of the outer products."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=_F32)


def _heads(n: int, D: int, q_refs, k_refs):
    """(refs, lanes, index in the block) of a group's ``n`` query heads and
    its key head, in that order: ``q_refs`` / ``k_refs`` the per-channel and
    per-head operands' blocks of the two parts."""
    return [(q_refs, slice(j * D, (j + 1) * D), j) for j in range(n)] \
        + [(k_refs, slice(0, D), 0)]


def _fwd_kernel(q0_ref, k0_ref, w1q_ref, w1k_ref, b1q_ref, b1k_ref, w2q_ref,
                w2k_ref, b2q_ref, b2k_ref, q_ref, k_ref, tail_scr, *,
                n: int, D: int, bt: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        tail_scr[...] = jnp.zeros(tail_scr.shape, _F32)

    heads = _heads(n, D, (w1q_ref, b1q_ref, w2q_ref, b2q_ref),
                   (w1k_ref, b1k_ref, w2k_ref, b2k_ref))

    def trip(i, before):
        rows = _strip(i)
        us = [q0_ref[0, rows, lanes].astype(_F32) for _, lanes, _ in
              heads[:n]] + [k0_ref[0, rows, :].astype(_F32)]
        after, c2s = [], []
        for (refs, lanes, at), u, (bu, bc) in zip(heads, us, before):
            w1_ref, b1_ref, w2_ref, b2_ref = refs
            tap0, tap1 = w1_ref[0:1, lanes], w1_ref[1:2, lanes]
            bias = b1_ref[:, lanes]
            c1s, befores = [], []
            for tile in _tiles(u):
                row = _row(tile)
                ur, = _rolls(tile, (1,))
                c1 = bias + tap0 * _down(row, 1, bu, ur) + tap1 * tile
                cr, = _rolls(c1, (1,))
                befores.append(_down(row, 1, bc, cr))
                c1s.append(c1)
                bu, bc = ur, cr
            after.append((bu, bc))
            dt = w2_ref.dtype
            c2s.append(
                _dot(jnp.concatenate(befores, 0).astype(dt), w2_ref[0, at])
                + _dot(jnp.concatenate(c1s, 0).astype(dt), w2_ref[1, at])
                + b2_ref[:, lanes])
        uk, q_sum = us[n], sum(us[:n])
        for j in range(n):
            q_ref[0, j, rows, :] = (c2s[j] + 0.5 * (us[j] + uk)).astype(
                q_ref.dtype)
        k_ref[0, 0, rows, :] = (c2s[n] + 0.5 * uk + (0.5 / n) * q_sum).astype(
            k_ref.dtype)
        return tuple(after)

    before = jax.lax.fori_loop(
        0, bt // STRIP, trip,
        tuple((tail_scr[j, 0], tail_scr[j, 1]) for j in range(n + 1)))
    for j, (bu, bc) in enumerate(before):
        tail_scr[j, 0] = bu
        tail_scr[j, 1] = bc


# The rows of a head's float32 vector sums.
_SUM_G, _SUM_W0, _SUM_W1, _SUM_B1 = range(4)


def _bwd_kernel(q0_ref, k0_ref, gq_ref, gk_ref, w1q_ref, w1k_ref, w2tq_ref,
                w2tk_ref, dq0_ref, dk0_ref, sq_ref, sk_ref, vq_ref, vk_ref,
                head_scr, sum_scr, *, n: int, D: int, bt: int):
    k = pl.program_id(2)                 # 0 is the sequence's last block

    @pl.when(k == 0)
    def _():
        head_scr[...] = jnp.zeros(head_scr.shape, _F32)
        sum_scr[...] = jnp.zeros(sum_scr.shape, _F32)
        sq_ref[...] = jnp.zeros(sq_ref.shape, _F32)
        sk_ref[...] = jnp.zeros(sk_ref.shape, _F32)

    heads = _heads(n, D, (w1q_ref, w2tq_ref, sq_ref),
                   (w1k_ref, w2tk_ref, sk_ref))
    trips = bt // STRIP

    def trip(i, after):
        rows = _strip(trips - 1 - i)
        us = [q0_ref[0, rows, lanes] for _, lanes, _ in heads[:n]] \
            + [k0_ref[0, rows, :]]
        gs = [gq_ref[0, j, rows, :] for j in range(n)] + [gk_ref[0, 0, rows, :]]
        g32 = [g.astype(_F32) for g in gs]
        gk, gq_sum = g32[n], sum(g32[:n])
        before = []
        for j, ((refs, lanes, at), u, g, (a1, a2, ad)) in enumerate(
                zip(heads, us, gs, after)):
            w1_ref, w2t_ref, s_ref = refs
            dt = w2t_ref.dtype
            tiles = _tiles(g32[j])
            row = _row(tiles[0])
            up1, up2 = [None] * len(tiles), [None] * len(tiles)
            for t in reversed(range(len(tiles))):
                l1, l2 = _rolls(tiles[t], (TILE - 1, TILE - 2))
                up1[t], up2[t] = _up(row, 1, a1, l1), _up(row, 2, a2, l2)
                a1, a2 = l1, l2
            g, u = g.astype(dt), u.astype(dt)
            g1 = jnp.concatenate(up1, 0).astype(dt)
            g2 = jnp.concatenate(up2, 0).astype(dt)
            s_ref[0, at, 0] += _dot_rows(u, g)
            s_ref[0, at, 1] += _dot_rows(u, g1)
            s_ref[0, at, 2] += _dot_rows(u, g2)
            dc1 = _tiles(_dot(g, w2t_ref[1, at]) + _dot(g1, w2t_ref[0, at]))
            u32 = _tiles(us[j].astype(_F32))
            lifted = [None] * len(dc1)
            sums = [None] * 4
            for t in reversed(range(len(dc1))):
                ld, = _rolls(dc1[t], (TILE - 1,))
                lifted[t] = _up(row, 1, ad, ld)
                ad = ld
                for which, part in ((_SUM_G, tiles[t]),
                                    (_SUM_W0, lifted[t] * u32[t]),
                                    (_SUM_W1, dc1[t] * u32[t]),
                                    (_SUM_B1, dc1[t])):
                    sums[which] = part if sums[which] is None \
                        else sums[which] + part
            for which in range(4):
                sum_scr[j, which] += sums[which]
            before.append((a1, a2, ad))
            du = w1_ref[1:2, lanes] * jnp.concatenate(dc1, 0) \
                + w1_ref[0:1, lanes] * jnp.concatenate(lifted, 0)
            if j < n:        # the mean's transpose
                dq0_ref[0, rows, lanes] = (
                    du + 0.5 * g32[j] + (0.5 / n) * gk).astype(dq0_ref.dtype)
            else:
                dk0_ref[0, rows, :] = (
                    du + 0.5 * gk + 0.5 * gq_sum).astype(dk0_ref.dtype)
        return tuple(before)

    after = jax.lax.fori_loop(
        0, trips, trip,
        tuple(tuple(head_scr[j, s] for s in range(3)) for j in range(n + 1)))
    for j, halo in enumerate(after):
        for s in range(3):
            head_scr[j, s] = halo[s]

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        for j in range(n + 1):
            v_ref, at = (vq_ref, j) if j < n else (vk_ref, 0)
            for which in range(4):
                v_ref[0, at, which:which + 1, :] = jnp.sum(
                    sum_scr[j, which], axis=0, keepdims=True)


def _sizes(q0, k0, w2, block_t: int):
    """Batch, rows, query heads, key heads, a head's channels, rows a grid
    step and the rows the sequence is padded to."""
    B, T, _ = q0.shape
    D = w2.shape[-1]
    bt = min(max(block_t // STRIP, 1) * STRIP, _padded(T, STRIP))
    return B, T, q0.shape[-1] // D, k0.shape[-1] // D, D, bt, _padded(T, bt)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _parts(H: int, n: int, D: int, rows: int):
    """The two ``BlockSpec``s of a per-channel operand ``[rows, N D]``: a
    group's query heads' channels and its key head's."""
    return [pl.BlockSpec((rows, n * D), lambda b, g, t: (0, g)),
            pl.BlockSpec((rows, D), lambda b, g, t: (0, H + g))]


def _matrices(H: int, n: int, D: int):
    """... and of ``W2`` ``[2, N, D, D]``."""
    return [pl.BlockSpec((2, n, D, D), lambda b, g, t: (0, g, 0, 0)),
            pl.BlockSpec((2, 1, D, D), lambda b, g, t: (0, H + g, 0, 0))]


@functools.partial(jax.jit, inline=True,
                   static_argnames=("block_t", "interpret"))
def _fwd_call(q0, k0, w1, b1, w2, b2, *, block_t, interpret):
    """``q0`` [B, T, H D], ``k0`` [B, T, Hkv D], ``w1`` [2, N D], ``b1``,
    ``b2`` [N D], ``w2`` [2, N, D, D] -> ``q`` [B, H, T, D], ``k`` [B, Hkv,
    T, D]."""
    B, T, H, Hkv, D, bt, Tp = _sizes(q0, k0, w2, block_t)
    n, N = H // Hkv, H + Hkv
    w1, b1, b2 = w1.astype(_F32), b1.astype(_F32)[None], b2.astype(_F32)[None]
    w2 = w2.astype(q0.dtype)
    q, k = pl.pallas_call(
        functools.partial(_fwd_kernel, n=n, D=D, bt=bt),
        name="tepdist_cca_mix_fwd",
        grid=(B, Hkv, Tp // bt),
        in_specs=[pl.BlockSpec((1, bt, n * D), lambda b, g, t: (b, t, g)),
                  pl.BlockSpec((1, bt, D), lambda b, g, t: (b, t, g)),
                  *_parts(H, n, D, 2), *_parts(H, n, D, 1),
                  *_matrices(H, n, D), *_parts(H, n, D, 1)],
        out_specs=[pl.BlockSpec((1, n, bt, D), lambda b, g, t: (b, g, t, 0)),
                   pl.BlockSpec((1, 1, bt, D), lambda b, g, t: (b, g, t, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, H, Tp, D), q0.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, Tp, D), q0.dtype)],
        scratch_shapes=[pltpu.VMEM((n + 1, 2, TILE, D), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=(4 * D + 8) * B * T * N * D, transcendentals=0,
            bytes_accessed=2 * B * T * N * D * q0.dtype.itemsize),
        compiler_params=_params(), interpret=interpret,
    )(_pad(q0, Tp), _pad(k0, Tp), w1, w1, b1, b1, w2, w2, b2, b2)
    return q[:, :, :T], k[:, :, :T]


@functools.partial(jax.jit, inline=True,
                   static_argnames=("block_t", "interpret"))
def _bwd_call(q0, k0, w1, b1, w2, gq, gk, *, block_t, interpret):
    """-> ``dq0``, ``dk0`` and the float32 ``dw1`` [2, N D], ``db1`` [N D],
    ``dw2`` [2, N, D, D], ``db2`` [N D]."""
    B, T, H, Hkv, D, bt, Tp = _sizes(q0, k0, w2, block_t)
    n, N, nt = H // Hkv, H + Hkv, Tp // bt
    w1 = w1.astype(_F32)
    w2t = jnp.swapaxes(w2, -1, -2).astype(q0.dtype)

    def late(b, g, t):
        return nt - 1 - t

    def pad_heads(g):
        return jnp.pad(g, ((0, 0), (0, 0), (0, Tp - T), (0, 0))) \
            if Tp > T else g

    def sums(heads, *dims):
        return pl.BlockSpec((1, heads) + dims,
                            lambda b, g, t: (b, g) + (0,) * len(dims))

    dq0, dk0, sq, sk, vq, vk = pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, D=D, bt=bt),
        name="tepdist_cca_mix_bwd",
        grid=(B, Hkv, nt),
        in_specs=[
            pl.BlockSpec((1, bt, n * D),
                         lambda b, g, t: (b, late(b, g, t), g)),
            pl.BlockSpec((1, bt, D), lambda b, g, t: (b, late(b, g, t), g)),
            pl.BlockSpec((1, n, bt, D),
                         lambda b, g, t: (b, g, late(b, g, t), 0)),
            pl.BlockSpec((1, 1, bt, D),
                         lambda b, g, t: (b, g, late(b, g, t), 0)),
            *_parts(H, n, D, 2), *_matrices(H, n, D)],
        out_specs=[
            pl.BlockSpec((1, bt, n * D),
                         lambda b, g, t: (b, late(b, g, t), g)),
            pl.BlockSpec((1, bt, D), lambda b, g, t: (b, late(b, g, t), g)),
            sums(n, 3, D, D), sums(1, 3, D, D), sums(n, 4, D), sums(1, 4, D)],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, H * D), q0.dtype),
                   jax.ShapeDtypeStruct((B, Tp, Hkv * D), q0.dtype),
                   jax.ShapeDtypeStruct((B, H, 3, D, D), _F32),
                   jax.ShapeDtypeStruct((B, Hkv, 3, D, D), _F32),
                   jax.ShapeDtypeStruct((B, H, 4, D), _F32),
                   jax.ShapeDtypeStruct((B, Hkv, 4, D), _F32)],
        scratch_shapes=[pltpu.VMEM((n + 1, 3, TILE, D), _F32),
                        pltpu.VMEM((n + 1, 4, TILE, D), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=(10 * D + 16) * B * T * N * D, transcendentals=0,
            bytes_accessed=3 * B * T * N * D * q0.dtype.itemsize),
        compiler_params=_params(), interpret=interpret,
    )(_pad(q0, Tp), _pad(k0, Tp), pad_heads(gq), pad_heads(gk), w1, w1,
      w2t, w2t)
    # What the kernel summed, into the weights' gradients: [N, ...] a head.
    s = jnp.concatenate([sq.sum(0), sk.sum(0)])              # [N, 3, D, D]
    v = jnp.concatenate([vq.sum(0), vk.sum(0)])              # [N, 4, D]
    first = jnp.concatenate([gq[:, :, 0], gk[:, :, 0]], 1).astype(
        _F32).sum(0)                                         # sum g_0 [N, D]
    tap = w1.reshape(2, N, D, 1)
    bias = b1.astype(_F32).reshape(N, D, 1)
    sum_g = v[:, _SUM_G][:, None, :]
    dw2 = jnp.stack([
        tap[1] * s[:, 1] + tap[0] * s[:, 2] + bias * (sum_g - first[:, None]),
        tap[1] * s[:, 0] + tap[0] * s[:, 1] + bias * sum_g])
    dw1 = jnp.stack([v[:, _SUM_W0], v[:, _SUM_W1]]).reshape(2, N * D)
    return dq0[:, :T], dk0[:, :T], dw1, v[:, _SUM_B1].reshape(N * D), dw2, \
        v[:, _SUM_G].reshape(N * D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _mix(q0, k0, w1, b1, w2, b2, block_t, interpret, layers):
    traced.count("cca_mix_calls", layers=layers)
    return _fwd_call(q0, k0, w1, b1, w2, b2, block_t=block_t,
                     interpret=interpret)


def _mix_fwd(q0, k0, w1, b1, w2, b2, block_t, interpret, layers):
    traced.count("cca_mix_calls", layers=layers)
    out = _fwd_call(q0, k0, w1, b1, w2, b2, block_t=block_t,
                    interpret=interpret)
    return out, (q0, k0, w1, b1, w2, b2)


def _mix_bwd(block_t, interpret, layers, res, cts):
    q0, k0, w1, b1, w2, b2 = res
    dq0, dk0, dw1, db1, dw2, db2 = _bwd_call(
        q0, k0, w1, b1, w2, *cts, block_t=block_t, interpret=interpret)
    return dq0, dk0, dw1.astype(w1.dtype), db1.astype(b1.dtype), \
        dw2.astype(w2.dtype), db2.astype(b2.dtype)


_mix.defvjp(_mix_fwd, _mix_bwd)


def _check(q0, k0, w1, b1, w2, b2):
    N, D = w2.shape[1], w2.shape[-1]
    Hkv = k0.shape[-1] // D
    if q0.ndim != 3 or k0.shape[:2] != q0.shape[:2] or w2.shape != (
            2, N, D, D) or q0.shape[-1] + k0.shape[-1] != N * D \
            or Hkv < 1 or (N - Hkv) % Hkv or w1.shape != (2, N * D) \
            or b1.shape != (N * D,) or b2.shape != (N * D,):
        raise ValueError(
            f"cca_mix: q0 {q0.shape}, k0 {k0.shape}, w1 {w1.shape}, b1 "
            f"{b1.shape}, w2 {w2.shape}, b2 {b2.shape}")
    return N - Hkv, Hkv, D


def cca_mix(q0, k0, w1, b1, w2, b2, *, block_t: int = BLOCK_T,
            interpret: Optional[bool] = None):
    """The module docstring's ``q`` [batch, H, T, D] and ``k`` [batch, Hkv,
    T, D] from the latents ``q0`` [batch, T, H D] and ``k0`` [batch, T, Hkv
    D] (token-major in, head-major out), ``w1`` [2, N D], ``b1`` [N D],
    ``w2`` [2, N, D, D], ``b2`` [N D] with ``N = H + Hkv`` and ``D`` a
    multiple of 128. Differentiable in all six. Any ``T``: the last block is
    padded with zero rows. ``block_t`` rows a grid step.

    Counts, while it is traced, each forward kernel call in
    ``cca_mix_calls`` (``telemetry/traced.py``)."""
    _, _, D = _check(q0, k0, w1, b1, w2, b2)
    if D % LANES:
        raise ValueError(f"cca_mix: heads of {D} channels; the kernels take "
                         f"multiples of {LANES} (reference() any)")
    return _mix(q0, k0, w1, b1, w2, b2, block_t, _interpret(interpret),
                traced.stood_for())


def reference(q0, k0, w1, b1, w2, b2):
    """The same function in ``jax.numpy``, what the kernels are held to
    (tests, ``tools/cca_bench.py``) and what a model whose heads are no
    multiple of 128 wide runs: pad, widen, add shifted slices, a batched
    matmul a tap."""
    H, Hkv, D = _check(q0, k0, w1, b1, w2, b2)
    B, T, _ = q0.shape
    N, n, dt = H + Hkv, H // Hkv, q0.dtype

    def shifted(x):          # row t - 1 at row t, zeros before the sequence
        return jnp.pad(x, ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))[:, :T]

    u = jnp.concatenate([q0, k0], axis=-1).astype(_F32)
    # Rounded to the latents' dtype where the kernel hands it to the matrix
    # unit, then float32 operands: the products are the same (one bf16 pass
    # by default) and the CPU has no batched bf16 matmul into float32.
    c1 = (b1.astype(_F32) + w1[0].astype(_F32) * shifted(u)
          + w1[1].astype(_F32) * u).astype(dt).astype(_F32).reshape(
              B, T, N, D)
    w2 = w2.astype(dt).astype(_F32)
    c2 = jnp.einsum("btnd,nde->bnte", shifted(c1), w2[0]) \
        + jnp.einsum("btnd,nde->bnte", c1, w2[1]) \
        + b2.astype(_F32).reshape(N, 1, D)
    qs = u[..., :H * D].reshape(B, T, Hkv, n, D)
    ks = u[..., H * D:].reshape(B, T, Hkv, 1, D)
    mean = jnp.concatenate([
        (0.5 * (qs + ks)).reshape(B, T, H, D),
        0.5 * (qs.mean(axis=3) + ks[:, :, :, 0])], axis=2)
    out = (c2 + mean.transpose(0, 2, 1, 3)).astype(dt)
    return out[:, :H], out[:, H:]
