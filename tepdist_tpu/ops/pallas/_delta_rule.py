"""What the chunked delta-rule kernels share (``kda_attention.py``: a decay
for every key channel; ``gdn_attention.py``: one decay a value head, value
heads in groups over a key head): the running sum inside a chunk, the
inverse of the chunk's triangular system and the way back through it, a
head's column of a ``[chunk, heads]`` block, the one sweep over ``(batch,
chunks, heads)`` with every head's float32 state in a VMEM scratch, and the
call's three forms for a walk that keeps its forward pass. The running sum,
the column, a column as a row and the sweep are also the state-space rule's
(``ssd_attention.py``: no triangular system, a state of ``[values, states]``
a head and two heads a lane block).

**The inverse by doubling**: ``A`` is strictly lower, so ``(I + A)^-1 = (I -
A)(I + A^2)(I + A^4) ...`` up to ``A^(C/2)``: two matmuls a factor, no
substitution row by row. The two are taken a step apart (``P <- P P``
beside ``X <- X + X P`` on the old ``P``), so that they share their right
operand and neither waits for the other; the operands are float32, two bf16
parts each, and a product inside the inverse is three passes of the matrix
unit where every other product of the rule (``_linear._dot``) is four; the
heads of a grid step walk their chains in lockstep, so that a head's step
stands beside another's and not behind its own last one (:func:`_inverse`;
on the chip, 2,048 inverses of ``[128, 128]`` a call: 4.0 ms as twelve
products in a row of four passes each, 2.3 with two heads side by side, 1.7
so). **Back through it**: with ``X = [W |
U] = (I + A)^-1 B``, ``dB = (I + A)^-T dX`` and ``dA = -dB X^T``: no inverse
of its own.

**The sweep**: the grid is ``(batch, chunks, heads)``, ``group`` heads a
step, the chunks sequential (last to first: ``reverse``) and the heads
innermost, the state ``[V, K]`` of every head in one float32 scratch ``[H,
V, K]`` from chunk to chunk, so that ``beta``, a decay a head and their
gradients are ``[chunk, H]`` blocks as a projection leaves them, read and
written once a chunk.

**The call** (:func:`differentiable`): a forward that is differentiated
writes, beside ``o``, the state before every chunk and the chunk's inverse;
the backward kernel reads them; inside a block that
``models/layers.py:scan_blocks`` walks the three go to the walk
(``flash_attention.hand_over``) and the block's recomputation runs no
forward kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tepdist_tpu.ops.pallas._linear import (
    _F32,
    _NN,
    _NT,
    _TN,
    _dot,
    _parts,
)
from tepdist_tpu.ops.pallas.flash_attention import hand_over
from tepdist_tpu.telemetry import traced


def _prefix(x, reverse: bool = False):
    """Running sums down the rows of ``x`` [C, K] (up them: ``reverse``),
    each row's own included, by doubling."""
    C = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    s = 1
    while s < C:
        if reverse:
            x = x + jnp.where(row < C - s, pltpu.roll(x, C - s, 0), 0.0)
        else:
            x = x + jnp.where(row >= s, pltpu.roll(x, s, 0), 0.0)
        s *= 2
    return x


def _ij(C: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0),
            jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))


def _row(col):
    """A column [C, 1] as a row [1, C], exactly (a masked sum of zeros)."""
    i, j = _ij(col.shape[0])
    return jnp.sum(jnp.where(i == j, col, 0.0), axis=0, keepdims=True)


def _col(row):
    """A row [1, C] as a column [C, 1], exactly."""
    i, j = _ij(row.shape[1])
    return jnp.sum(jnp.where(i == j, row, 0.0), axis=1, keepdims=True)


def _product(a, b):
    """``a @ b`` of two float32 operands, each in its two bf16 parts ``(hi,
    lo)``, in three passes of the matrix unit where ``_linear._dot`` makes
    four: ``lo hi + hi lo + hi hi`` as one product over the parts joined
    along the contraction, summed inside the unit. ``lo lo`` is at most
    2^-18 of the product, under the 2^-17 of each operand that two parts
    have already given away."""
    (a_hi, a_lo), (b_hi, b_lo) = a, b
    return jax.lax.dot_general(
        jnp.concatenate([a_lo, a_hi, a_hi], axis=1),
        jnp.concatenate([b_hi, b_lo, b_hi], axis=0), _NN,
        preferred_element_type=_F32)


def _inverse(A, narrow):
    """``(I + A)^-1`` of a strictly lower ``A`` [C, C], by doubling: ``X =
    I - A``, ``P = A A``, then ``P <- P P`` and ``X <- X + X P`` (the old
    ``P`` in both: two products with their right operand in common and
    neither waiting for the other, side by side) up to ``P = A^(C/2)``. Of
    each of a sequence of them (the heads of a grid step), a list: the
    chains walk in lockstep, a step of every head's before the next step,
    so that a head's products stand beside another's and not behind their
    own last ones. ``narrow``: a product is :func:`_product`'s three
    passes; else one float32 matmul (``_linear._dot``)."""
    many = isinstance(A, (list, tuple))
    powers = list(A) if many else [A]
    C = powers[0].shape[0]
    i, j = _ij(C)
    eye = jnp.where(i == j, 1.0, 0.0)

    def times(a, b):
        """``a @ b`` of two operands as ``_linear._parts`` hands them."""
        return _product(a, b) if narrow else _dot(*a, *b, _NN, narrow)

    invs = [eye - a for a in powers]
    powers = [times(a, a) for a in [_parts(a, narrow) for a in powers]]
    n = 4
    while n < C:                    # A^C = 0
        for h, (p, x) in enumerate(zip(powers, invs)):
            p = _parts(p, narrow)
            powers[h] = times(p, p)
            invs[h] = x + times(_parts(x, narrow), p)
        n *= 2
    invs = [x + times(_parts(x, narrow), _parts(p, narrow))
            for x, p in zip(invs, powers)]
    return invs if many else invs[0]


def _through_inverse(inv, dW, dU, W, U, narrow):
    """``X = [W | U] = (I + A)^-1 B``: ``(dB``'s two halves, ``dA)`` from
    ``dX``'s; ``dA`` strictly lower."""
    i, j = _ij(inv.shape[0])
    dBw = _dot(inv, dW, _TN, narrow)
    dBu = _dot(inv, dU, _TN, narrow)
    dA = -jnp.where(j < i, _dot(dBw, W, _NT, narrow)
                    + _dot(dBu, U, _NT, narrow), 0.0)
    return dBw, dBu, dA


def _column(b, h):
    """Head ``h``'s column [C, 1] of a ``[C, H]`` block."""
    lane = jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)
    return jnp.sum(jnp.where(lane == h, b.astype(_F32), 0.0), axis=1,
                   keepdims=True)


def sweep(kernel, name, operands, outs, *, chunk, reverse, flops,
          transcendentals, interpret, group=None, state=None):
    """One sweep over the chunks. ``operands``: ``(kind, array)`` each;
    ``outs``: ``(kind, dtype)`` of each result (whole chunks: the caller
    pads). The kinds, for ``H`` heads of ``K`` channels, ``group`` of them a
    grid step (None: one, its axis squeezed out of a block): ``wide`` ``[B,
    T, H * K]``, ``group * K`` lanes a step; ``key`` ``[B, T, H / group *
    cols]``, the ``cols`` lanes the step's heads share; ``beta`` ``[B, T,
    H]``, the whole block every step; ``states`` ``[B, chunks, states, rows,
    cols]`` and ``inv`` ``[B, chunks, H, chunk, chunk]``, the step's heads';
    ``lanes`` ``[1, H * K]``, a number a lane whatever the token, the step's
    ``group * K`` of them, and ``lanes_out`` ``[B, chunks, 1, H * K]``, one
    such row a chunk.

    ``state``: ``(n, rows, cols)``, the ``n`` float32 states ``[rows,
    cols]`` a grid step carries in the scratch and reads or writes as
    ``states`` (None: a ``[K, K]`` state a head, the delta rules';
    ``ssd_attention.py``: a ``[heads a lane block * K, cols]`` state a lane
    block)."""
    B, T, H = next(x.shape for kind, x in operands if kind == "beta")
    r = group or 1
    K = next(x.shape[2] for kind, x in operands if kind == "wide") // H
    n, rows, cols = state or (group, K, K)
    steps = H // r
    states = steps * (n or 1)
    nc = T // chunk
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)

    def per_step(n, *tail):
        return pl.BlockSpec((None, None, n) + tail,
                            lambda b, c, h: (b, at(c), h, 0, 0))

    specs = {
        "wide": pl.BlockSpec((None, chunk, r * K),
                             lambda b, c, h: (b, at(c), h)),
        "key": pl.BlockSpec((None, chunk, cols),
                            lambda b, c, h: (b, at(c), h)),
        "beta": pl.BlockSpec((None, chunk, H), lambda b, c, h: (b, at(c), 0)),
        "states": per_step(n, rows, cols),
        "inv": per_step(group, chunk, chunk),
        "lanes": pl.BlockSpec((1, r * K), lambda b, c, h: (0, h)),
        "lanes_out": pl.BlockSpec((None, None, 1, r * K),
                                  lambda b, c, h: (b, at(c), 0, h)),
    }
    shapes = {"wide": (B, T, H * K), "key": (B, T, steps * cols),
              "beta": (B, T, H), "states": (B, nc, states, rows, cols),
              "inv": (B, nc, H, chunk, chunk),
              "lanes_out": (B, nc, 1, H * K)}
    out_shape = [jax.ShapeDtypeStruct(shapes[kind], dtype)
                 for kind, dtype in outs]
    return pl.pallas_call(
        kernel,
        name=name,
        grid=(B, nc, steps),
        in_specs=[specs[kind] for kind, _ in operands],
        out_specs=[specs[kind] for kind, _ in outs],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((states, rows, cols), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=transcendentals,
            bytes_accessed=sum(
                x.size * jnp.dtype(x.dtype).itemsize
                for x in [x for _, x in operands] + out_shape)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*(x for _, x in operands))


def differentiable(forward, backward, counter: str):
    """``call(q, k, v, g, beta, chunk, interpret) -> o``, differentiable in
    all five, of a kernel pair ``forward(q, k, v, g, beta, chunk=,
    interpret=, states=)`` (``o``, or with ``states`` ``(o, states, inv)``)
    and ``backward(q, k, v, g, beta, do, kept=(states, inv), chunk=,
    interpret=)`` (five gradients, the last two float32). Inside a block
    that ``models/layers.py:scan_blocks`` walks the call hands its forward
    pass, ``(o, states, inv)``, to the walk (``flash_attention.
    KeptForward``): the values and the backward kernel are the same. Counts,
    while it is traced, each forward kernel call in the gauge ``counter``
    (``telemetry/traced.py``)."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
    def _attend(q, k, v, g, beta, chunk, interpret, layers):
        traced.count(counter, layers=layers)
        return forward(q, k, v, g, beta, chunk=chunk, interpret=interpret)

    def _attend_fwd(q, k, v, g, beta, chunk, interpret, layers):
        traced.count(counter, layers=layers)
        o, *kept = forward(q, k, v, g, beta, chunk=chunk,
                           interpret=interpret, states=True)
        return o, (q, k, v, g, beta, *kept)

    def _attend_bwd(chunk, interpret, layers, res, do):
        q, k, v, g, beta, *kept = res
        dq, dk, dv, dg, dbeta = backward(q, k, v, g, beta, do, kept=kept,
                                         chunk=chunk, interpret=interpret)
        return dq, dk, dv, dg.astype(g.dtype), dbeta.astype(beta.dtype)

    _attend.defvjp(_attend_fwd, _attend_bwd)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
    def _attend_from(q, k, v, g, beta, o, states, inv, chunk, interpret):
        """``_attend`` where the forward kernel's three results are already
        in hand: the primal is ``o`` as given (no kernel), the backward is
        ``_attend``'s on the residuals ``_attend_fwd`` would have saved."""
        return o

    def _attend_from_fwd(q, k, v, g, beta, o, states, inv, chunk, interpret):
        return o, (q, k, v, g, beta, states, inv)

    def _attend_from_bwd(chunk, interpret, res, do):
        return _attend_bwd(chunk, interpret, None, res, do) \
            + (None, None, None)

    _attend_from.defvjp(_attend_from_fwd, _attend_from_bwd)

    def call(q, k, v, g, beta, chunk, interpret):
        def attend(saved):
            """The call in the part a ``KeptForward`` asks of it
            (``flash_attention.hand_over``): None the whole of it with its
            custom VJP, ``()`` the forward kernel alone (not
            differentiable), ``(o, states, inv)`` as that gave them the call
            from its saved forward."""
            if saved:
                return _attend_from(q, k, v, g, beta, *saved, chunk,
                                    interpret)
            if saved is None:
                return _attend(q, k, v, g, beta, chunk, interpret,
                               traced.stood_for())
            traced.count(counter)
            return forward(q, k, v, g, beta, chunk=chunk,
                           interpret=interpret, states=True)

        return hand_over(attend)

    return call
