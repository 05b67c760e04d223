"""Pallas TPU kernels for multi-head latent attention's core (DeepSeek-V2's
MLA as trained: keys and values up-projected, a decoupled rotary part).

A head's score has two parts and its value another width:

    s_h = (q_nope_h k_nope_h^T + q_rope_h k_rope^T) * scale
    o_h = softmax_causal(s_h) v_h

with ``q_nope``, ``k_nope`` [B, H, T, Dn] a head, ``q_rope`` [B, H, T, Dr] a
head, **``k_rope`` [B, 1, T, Dr] one array that all heads read** and ``v``,
``o`` [B, H, T, Dv] (sarvam-105b: 128 + 64 and 128). ``flash_attention``
has one ``D`` for q, k and v; through it this layer would join a broadcast
``k_rope`` to every head in HBM and pad ``v`` to the keys' 192, for 1.5
times the value matmuls' work and bytes. Here no key or value is wider than
the model has it: the two score parts are two matmuls into one float32
tile, ``k_rope`` reaches every head's grid step **through the index map**
(head ``b`` reads batch row ``b // H``, as ``flash_attention._kv_spec``
serves a grouped-query group; no broadcast copy exists in HBM) and its
gradient is the sum over the heads of what each head's dK/dV step wrote,
taken in float32 outside the kernel.

Two kernels, ``tepdist_mla_fwd__…`` and, the whole backward pass,
``tepdist_mla_dkv__…`` (causal flag, scale and heads in the name as the
flash kernels carry them; not ``tepdist_flash_*``, whose readers cost a
call by one ``D``), under one ``jax.custom_vjp``. They are the flash
kernels' algorithm on two more operands (the backward one kernel as theirs:
it walks a K/V block's Q blocks, makes each pair's ``P^T`` and ``dS^T`` once
and adds them into dV, both parts of dK and both parts of the head's dQ^T;
the dQ and dK/dV pair it was computed a pair's two score products,
exponentials and ``dO V^T`` twice; PERF.md, PR 47) and share its pieces
(``flash_attention.py``: the transposed score tile and its masks
``_scores_t``, the block walks ``_over_key_blocks`` /
``_over_query_blocks``, ``_resolve_blocks``, the row statistics' ``[B*H,
T/bq, 1, bq]`` layout, ``_compiler_params``) and its precision: operands as
they arrive, P and dS rounded to them, float32 scores, statistics and
accumulators. Inside a block that ``models/layers.py:scan_blocks`` walks a
call hands its forward pass ``(o, lse)`` to the walk
(``flash_attention.hand_over``), so the forward kernel runs once a layer and
micro batch.

Each grid step holds the whole-sequence operands of its head in VMEM
(forward: ``k_nope``, ``k_rope``, ``v``; backward: ``q_nope``, ``q_rope``,
``dO``); a ``Dr`` of 64 fills half of a 128-lane tile there, so the three
count as three ``[T, 128]`` arrays and a call asks for its scoped VMEM as
``_compiler_params`` reckons it (T = 16,384 in bf16: 24 MiB double-buffered,
a limit of 40 forward). The backward also holds the head's two dQ^T in
float32 and its two dQ result blocks, double-buffered (``_bwd_holds``: 12
and 16 MiB there, a limit of 68 of the chip's 128). ``tools/mla_bench.py``
times the kernels alone on the chip and holds them to dense float32
attention; ``tests/test_tpu_compile.py`` compiles them, and the cell's step
with them, for a described v5e.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tepdist_tpu.ops.pallas import _interpret
from tepdist_tpu.ops.pallas.flash_attention import (
    _NEG_INF,
    _NT,
    _TN,
    _compiler_params,
    _dot,
    _fold_scale,
    _over_key_blocks,
    _over_query_blocks,
    _resolve_blocks,
    _scores_t,
    hand_over,
)
from tepdist_tpu.telemetry import traced

traced.declare(
    "mla_fwd_calls", "calls a micro batch that run the latent-attention "
    "forward kernel (ops/pallas/mla_attention.py): one a walked layer, whose "
    "recomputation takes the kept forward; two a layer where nothing is kept")
traced.declare(
    "mla_bwd_calls", "differentiated latent-attention calls a micro batch: "
    "each one's backward pass is one kernel (``tepdist_mla_dkv``: both parts "
    "of dq, dk_nope, a head's part of dk_rope and dv)")


def _fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref, *,
                block_k: int, causal: bool, scale: float, q_block: int,
                seq_len: int):
    """One Q block of one head against the K/V blocks up to its diagonal:
    ``flash_attention._fwd_kernel`` with the scores' second part."""
    qi = pl.program_id(1)
    qn, qr = qn_ref[0], qr_ref[0]                     # [bq, Dn], [bq, Dr]
    bq, Dv = qn.shape[0], v_ref.shape[-1]
    fold = _fold_scale(qn.dtype, scale)
    if fold:
        qn, qr = qn * scale, qr * scale

    def step(j, carry, causal_from, window_from=None):
        m, l, ot = carry
        keys = pl.dslice(j * block_k, block_k)
        v = v_ref[0, keys]
        st = _scores_t(kn_ref[0, keys], qn, scale, fold, causal_from,
                       window_from, shared=(kr_ref[0, keys], qr))
        m_new = jnp.maximum(m, st.max(axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)                      # [bk, bq]
        corr = jnp.exp(m - m_new)
        l_new = l * corr + pt.sum(axis=0, keepdims=True)
        return m_new, l_new, ot * corr + _dot(v, pt.astype(v.dtype), _TN)

    carry = (jnp.full((1, bq), _NEG_INF, jnp.float32),
             jnp.zeros((1, bq), jnp.float32),
             jnp.zeros((Dv, bq), jnp.float32))
    m, l, ot = _over_key_blocks(step, carry, causal, qi, q_block, block_k,
                                seq_len // block_k)
    o_ref[0] = (ot * (1.0 / l)).T.astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)                    # [1, bq]


def _bwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                delta_ref, dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
                dqn_t_ref, dqr_t_ref, *, block_q: int, causal: bool,
                scale: float, k_block: int, seq_len: int):
    """One K/V block of one head over the Q blocks that see it, as
    ``flash_attention._bwd_kernel``: ``P_i^T`` and ``dS_i^T`` are made once
    a pair and feed dV, the head's own dK, **this head's part** of the
    shared key's gradient, and both parts of Q block ``i``'s dQ^T, ``K_nope^T
    dS_i^T`` into ``dqn_t_ref`` [T/bq, Dn, bq] and ``K_rope^T dS_i^T`` into
    ``dqr_t_ref`` [T/bq, Dr, bq]: the head's whole dQ^T in float32, which
    stays in VMEM over the head's key blocks (the grid's inner axis, in
    rising order: the order a walk over a Q block's key blocks sums in),
    zeroed at the first, scaled, transposed and written as ``dq_nope`` [T,
    Dn] and ``dq_rope`` [T, Dr] at the last."""
    ki = pl.program_id(1)
    kn, kr, v = kn_ref[0], kr_ref[0], v_ref[0]
    bk = kn.shape[0]
    fold = _fold_scale(kn.dtype, scale)
    n_q = seq_len // block_q

    @pl.when(ki == 0)
    def _():
        dqn_t_ref[...] = jnp.zeros(dqn_t_ref.shape, jnp.float32)
        dqr_t_ref[...] = jnp.zeros(dqr_t_ref.shape, jnp.float32)

    def step(i, carry, causal_from, window_from=None):
        dkn, dkr, dv = carry
        rows = pl.dslice(i * block_q, block_q)
        qn, qr, do = qn_ref[0, rows], qr_ref[0, rows], do_ref[0, rows]
        if fold:
            qn, qr = qn * scale, qr * scale
        st = _scores_t(kn, qn, scale, fold, causal_from, window_from,
                       shared=(kr, qr))
        pt = jnp.exp(st - lse_ref[0, i])              # P^T [bk, bq]
        dv = dv + _dot(pt.astype(do.dtype), do)
        dst = (pt * (_dot(v, do, _NT) - delta_ref[0, i])).astype(qn.dtype)
        # The key block from its ref, not ``kn`` / ``kr`` above: a loop-
        # invariant transposed operand shared between the loop and its
        # straight-line edge step is refused by the compiler (PERF.md, PR 46).
        dqn_t_ref[i] += _dot(kn_ref[0], dst, _TN)
        dqr_t_ref[i] += _dot(kr_ref[0], dst, _TN)
        return dkn + _dot(dst, qn), dkr + _dot(dst, qr), dv

    dkn, dkr, dv = _over_query_blocks(
        step, (jnp.zeros((bk, kn.shape[1]), jnp.float32),
               jnp.zeros((bk, kr.shape[1]), jnp.float32),
               jnp.zeros((bk, v.shape[1]), jnp.float32)),
        causal, ki, block_q, k_block, n_q)
    if not fold:                  # folded into q, dk already carries it
        dkn, dkr = dkn * scale, dkr * scale
    dkn_ref[0] = dkn.astype(dkn_ref.dtype)
    dkr_ref[0] = dkr.astype(dkr_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(ki == seq_len // k_block - 1)
    def _():
        def write(i, _):
            rows = pl.dslice(i * block_q, block_q)
            dqn_ref[0, rows] = (dqn_t_ref[i] * scale).T.astype(dqn_ref.dtype)
            dqr_ref[0, rows] = (dqr_t_ref[i] * scale).T.astype(dqr_ref.dtype)
            return _

        jax.lax.fori_loop(0, n_q, write, None)


def _kernel_name(which: str, causal, scale, heads: int) -> str:
    """As ``flash_attention._kernel_name``: a device trace and the compiled
    HLO tell forward and backward apart by it (the backward keeps ``dkv``,
    the name its dK/dV half had: trace readers go by it)."""
    return f"tepdist_mla_{which}__c{int(causal)}__s{scale!r}__h{heads}"


def _vmem(T: int, itemsize: int, more: int = 0):
    """The three whole-sequence operands of a grid step fill three ``[T,
    128]`` tiles' worth of VMEM: ``_compiler_params``' two at 192, and
    ``more`` bytes the call holds beside them (:func:`_bwd_holds`)."""
    return _compiler_params(T, 192, itemsize, more)


def _bwd_holds(T: int, Dn: int, Dr: int, itemsize: int) -> int:
    """Bytes the backward call holds whole beside ``q_nope``, ``q_rope`` and
    ``dO``: the head's two dQ^T in float32 and its two dQ result blocks,
    double-buffered, each of those at whole 128-lane tiles."""
    lanes = sum(-(-D // 128) * 128 for D in (Dn, Dr))
    return T * (Dn + Dr) * 4 + 2 * T * lanes * itemsize


def _specs(H: int, block: int, T: int, widths):
    """(a block of ``block`` rows, the whole sequence) BlockSpecs of a
    head's own operand of each of ``widths`` under the grid ``(B * H,
    blocks)``, and the same two of the key part all heads share, which head
    ``b`` reads at batch row ``b // H``."""
    Dr = widths[1]
    own = [(pl.BlockSpec((1, block, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0))) for D in widths]
    shared = (pl.BlockSpec((1, block, Dr), lambda b, i: (b // H, i, 0)),
              pl.BlockSpec((1, T, Dr), lambda b, i: (b // H, 0, 0)))
    return own, shared


def _flat(x):
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def _fwd_call(q_nope, q_rope, k_nope, k_rope, v, causal, scale, block_q,
              block_k, interpret):
    B, H, T, Dn = q_nope.shape
    Dr, Dv = q_rope.shape[-1], v.shape[-1]
    (nope, rope, val), shared = _specs(H, block_q, T, (Dn, Dr, Dv))
    rows = (B * H, T // block_q, 1, block_q)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=block_k, causal=causal,
                          scale=scale, q_block=block_q, seq_len=T),
        name=_kernel_name("fwd", causal, scale, H),
        grid=(B * H, T // block_q),
        in_specs=[nope[0], rope[0], nope[1], shared[1], val[1]],
        out_specs=[val[0], pl.BlockSpec((1, 1, 1, block_q),
                                        lambda b, i: (b, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B * H, T, Dv), v.dtype),
                   jax.ShapeDtypeStruct(rows, jnp.float32)],
        compiler_params=_vmem(T, q_nope.dtype.itemsize),
        interpret=interpret,
    )(_flat(q_nope), _flat(q_rope), _flat(k_nope), _flat(k_rope), _flat(v))
    return o.reshape(B, H, T, Dv), lse.reshape(B, H, T)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def _bwd_call(causal, scale, block_q, block_k, interpret, res, do):
    q_nope, q_rope, k_nope, k_rope, v, o, lse = res
    B, H, T, Dn = q_nope.shape
    Dr, Dv = q_rope.shape[-1], v.shape[-1]
    BH = B * H
    rows = (BH, T // block_q, 1, block_q)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(rows)
    row_full = pl.BlockSpec((1,) + rows[1:], lambda b, i: (b, 0, 0, 0))
    itemsize = q_nope.dtype.itemsize
    (nope, rope, val), shared = _specs(H, block_k, T, (Dn, Dr, Dv))
    # One call for the whole backward pass, under the name and with the
    # operands the dK/dV kernel had (a device trace's readers find it by
    # them); both parts of dq join its results, first.
    dqn, dqr, dkn, dkr, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, block_q=block_q, causal=causal,
                          scale=scale, k_block=block_k, seq_len=T),
        name=_kernel_name("dkv", causal, scale, H),
        grid=(BH, T // block_k),
        in_specs=[nope[1], rope[1], nope[0], shared[0], val[0], val[1],
                  row_full, row_full],
        out_specs=[nope[1], rope[1], nope[0], rope[0], val[0]],
        out_shape=[jax.ShapeDtypeStruct((BH, T, Dn), q_nope.dtype),
                   jax.ShapeDtypeStruct((BH, T, Dr), q_rope.dtype),
                   jax.ShapeDtypeStruct((BH, T, Dn), k_nope.dtype),
                   jax.ShapeDtypeStruct((BH, T, Dr), k_rope.dtype),
                   jax.ShapeDtypeStruct((BH, T, Dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((T // block_q, Dn, block_q), jnp.float32),
                        pltpu.VMEM((T // block_q, Dr, block_q), jnp.float32)],
        compiler_params=_vmem(T, itemsize, _bwd_holds(T, Dn, Dr, itemsize)),
        interpret=interpret,
    )(_flat(q_nope), _flat(q_rope), _flat(k_nope), _flat(k_rope), _flat(v),
      _flat(do), lse.reshape(rows), delta)
    # Each head wrote its part of the shared key's gradient; their sum is
    # the head broadcast's transpose, without the broadcast.
    dkr = jnp.sum(dkr.reshape(B, H, T, Dr), axis=1, keepdims=True,
                  dtype=jnp.float32).astype(k_rope.dtype)
    return (dqn.reshape(q_nope.shape), dqr.reshape(q_rope.shape),
            dkn.reshape(k_nope.shape), dkr, dv.reshape(v.shape))


# ``layers``, the last static argument of the two calls below: the runs one
# trace of the call stands for (``traced.stood_for()`` where it is called, as
# the flash calls carry it), for the forward rules' count of
# ``mla_bwd_calls``.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _mla(q_nope, q_rope, k_nope, k_rope, v, causal, scale, block_q, block_k,
         interpret, layers):
    return _fwd_call(q_nope, q_rope, k_nope, k_rope, v, causal, scale,
                     block_q, block_k, interpret)[0]


def _mla_fwd(q_nope, q_rope, k_nope, k_rope, v, *static):
    *static, layers = static
    traced.count("mla_bwd_calls", layers=layers)
    o, lse = _fwd_call(q_nope, q_rope, k_nope, k_rope, v, *static)
    return o, (q_nope, q_rope, k_nope, k_rope, v, o, lse)


def _mla_bwd(causal, scale, block_q, block_k, interpret, layers, res, do):
    return _bwd_call(causal, scale, block_q, block_k, interpret, res, do)


_mla.defvjp(_mla_fwd, _mla_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _mla_from(q_nope, q_rope, k_nope, k_rope, v, o, lse, causal, scale,
              block_q, block_k, interpret, layers):
    """``_mla`` where the forward kernel's two outputs are already in hand:
    the primal is ``o`` as given (no kernel), the backward is ``_mla``'s on
    the residuals ``_mla_fwd`` would have saved."""
    return o


def _mla_from_fwd(q_nope, q_rope, k_nope, k_rope, v, o, lse, *static):
    traced.count("mla_bwd_calls", layers=static[-1])
    return o, (q_nope, q_rope, k_nope, k_rope, v, o, lse)


def _mla_from_bwd(*static_res_do):
    return _mla_bwd(*static_res_do) + (None, None)


_mla_from.defvjp(_mla_from_fwd, _mla_from_bwd)


def mla_attention(q_nope, q_rope, k_nope, k_rope, v, causal: bool = True,
                  scale: Optional[float] = None,
                  block_q: Optional[int] = None,
                  block_k: Optional[int] = None,
                  interpret: Optional[bool] = None):
    """q_nope, k_nope [B, H, T, Dn], q_rope [B, H, T, Dr], k_rope [B, 1, T,
    Dr], v [B, H, T, Dv] -> o [B, H, T, Dv]. Differentiable (custom VJP) in
    all five; ``k_rope``'s gradient is the sum over the heads. ``scale``
    (``(Dn + Dr) ** -0.5`` where none is given) multiplies the sum of both
    score parts. ``T`` needs a lane-aligned tile
    (``flash_attention._default_block``).

    Inside a block that ``models/layers.py:scan_blocks`` walks the call
    hands its forward pass to the walk (``flash_attention.KeptForward``): the
    values and the backward kernel are the same."""
    return hand_over(functools.partial(
        mla_attention_kept, q_nope, q_rope, k_nope, k_rope, v, causal=causal,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret))


def mla_attention_kept(q_nope, q_rope, k_nope, k_rope, v, forward,
                       causal: bool = True, scale: Optional[float] = None,
                       block_q: Optional[int] = None,
                       block_k: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """:func:`mla_attention` in the part a ``KeptForward`` asks of a call, as
    ``flash_attention_kept``: ``forward`` None the whole of it with its
    custom VJP, ``()`` the forward kernel alone (``(o, lse [B, H, T]
    float32)``, not differentiable), ``(o, lse)`` as that gave them the
    attention from its saved forward (primal ``o``, no kernel; the backward
    kernel as :func:`mla_attention`'s, bit for bit)."""
    B, H, T, Dn = q_nope.shape
    Dr = q_rope.shape[-1]
    if q_rope.shape != (B, H, T, Dr) or k_nope.shape != q_nope.shape \
            or k_rope.shape != (B, 1, T, Dr) or v.shape[:3] != (B, H, T):
        raise ValueError(
            f"mla_attention: q_nope {q_nope.shape}, q_rope {q_rope.shape}, "
            f"k_nope {k_nope.shape}, k_rope {k_rope.shape}, v {v.shape}")
    blocks = _resolve_blocks(T, block_q, block_k)
    if blocks is None:
        raise ValueError(f"mla_attention: no lane-aligned tile divides T={T}")
    scale = scale if scale is not None else 1.0 / math.sqrt(Dn + Dr)
    static = (causal, scale, *blocks, _interpret(interpret))
    operands = (q_nope, q_rope, k_nope, k_rope, v)
    if forward:
        return _mla_from(*operands, *forward, *static, traced.stood_for())
    traced.count("mla_fwd_calls")
    if forward is None:
        return _mla(*operands, *static, traced.stood_for())
    return _fwd_call(*operands, *static)
