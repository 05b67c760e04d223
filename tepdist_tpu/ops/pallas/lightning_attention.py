"""Pallas TPU decayed linear attention (Lightning Attention-2's chunked
form), forward and backward.

For one head with decay ``lam = exp(log_decay)`` in (0, 1] and a sequence
``q_t, k_t, v_t`` of ``D`` channels each:

    S_t = lam * S_{t-1} + k_t^T v_t          S_0 = 0,  S a [D, D] matrix
    o_t = q_t S_t = sum_{s <= t} lam^(t-s) (q_t . k_s) v_s

no softmax. The decay is one scalar a head, so a chunk of ``C`` tokens is
matmuls: with ``S`` the state before the chunk and ``i, j`` positions in it,

    o   = ((q k^T) * M) v + Lam * (q S)       M_ij = lam^(i-j) for j <= i
    S'  = lam^C S + (Gam * k)^T v             Lam_i = lam^(i+1)
                                              Gam_j = lam^(C-1-j)

**Memory in the sequence is O(chunk)**: the grid is ``(batch, heads, chunks)``
with the chunks sequential and the state in a VMEM scratch from one to the
next. The backward is two more sweeps and **keeps nothing but the operands**:
``tepdist_lightning_bwd_dq`` (the forward's sweep on other operands) walks
the chunks first to last and makes the states again as the forward did (``dq = ((do v^T) * M) k + Lam * (do S^T)``),
``tepdist_lightning_bwd_dkv`` walks them last to first with the gradient of
the state carried as the state was:

    dv = ((k q^T) * M^T) do + Gam * (k dS)    dk = ((v do^T) * M^T) q
    dS' = lam^C dS + (Lam * q)^T do                + Gam * (v dS^T)

No chunk-boundary state crosses HBM; the price is the ``k^T v`` product of
the forward once more.

Precision: the state, the decay factors and every accumulation are float32.
The matrix unit takes bf16, so a float32 operand of a matmul (the masked
scores, the state, the scaled keys and queries) goes in as two bf16 parts,
``x = hi + lo``, each product accumulated in float32: 16 bits of mantissa
where one rounding to bf16 keeps 8 (``_linear.py``, shared with
``kda_attention.py``). The operands ``q, k, v`` are read as they come. With
float32 operands (the CPU tests) every matmul is float32.

``q, k, v`` are ``[batch, T, heads * D]``, a projection's own layout (on
the chip ``[T, heads, D]`` is tiled otherwise and a reshape between the two
is a copy): the kernels read head ``h`` as lane block ``h``. ``log_decay``
is a float32 ``[heads]`` operand: which heads decay how fast is data, not
code. Any ``T``: the last chunk is padded with zero rows, which
add nothing to a state and whose outputs are dropped.

Kernel names ``tepdist_lightning_fwd`` / ``_bwd_dq`` / ``_bwd_dkv`` show in
a device trace and in the compiled HLO. Interpret mode off the TPU (tests),
compiled on it (``D`` a multiple of 128 there). ``tools/sala_bench.py`` times
them alone.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tepdist_tpu.ops.pallas import _interpret
from tepdist_tpu.ops.pallas._linear import (
    _BF16,
    _F32,
    _NN,
    _NT,
    _TN,
    _carried,
    _dot,
    _padded,
)
from tepdist_tpu.telemetry import traced

CHUNK = 256                 # tokens a grid step

traced.declare(
    "lin_attn_calls", "forward linear-attention kernel calls a micro batch "
    "(a rematerialised layer's second run counted)")


def _decays(ld, C: int):
    """``Lam`` and ``Gam`` [C, 1] and ``lam^C``, from the head's log decay."""
    row = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0).astype(_F32)
    return jnp.exp(ld * (row + 1.0)), jnp.exp(ld * (C - 1.0 - row)), \
        jnp.exp(ld * C)


def _set_mask(m_scr, ld, transposed: bool):
    """``M`` (or ``M^T``) into its scratch, once a head."""
    C = m_scr.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    ahead = (j - i) if transposed else (i - j)
    m_scr[...] = jnp.where(
        ahead >= 0, jnp.exp(ld * jnp.maximum(ahead, 0).astype(_F32)), 0.0)


def _begin(ld_ref, state_scr, m_scr, transposed: bool):
    """A grid step's decay factors; at a head's first step the state (or its
    gradient) zeroed and the mask made."""
    ld = ld_ref[pl.program_id(1)]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_scr[...] = jnp.zeros(state_scr.shape, _F32)
        _set_mask(m_scr, ld, transposed)

    return _decays(ld, m_scr.shape[0])


def _sweep_kernel(ld_ref, a_ref, k_ref, v_ref, out_ref, s_scr, m_scr, *,
                  grad: bool, narrow, state_dtype):
    """First chunk to last with the state carried. The forward (``a`` is
    ``q``): ``o = ((q k^T) * M) v + Lam * (q S)``. ``grad`` (``a`` is ``d
    o``): ``dq = ((do v^T) * M) k + Lam * (do S^T)``, the states made again
    as the forward made them."""
    lam_in, gam, lam_c = _begin(ld_ref, s_scr, m_scr, False)
    a, k, v = a_ref[...], k_ref[...], v_ref[...]
    with_, onto, state_dims = (v, k, _NT) if grad else (k, v, _NN)
    state = s_scr[...]
    out = _dot(_dot(a, with_, _NT, narrow) * m_scr[...], onto, _NN, narrow) \
        + lam_in * _dot(a, state, state_dims, narrow)
    out_ref[...] = out.astype(out_ref.dtype)
    s_scr[...] = _carried(
        lam_c * state + _dot(k.astype(_F32) * gam, v, _TN, narrow),
        state_dtype)


def _dkv_kernel(ld_ref, q_ref, k_ref, v_ref, do_ref, dk_ref, dv_ref, ds_scr,
                m_scr, *, narrow, state_dtype):
    """Last chunk to first with the state's gradient carried."""
    lam_in, gam, lam_c = _begin(ld_ref, ds_scr, m_scr, True)
    q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
    d_state = ds_scr[...]
    mask_t = m_scr[...]
    dv = _dot(_dot(k, q, _NT, narrow) * mask_t, do, _NN, narrow) \
        + gam * _dot(k, d_state, _NN, narrow)
    dk = _dot(_dot(v, do, _NT, narrow) * mask_t, q, _NN, narrow) \
        + gam * _dot(v, d_state, _NT, narrow)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    ds_scr[...] = _carried(
        lam_c * d_state + _dot(q.astype(_F32) * lam_in, do, _TN, narrow),
        state_dtype)


def _call(kernel, name, operands, n_out, log_decay, *, chunk, reverse,
          matmuls, interpret, out_dtype=None, state_dtype=None):
    """One sweep over the chunks of ``operands`` ([B, T, H * D] each, padded
    here to whole chunks): ``n_out`` results of the same shape, in
    ``out_dtype`` (the operands' where None)."""
    length = operands[0].shape[1]
    operands = [_padded(x, chunk) for x in operands]
    B, T, HD = operands[0].shape
    H = log_decay.shape[0]
    D = HD // H
    nc = T // chunk
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    spec = pl.BlockSpec((None, chunk, D), lambda b, h, c, ld: (b, at(c), h))
    shape = jax.ShapeDtypeStruct((B, T, H * D),
                                 out_dtype or operands[0].dtype)
    size = operands[0].dtype.itemsize
    out = pl.pallas_call(
        functools.partial(kernel, narrow=operands[0].dtype == _BF16,
                          state_dtype=state_dtype),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H, nc),
            in_specs=[spec] * len(operands), out_specs=[spec] * n_out,
            scratch_shapes=[pltpu.VMEM((D, D), _F32),
                            pltpu.VMEM((chunk, chunk), _F32)]),
        out_shape=[shape] * n_out,
        cost_estimate=pl.CostEstimate(
            flops=2 * matmuls * B * H * T * D * (chunk + D) // 2,
            transcendentals=B * H * (chunk * chunk + 2 * T),
            bytes_accessed=size * (len(operands) + n_out) * B * T * H * D),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(log_decay.astype(_F32), *operands)
    return [o[:, :length] for o in out]


def forward(q, k, v, log_decay, *, chunk: int = CHUNK, interpret=None,
            **how):
    """The forward kernel alone. ``how``: a check's ``out_dtype`` (results
    in float32, not rounded to the operands' dtype) and ``state_dtype`` (the
    carried state through a narrower dtype: the check's control)."""
    return _call(functools.partial(_sweep_kernel, grad=False),
                 "tepdist_lightning_fwd", [q, k, v], 1, log_decay,
                 chunk=chunk, reverse=False, matmuls=4,
                 interpret=_interpret(interpret), **how)[0]


def backward(q, k, v, log_decay, do, *, chunk: int = CHUNK, interpret=None,
             **how):
    """``(dq, dk, dv)`` by the two backward kernels."""
    interpret = _interpret(interpret)
    dq, = _call(functools.partial(_sweep_kernel, grad=True),
                "tepdist_lightning_bwd_dq", [do, k, v], 1, log_decay,
                chunk=chunk, reverse=False, matmuls=4, interpret=interpret,
                **how)
    dk, dv = _call(_dkv_kernel, "tepdist_lightning_bwd_dkv", [q, k, v, do],
                   2, log_decay, chunk=chunk, reverse=True, matmuls=7,
                   interpret=interpret, **how)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attend(q, k, v, log_decay, chunk, interpret, layers):
    traced.count("lin_attn_calls", layers=layers)
    return forward(q, k, v, log_decay, chunk=chunk, interpret=interpret)


def _attend_fwd(q, k, v, log_decay, chunk, interpret, layers):
    return _attend(q, k, v, log_decay, chunk, interpret, layers), \
        (q, k, v, log_decay)


def _attend_bwd(chunk, interpret, layers, res, do):
    q, k, v, log_decay = res
    # The decay is a convention of the model and no parameter: no gradient.
    return backward(q, k, v, log_decay, do, chunk=chunk,
                    interpret=interpret) + (jnp.zeros_like(log_decay),)


_attend.defvjp(_attend_fwd, _attend_bwd)


def lightning_attention(q, k, v, log_decay, *, chunk: int = CHUNK,
                        interpret: Optional[bool] = None):
    """``o_t = sum_{s <= t} lam_h^(t-s) (q_t . k_s) v_s``: ``q, k, v``
    [batch, T, heads * D], ``log_decay`` float32 [heads] (``log lam_h``, at
    most 0) -> [batch, T, heads * D] in ``q``'s dtype. Differentiable in
    ``q, k, v``; ``log_decay`` gets a zero gradient. No scale is applied:
    the caller's ``q`` carries it.

    Counts, while it is traced, each forward kernel call in
    ``lin_attn_calls`` (``telemetry/traced.py``)."""
    if q.shape != k.shape or q.shape != v.shape or q.ndim != 3 \
            or log_decay.ndim != 1 or q.shape[2] % log_decay.shape[0] \
            or chunk % 8:
        raise ValueError(
            f"lightning_attention: q {q.shape}, k {k.shape}, v {v.shape}, "
            f"log_decay {log_decay.shape}, chunk {chunk}")
    chunk = min(chunk, -(-q.shape[1] // 8) * 8)
    return _attend(q, k, v, log_decay, chunk, _interpret(interpret),
                   traced.stood_for())
