"""Pallas TPU flash attention kernel (forward + backward).

The intra-device hot op: online-softmax blockwise attention computed in VMEM
(one pass over K/V blocks per Q block), MXU-shaped [block, head_dim] matmuls,
fp32 accumulators. Training-ready via ``jax.custom_vjp``: the forward saves
(O, LSE) residuals and the backward recomputes P blockwise — two kernels,
one accumulating dQ over K blocks, one accumulating dK/dV over Q blocks —
so no [T, T] matrix is ever materialised in HBM in either direction.

Usable standalone, as the ``inner`` of Ulysses sequence parallelism, or as
the per-block compute of ring attention. Runs in interpret mode off-TPU
(tests), compiled on TPU. Reference parity: none — the reference has no
fused attention at all (SURVEY.md §5.7); this is TPU-native surplus.

Sequence-length limit: T <= 8192 per call. Each grid step holds a whole
``(1, T, D)`` K and V block (forward, dQ) or Q and dO block (dK/dV) in
VMEM and only tiles the other operand, so VMEM use grows with T. The
v5e compiler (16 MiB scoped-VMEM limit) accepts forward+backward at
(B,H,T,D) = (1,12,8192,64) and refuses the backward at T = 16384 and the
forward at T = 32768 ("Scoped allocation with size 32.75M and limit
16.00M") — a compile error, never a wrong answer. Longer sequences go
through ring attention, which calls this kernel per T/P block.
tests/test_tpu_compile.py compiles the main-path shapes for a described
v5e.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

log = logging.getLogger(__name__)

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                causal: bool, scale: float, q_block: int, seq_len: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # [bq, D]
    bq, D = q.shape

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    o0 = jnp.zeros((bq, D), jnp.float32)

    n_blocks = seq_len // block_k

    def body(j, carry):
        m, l, o = carry
        k = k_ref[0, pl.dslice(j * block_k, block_k)].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * block_k, block_k)].astype(jnp.float32)
        s = q @ k.T                                   # [bq, bk]
        if causal:
            qpos = qi * q_block + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_blk = s.max(axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new <= _NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m - m_new)
        corr = jnp.where(m <= _NEG_INF / 2, 0.0, corr)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        o_new = o * corr + p @ v
        return m_new, l_new, o_new

    if causal:
        # Only blocks up to (and including) the diagonal contribute.
        hi = jnp.minimum(((qi + 1) * q_block + block_k - 1) // block_k,
                         n_blocks)
    else:
        hi = n_blocks
    m, l, o = jax.lax.fori_loop(0, hi, body, (m0, l0, o0))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(jnp.maximum(l, 1e-30))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               block_k: int, causal: bool, scale: float, q_block: int,
               seq_len: int):
    """One Q block: dQ = scale * sum_j dS_j @ K_j, with P recomputed from
    the saved LSE (no renormalisation pass needed)."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # [bq, D]
    do = do_ref[0].astype(jnp.float32)                # [bq, D]
    lse = lse_ref[0]                                  # [bq, 1]
    delta = delta_ref[0]                              # [bq, 1]
    bq, D = q.shape
    n_blocks = seq_len // block_k

    def body(j, dq):
        k = k_ref[0, pl.dslice(j * block_k, block_k)].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * block_k, block_k)].astype(jnp.float32)
        s = q @ k.T                                   # [bq, bk] (pre-scaled)
        if causal:
            qpos = qi * q_block + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse)                          # exact softmax probs
        dp = do @ v.T                                 # [bq, bk]
        ds = p * (dp - delta)
        return dq + ds @ k

    if causal:
        hi = jnp.minimum(((qi + 1) * q_block + block_k - 1) // block_k,
                         n_blocks)
    else:
        hi = n_blocks
    dq = jax.lax.fori_loop(0, hi, body, jnp.zeros((bq, D), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, block_q: int, causal: bool, scale: float,
                k_block: int, seq_len: int):
    """One K/V block: dV = sum_i P_i^T @ dO_i, dK = scale * sum_i dS_i^T @ Q_i."""
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)                  # [bk, D]
    v = v_ref[0].astype(jnp.float32)
    bk, D = k.shape
    n_blocks = seq_len // block_q

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.dslice(i * block_q, block_q)].astype(
            jnp.float32) * scale                      # [bq, D]
        do = do_ref[0, pl.dslice(i * block_q, block_q)].astype(jnp.float32)
        lse = lse_ref[0, pl.dslice(i * block_q, block_q)]   # [bq, 1]
        delta = delta_ref[0, pl.dslice(i * block_q, block_q)]
        s = q @ k.T                                   # [bq, bk]
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            kpos = ki * k_block + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dv_new = dv + p.T @ do
        dp = do @ v.T
        ds = p * (dp - delta)
        dk_new = dk + ds.T @ q
        return dk_new, dv_new

    if causal:
        # Q blocks strictly before this K block contribute nothing.
        lo = (ki * k_block) // block_q
    else:
        lo = 0
    dk, dv = jax.lax.fori_loop(
        lo, n_blocks, body,
        (jnp.zeros((bk, D), jnp.float32), jnp.zeros((bk, D), jnp.float32)))
    # q was pre-scaled, so dk already carries one factor of scale.
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _kernel_name(which: str, causal, scale, heads: int) -> str:
    """A stable name for each kernel: a device trace and the compiled HLO
    show it, so forward, dQ and dK/dV are told apart by name."""
    return f"tepdist_flash_{which}__c{int(causal)}__s{scale!r}__h{heads}"


def _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret):
    B, H, T, D = q.shape
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, causal=causal, scale=scale,
        q_block=block_q, seq_len=T)
    o, lse = pl.pallas_call(
        kernel,
        # The name tags the eqn so the seq-axis planner can motif-match
        # flash call sites in traced graphs (parallel/attention_motif.py)
        # — causal flag, softmax scale and head count ride along for the
        # rewrite (H lets the ulysses lowering un-flatten [B*H, T, D]).
        name=_kernel_name("fwd", causal, scale, H),
        grid=(B * H, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return o.reshape(B, H, T, D), lse.reshape(B, H, T)


def _bwd_call(causal, scale, block_q, block_k, interpret, res, do,
              dlse=None):
    q, k, v, o, lse = res
    B, H, T, D = q.shape
    BH = B * H
    qf, kf, vf = (x.reshape(BH, T, D) for x in (q, k, v))
    dof = do.reshape(BH, T, D)
    lsef = lse.reshape(BH, T, 1)
    # delta = rowsum(dO * O): cheap elementwise reduce, XLA fuses it.
    # An LSE cotangent folds in exactly here: dS = P * (dP - delta + dLSE)
    # (d lse / d s = P), so delta -= dlse reuses the unmodified kernels.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(BH, T, 1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32).reshape(BH, T, 1)

    full_spec = pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0))
    row_full = pl.BlockSpec((1, T, 1), lambda b, i: (b, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, causal=causal,
                          scale=scale, q_block=block_q, seq_len=T),
        name=_kernel_name("dq", causal, scale, H),
        grid=(BH, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            full_spec, full_spec,
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, causal=causal,
                          scale=scale, k_block=block_k, seq_len=T),
        name=_kernel_name("dkv", causal, scale, H),
        grid=(BH, T // block_k),
        in_specs=[
            full_spec,
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            full_spec, row_full, row_full,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v.dtype),
        ],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)
    shape = (B, H, T, D)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    o, _ = _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, do):
    return _bwd_call(causal, scale, block_q, block_k, interpret, res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_o_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    """(o, lse) flash: the LSE is a first-class differentiable output —
    the per-block form ring attention merges across hops."""
    return _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret)


def _flash_o_lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret)
    return (o, lse), (q, k, v, o, lse)


def _flash_o_lse_bwd(causal, scale, block_q, block_k, interpret, res, cts):
    do, dlse = cts
    return _bwd_call(causal, scale, block_q, block_k, interpret, res, do,
                     dlse=dlse)


_flash_o_lse.defvjp(_flash_o_lse_fwd, _flash_o_lse_bwd)


def _resolve_blocks(T: int, block_q: Optional[int],
                    block_k: Optional[int]) -> Optional[tuple]:
    """Shared block dispatch: (block_q, block_k), or None when no
    lane-aligned tile exists and the caller passed none (take a
    fallback). An explicitly-passed block wins even when no default
    exists; the missing one derives from its partner."""
    default = _default_block(T)
    if default is None and block_q is None and block_k is None:
        return None
    bq = min(block_q or block_k or default, T)
    bk = min(block_k or bq, T)
    if T % bq or T % bk:
        raise ValueError(f"seq len {T} must divide blocks {bq}/{bk}")
    return bq, bk


def flash_attention_with_lse(q, k, v, causal: bool = True,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """[B, H, T, D] -> (o [B, H, T, D], lse [B, H, T]), both
    differentiable (the lse cotangent folds into the bwd delta). Used as
    the per-hop inner of ring attention. Tile-less seq lens take the same
    fallbacks as ``flash_attention``: causal pads to the next 128 multiple
    (padded keys are masked, padded rows sliced — memory stays
    O(T*block)); only non-causal awkward T goes dense."""
    B, H, T, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    blocks = _resolve_blocks(T, block_q, block_k)
    if blocks is None:
        if causal:
            Tp = -(-T // 128) * 128
            pad = ((0, 0), (0, 0), (0, Tp - T), (0, 0))
            o, lse = flash_attention_with_lse(
                jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                causal=True, scale=scale, interpret=interpret)
            return o[:, :, :T, :], lse[:, :, :T]
        _log_dense_fallback(T)
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", p / jnp.maximum(l, 1e-30),
                       v.astype(jnp.float32))
        return o.astype(q.dtype), (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    block_q, block_k = blocks
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _flash_o_lse(q, k, v, causal, scale, block_q, block_k, interpret)


def _default_block(T: int) -> Optional[int]:
    """Largest divisor of T up to 512. On-chip sweep (v5e, GPT-2 1.5B
    training step, T=1024/D=64): 512x512 tiles beat the conventional
    128x128 by 39% end to end (8,495 vs 6,138 tok/s) — bigger tiles mean
    fewer grid steps, fewer LSE/accumulator round-trips, and longer MXU
    bursts; 1024 tiles regress (VMEM pressure). 512 caps the S-block at
    512*512*4B = 1 MiB of VMEM, safe alongside K/V for any practical D.
    Must DIVIDE T (grid constraint). Mosaic wants lane-aligned tiles, so
    only multiples of 128 (ideal) or 8 (acceptable) are returned; an
    awkward T (prime, 3*11*31, ...) gets None and the caller falls back
    to the einsum path rather than silently emitting 341- or 1-wide
    blocks that mis-tile the MXU."""
    for step in (128, 8):
        for b in range(min(T, 512) // step * step, 0, -step):
            if T % b == 0:
                return b
    return None


@functools.lru_cache(maxsize=None)
def _log_dense_fallback(T: int) -> None:
    """Runs at trace time; the cache makes it once per sequence length."""
    log.warning("flash_attention: no lane-aligned tile divides non-causal "
                "T=%d; tracing the dense O(T^2) einsum instead of the "
                "pallas kernel", T)


def _dense_attention(q, k, v, causal: bool, scale: float):
    """Einsum fallback for seq lens no lane-aligned tile divides."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q, k, v: [B, H, T, D] -> [B, H, T, D]. Differentiable (custom VJP)."""
    B, H, T, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    blocks = _resolve_blocks(T, block_q, block_k)
    if blocks is None:
        if causal:
            # Pad T up to the next multiple of 128 and slice the result:
            # under the causal mask real queries (pos < T) never attend
            # padded keys (pos >= T), and padded query rows are sliced
            # off (their cotangents are zero), so numerics are exact and
            # memory stays O(T*block) instead of the dense O(T^2).
            Tp = -(-T // 128) * 128
            pad = ((0, 0), (0, 0), (0, Tp - T), (0, 0))
            out = flash_attention(
                jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                causal=True, scale=scale, interpret=interpret)
            return out[:, :, :T, :]
        # Non-causal: padded keys would be attended; dense is the only
        # exact fallback (rare — awkward T with bidirectional attention).
        _log_dense_fallback(T)
        return _dense_attention(q, k, v, causal, scale)
    block_q, block_k = blocks
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _flash(q, k, v, causal, scale, block_q, block_k, interpret)
