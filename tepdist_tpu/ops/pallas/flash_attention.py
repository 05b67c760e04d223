"""Pallas TPU flash attention kernel (forward + backward).

The intra-device hot op: online-softmax blockwise attention computed in VMEM
(one pass over K/V blocks per Q block). Training-ready via
``jax.custom_vjp``: the forward saves (O, LSE) residuals and the backward
recomputes P blockwise in one kernel, which walks a K/V block's Q blocks,
makes each pair's ``P^T`` and ``dS^T`` once and adds them into dK, dV and the
head's dQ^T, so no [T, T] matrix is ever materialised in HBM in either
direction and a pair's scores, exponentials and ``dO V^T`` are computed once
(two kernels, one a gradient side, computed them twice: seven matmuls a pair
for the backward pass's five; PERF.md, PR 46).

Precision follows the inputs' dtype and nothing else. The MXU takes q, k, v
and dO as they arrive and P and dS rounded to that dtype (as the model's
own einsum attention rounds its probabilities), and accumulates in float32;
scores, running max and sum, ``exp``, the output and gradient accumulators,
the log-sum-exp and ``delta`` are float32 whatever the inputs. float32
inputs get float32 matmuls. (On the v5e the compiler already fed float32
operands to the MXU in one bf16 pass: bf16 results were identical to the
last digit before and after; PERF.md, PR 25.)

Both kernels compute the scores transposed, ``S^T = K Q^T`` [bk, bq],
keys on the rows and queries on the lanes. The softmax statistics are then
reductions over rows (elementwise maxima and adds of vector registers
instead of a cross-lane reduction per eight rows, which took 40% of the
forward), they are [1, bq] rows, and ``P^T`` and ``dS^T`` are what the
dK/dV matmuls take. The log-sum-exp and ``delta`` cross HBM in that layout,
``[B*H, T/bq, 1, bq]`` with a Q block's rows on the lanes: one float32 a
row. (As ``[B*H, T, 1]`` they were tiled (8, 128), 128 times their size.)

Usable standalone, as the ``inner`` of Ulysses sequence parallelism, or as
the per-block compute of ring attention. Runs in interpret mode off-TPU
(tests), compiled on TPU. Reference parity: none — the reference has no
fused attention at all (SURVEY.md §5.7); this is TPU-native surplus.

Sequence length: each grid step holds a whole ``(1, T, D)`` K and V block
(forward) or Q and dO block (backward) in VMEM and only tiles the other
operand, and the backward holds the head's dQ^T in float32 and its ``(1, T,
D)`` dQ block beside them, so VMEM use grows with T. Under the v5e
compiler's default limit for one kernel (16 MiB of scoped VMEM) the forward
compiles up to (B,H,T,D) = (1,12,8192,64) in bf16 and float32,
(1,32,8192,128) and (1,12,16384,64) in bf16, and the backward to half those
lengths: those calls are compiled as they always were. Past that (what a
grid step holds whole, double-buffered where it is an operand or a result,
over 8 MiB) the call asks for the limit it needs (``_compiler_params``), of
the chip's 128 MiB: 32 MiB forward and 48 backward at T = 16384 and D = 128
in bf16, 32 backward at T = 8192. What still does not fit is a compile
error, never a wrong answer; longer sequences go through ring attention,
which calls this kernel per T/P block. tests/test_tpu_compile.py compiles
the main-path shapes for a described v5e; tools/flash_bench.py times the
two kernels alone on the chip.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tepdist_tpu.ops.pallas import _interpret
from tepdist_tpu.telemetry import traced

log = logging.getLogger(__name__)

traced.declare(
    "flash_bwd_calls", "differentiated flash calls a micro batch: each one's "
    "backward pass is one kernel (``tepdist_flash_dkv``: dq, dk and dv)")

_NEG_INF = -1e30
_MIB = 2 ** 20


def _compiler_params(T: int, D: int, itemsize: int, more: int = 0):
    """None (the compiler's default scoped-VMEM limit, 16 MiB a kernel)
    where the two whole-sequence operands of a grid step, double-buffered,
    and ``more`` bytes the call holds beside them (:func:`_bwd_holds`) leave
    room under it for the tiles, accumulators and score blocks; past that, a
    limit of their size and 16 MiB more."""
    whole = 2 * 2 * T * D * itemsize + more
    if whole <= 8 * _MIB:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=whole + 16 * _MIB)


def _bwd_holds(T: int, D: int, itemsize: int) -> int:
    """Bytes the backward call holds whole beside q and dO: the head's dQ^T
    in float32 and its dQ result block, double-buffered."""
    return T * D * 4 + 2 * T * D * itemsize


_NT = (((1,), (1,)), ((), ()))    # a @ b.T: contract the last dim of both
_TN = (((0,), (0,)), ((), ()))    # a.T @ b: contract the first dim of both


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """MXU matmul on the operands as they are, float32 accumulation.
    float32 operands take their passes from ``jax_default_matmul_precision``
    as before (one bf16 pass by default; ``highest`` ran the parent's
    kernels four times slower); narrower operands have no more bits to
    give and say so, or Mosaic refuses them under that setting."""
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _fold_scale(dtype, scale: float) -> bool:
    """Whether q may carry the softmax scale into the matmul: always in
    float32 (as before), in a narrower dtype only where the product is
    exact (a power of two, 0.125 at D = 64). Otherwise the float32 scores
    are scaled."""
    return dtype == jnp.float32 or math.frexp(scale)[0] == 0.5


def _scores_t(k, q, scale, fold, causal_from=None, window_from=None,
              shared=None):
    """Transposed scores [keys, queries] in float32. Keys on the rows and
    queries on the lanes: the softmax statistics are then reductions over
    rows (vector maxima and adds, no cross-lane work) and [1, queries]
    rows, the layout they are stored in, and P^T and dS^T are what the
    backward matmuls take. ``causal_from`` is the (key, query) position of
    ``st[0, 0]`` where the diagonal may cross the tile: keys after the
    query's own position go to -inf. ``window_from`` is (key, query,
    window) where the window's far edge may cross it: keys ``window`` or
    more positions before the query go to -inf too. ``shared`` is a second
    ``(k, q)`` pair whose product joins the scores before the scale: the key
    part all heads of a latent-attention layer read
    (``ops/pallas/mla_attention.py``)."""
    st = _dot(k, q, _NT)
    if shared is not None:
        st = st + _dot(*shared, _NT)
    if not fold:
        st = st * scale
    if causal_from is not None or window_from is not None:
        ahead = (jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
                 - jax.lax.broadcasted_iota(jnp.int32, st.shape, 0))
        if causal_from is not None:
            k0, q0 = causal_from
            st = jnp.where(ahead >= k0 - q0, st, _NEG_INF)
        if window_from is not None:
            k0, q0, window = window_from
            st = jnp.where(ahead < window + k0 - q0, st, _NEG_INF)
    return st


def _once_if(ok, j, body, carry):
    """``body(j, carry)`` if ``ok`` (traced), else ``carry``: a loop of one
    trip or none, so that an edge block keeps its mask at a fixed place."""
    j = jnp.where(ok, j, 0)
    return jax.lax.fori_loop(j, jnp.where(ok, j + 1, j), body, carry)


def _over_key_blocks(step, carry, causal, qi, block_q, block_k, n_blocks,
                     window=None):
    """``step(j, carry, causal_from)`` over the K/V blocks Q block ``qi``
    sees; ``causal_from`` is ``_scores_t``'s. With equal tiles the diagonal
    crosses only the block of the Q block's own positions: the blocks below
    it run unmasked in the loop and that one after it, outside, its mask at
    a fixed place (straight-line code with a constant mask took 15% off the
    forward; a mask at a computed place costs as much as the loop did;
    PERF.md, PR 25). Blocks above the diagonal are skipped.

    With a ``window`` (causal; key j visible to query i iff ``0 <= i - j <
    window``) ``step`` takes ``window_from`` too, and the blocks wholly
    before the window are skipped as those above the diagonal are. Where
    equal tiles divide the window its far edge crosses one block only,
    ``window / block`` blocks before the diagonal's, again at a fixed
    place; any other tiling masks every block it visits at a computed one."""
    def plain(j, carry):
        return step(j, carry, None)

    if window is not None:
        if block_q == block_k and window % block_q == 0:
            far = qi - window // block_q
            carry = _once_if(
                far >= 0, far,
                lambda j, c: step(j, c, None, (0, window, window)), carry)
            carry = jax.lax.fori_loop(jnp.maximum(far + 1, 0), qi, plain,
                                      carry)
            return step(qi, carry, (0, 0))
        q0 = qi * block_q
        lo = jnp.maximum(q0 - window + 1, 0) // block_k
        hi = (q0 + block_q + block_k - 1) // block_k
        return jax.lax.fori_loop(
            lo, hi, lambda j, c: step(j, c, (j * block_k, q0),
                                      (j * block_k, q0, window)), carry)
    if not causal:
        return jax.lax.fori_loop(0, n_blocks, plain, carry)
    if block_q == block_k:
        return step(qi, jax.lax.fori_loop(0, qi, plain, carry), (0, 0))
    hi = ((qi + 1) * block_q + block_k - 1) // block_k
    return jax.lax.fori_loop(
        0, hi, lambda j, carry: step(j, carry, (j * block_k, qi * block_q)),
        carry)


def _over_query_blocks(step, carry, causal, ki, block_q, k_block, n_blocks,
                       window=None):
    """``step(i, carry, causal_from)`` over the Q blocks that see K/V block
    ``ki``: :func:`_over_key_blocks`' cases from the key's side, from the
    block's own diagonal on (to its window's far edge, with a window)."""
    def plain(i, carry):
        return step(i, carry, None)

    if window is not None and block_q == k_block and window % k_block == 0:
        far = ki + window // k_block
        carry = jax.lax.fori_loop(ki + 1, jnp.minimum(far, n_blocks), plain,
                                  step(ki, carry, (0, 0)))
        return _once_if(
            far < n_blocks, far,
            lambda i, c: step(i, c, None, (0, window, window)), carry)
    if window is not None:
        k0 = ki * k_block
        hi = jnp.minimum((k0 + k_block + window + block_q - 2) // block_q,
                         n_blocks)
        return jax.lax.fori_loop(
            k0 // block_q, hi,
            lambda i, c: step(i, c, (k0, i * block_q),
                              (k0, i * block_q, window)), carry)
    if not causal:
        return jax.lax.fori_loop(0, n_blocks, plain, carry)
    if block_q == k_block:
        # As in _over_key_blocks: Q blocks before this K block see none of
        # it, its own sees it across the diagonal, those after see it all.
        return jax.lax.fori_loop(ki + 1, n_blocks, plain,
                                 step(ki, carry, (0, 0)))
    lo = (ki * k_block) // block_q
    return jax.lax.fori_loop(
        lo, n_blocks,
        lambda i, carry: step(i, carry, (ki * k_block, i * block_q)), carry)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                causal: bool, scale: float, q_block: int, seq_len: int,
                window: Optional[int] = None):
    """One Q block against the K/V blocks up to its diagonal (from its
    window's far edge, with a window); the output accumulates as O^T
    [D, bq] and is transposed once at the end."""
    qi = pl.program_id(1)
    q = q_ref[0]                                      # [bq, D]
    bq, D = q.shape
    fold = _fold_scale(q.dtype, scale)
    if fold:
        q = q * scale

    def step(j, carry, causal_from, window_from=None):
        m, l, ot = carry
        k = k_ref[0, pl.dslice(j * block_k, block_k)]
        v = v_ref[0, pl.dslice(j * block_k, block_k)]
        st = _scores_t(k, q, scale, fold, causal_from, window_from)
        m_new = jnp.maximum(m, st.max(axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)                      # [bk, bq]
        corr = jnp.exp(m - m_new)
        l_new = l * corr + pt.sum(axis=0, keepdims=True)
        return m_new, l_new, ot * corr + _dot(v, pt.astype(v.dtype), _TN)

    # m starts finite, so exp(m - m_new) is 0 (not NaN) on the first block;
    # every query meets a live key in the first block it sees (key 0 under
    # the causal mask), so no running max stays at its start and l > 0.
    # Under a window a query may see nothing of the far edge's block: its
    # masked scores then equal the start of m and count as ones, until the
    # first live key (its own position at the latest) raises m and
    # exp(m - m_new) = 0 wipes them.
    carry = (jnp.full((1, bq), _NEG_INF, jnp.float32),
             jnp.zeros((1, bq), jnp.float32),
             jnp.zeros((D, bq), jnp.float32))
    m, l, ot = _over_key_blocks(step, carry, causal, qi, q_block, block_k,
                                seq_len // block_k, window)
    o_ref[0] = (ot * (1.0 / l)).T.astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)                    # [1, bq]


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dqt_ref, *, block_q: int,
                causal: bool, scale: float, k_block: int, seq_len: int,
                window: Optional[int] = None):
    """One K/V block against the Q blocks that see it
    (``_over_query_blocks``), with P recomputed from the saved LSE (no
    renormalisation pass needed). ``P_i^T`` and ``dS_i^T`` are made once a
    pair and used three times: dV = sum_i P_i^T @ dO_i, dK = scale * sum_i
    dS_i^T @ Q_i, and Q block ``i``'s dQ^T += K^T @ dS_i^T into
    ``dqt_ref`` [T/bq, D, bq], the head's whole dQ^T in float32, which stays
    in VMEM over the head's key blocks (the grid's inner axis, in rising
    order: the order a walk over a Q block's key blocks sums in): zeroed at
    the first, scaled, transposed and written as dQ [T, D] at the last."""
    ki = pl.program_id(1)
    k = k_ref[0]                                      # [bk, D]
    v = v_ref[0]
    bk, D = k.shape
    fold = _fold_scale(k.dtype, scale)
    n_q = seq_len // block_q

    @pl.when(ki == 0)
    def _():
        dqt_ref[...] = jnp.zeros(dqt_ref.shape, jnp.float32)

    def step(i, carry, causal_from, window_from=None):
        dk, dv = carry
        q = q_ref[0, pl.dslice(i * block_q, block_q)]  # [bq, D]
        if fold:
            q = q * scale
        do = do_ref[0, pl.dslice(i * block_q, block_q)]
        st = _scores_t(k, q, scale, fold, causal_from, window_from)
        pt = jnp.exp(st - lse_ref[0, i])              # P^T [bk, bq], exact
        dv = dv + _dot(pt.astype(do.dtype), do)
        dst = pt * (_dot(v, do, _NT) - delta_ref[0, i])   # dS^T
        dst = dst.astype(q.dtype)
        dqt_ref[i] += _dot(k_ref[0], dst, _TN)
        return dk + _dot(dst, q), dv

    dk, dv = _over_query_blocks(
        step, (jnp.zeros((bk, D), jnp.float32),
               jnp.zeros((bk, D), jnp.float32)),
        causal, ki, block_q, k_block, n_q, window)
    # With the scale folded into q, dk already carries it.
    if not fold:
        dk = dk * scale
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(ki == seq_len // k_block - 1)
    def _():
        def write(i, _):
            dq_ref[0, pl.dslice(i * block_q, block_q)] = \
                (dqt_ref[i] * scale).T.astype(dq_ref.dtype)
            return _

        jax.lax.fori_loop(0, n_q, write, None)


def _kernel_name(which: str, causal, scale, heads: int,
                 window: Optional[int] = None, kv_heads: int = 0) -> str:
    """A stable name for each kernel: a device trace and the compiled HLO
    show it, so forward and backward are told apart by name (the backward
    keeps ``dkv``, the name its dK/dV half had: trace readers go by it). A
    window and a smaller number of key/value heads follow the fields every
    call has (``...__h32__w2048__kv4``); a call with neither is named as
    before."""
    name = f"tepdist_flash_{which}__c{int(causal)}__s{scale!r}__h{heads}"
    if window is not None:
        name += f"__w{window}"
    if kv_heads and kv_heads != heads:
        name += f"__kv{kv_heads}"
    return name


def _kv_spec(block, group: int, tiled: bool):
    """The BlockSpec of a key/value operand [B*Hkv, T, D] under a grid over
    the ``B*H`` query heads: query head ``b`` reads key/value head ``b //
    group``, so a group's heads find the block already in VMEM and no
    broadcast copy of k or v exists in HBM."""
    if group == 1:
        return pl.BlockSpec(block, (lambda b, i: (b, i, 0)) if tiled
                            else (lambda b, i: (b, 0, 0)))
    return pl.BlockSpec(block, (lambda b, i: (b // group, i, 0)) if tiled
                        else (lambda b, i: (b // group, 0, 0)))


# The two calls below are traced once a shape and a process (an inlined
# ``jit``: the same equations in the caller's program, the kernel bodies
# traced once): a step's trace meets them in every pass over a layer.
@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "window"))
def _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret,
              window=None):
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * Hkv, T, D)
    vf = v.reshape(B * Hkv, T, D)
    kv_full = _kv_spec((1, T, D), H // Hkv, tiled=False)
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, causal=causal, scale=scale,
        q_block=block_q, seq_len=T, window=window)
    o, lse = pl.pallas_call(
        kernel,
        # The name tags the eqn so the seq-axis planner can motif-match
        # flash call sites in traced graphs (parallel/attention_motif.py)
        # — causal flag, softmax scale and head count ride along for the
        # rewrite (H lets the ulysses lowering un-flatten [B*H, T, D]).
        name=_kernel_name("fwd", causal, scale, H, window, Hkv),
        grid=(B * H, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            kv_full, kv_full,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, i: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            # One float32 per row, the rows of a Q block on the lanes: a
            # [.., T, 1] array is tiled (8, 128) in HBM, 128 times its size.
            jax.ShapeDtypeStruct((B * H, T // block_q, 1, block_q),
                                 jnp.float32),
        ],
        compiler_params=_compiler_params(T, D, q.dtype.itemsize),
        interpret=interpret,
    )(qf, kf, vf)
    return o.reshape(B, H, T, D), lse.reshape(B, H, T)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "window"))
def _bwd_call(causal, scale, block_q, block_k, interpret, res, do,
              dlse=None, window=None):
    q, k, v, o, lse = res
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    BH = B * H
    qf = q.reshape(BH, T, D)
    kf, vf = (x.reshape(B * Hkv, T, D) for x in (k, v))
    dof = do.reshape(BH, T, D)
    rows = (BH, T // block_q, 1, block_q)
    lsef = lse.reshape(rows)
    # delta = rowsum(dO * O): cheap elementwise reduce, XLA fuses it.
    # An LSE cotangent folds in exactly here: dS = P * (dP - delta + dLSE)
    # (d lse / d s = P), so delta -= dlse reuses the unmodified kernels.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    delta = delta.reshape(rows)

    full_spec = pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0))
    kv_block = _kv_spec((1, block_k, D), group, tiled=True)
    row_full = pl.BlockSpec((1,) + rows[1:], lambda b, i: (b, 0, 0, 0))
    itemsize = q.dtype.itemsize
    vmem = _compiler_params(T, D, itemsize, _bwd_holds(T, D, itemsize))

    # One call for the whole backward pass, under the name and with the
    # operands the dK/dV kernel had (a device trace's readers find it by
    # them); dq joins its results, first.
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, block_q=block_q, causal=causal,
                          scale=scale, k_block=block_k, seq_len=T,
                          window=window),
        name=_kernel_name("dkv", causal, scale, H, window, Hkv),
        grid=(BH, T // block_k),
        in_specs=[
            full_spec, kv_block, kv_block,
            full_spec, row_full, row_full,
        ],
        out_specs=[
            full_spec,
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((T // block_q, D, block_q), jnp.float32)],
        compiler_params=vmem,
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)
    shape = (B, H, T, D)
    if group > 1:
        # The kernel wrote each query head's part of its key/value head's
        # gradient; the group's sum is the head broadcast's transpose,
        # without the broadcast.
        dk, dv = (jnp.sum(x.reshape(B, Hkv, group, T, D), axis=2,
                          dtype=jnp.float32).astype(x.dtype)
                  for x in (dk, dv))
        return dq.reshape(shape), dk, dv
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)


# ``layers``, the last static argument of the three calls below: the runs one
# trace of the call stands for (``traced.stood_for()`` where it is called:
# JAX may trace a rule after a walk's body has returned), for the forward
# rules' count of ``flash_bwd_calls``.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret, window,
           layers):
    o, _ = _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret,
                     window)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret, window,
               layers):
    traced.count("flash_bwd_calls", layers=layers)
    o, lse = _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret,
                       window)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, layers,
               res, do):
    return _bwd_call(causal, scale, block_q, block_k, interpret, res, do,
                     window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_from(q, k, v, o, lse, causal, scale, block_q, block_k, interpret,
                window, layers):
    """``_flash`` where the forward kernel's two outputs are already in
    hand: the primal is ``o`` as given (no kernel), the backward is
    ``_flash``'s on the residuals ``_flash_fwd`` would have saved."""
    return o


def _flash_from_fwd(q, k, v, o, lse, causal, scale, block_q, block_k,
                    interpret, window, layers):
    traced.count("flash_bwd_calls", layers=layers)
    return o, (q, k, v, o, lse)


def _flash_from_bwd(*static_res_do):
    return _flash_bwd(*static_res_do) + (None, None)


_flash_from.defvjp(_flash_from_fwd, _flash_from_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_o_lse(q, k, v, causal, scale, block_q, block_k, interpret,
                 layers):
    """(o, lse) flash: the LSE is a first-class differentiable output —
    the per-block form ring attention merges across hops."""
    return _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret)


def _flash_o_lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                     layers):
    traced.count("flash_bwd_calls", layers=layers)
    o, lse = _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret)
    return (o, lse), (q, k, v, o, lse)


def _flash_o_lse_bwd(causal, scale, block_q, block_k, interpret, layers, res,
                     cts):
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
    return _flash_o_lse(q, k, v, causal, scale, block_q, block_k,
                        _interpret(interpret), traced.stood_for())


def _default_block(T: int) -> Optional[int]:
    """Largest divisor of T up to 512. 512x512 tiles beat the conventional
    128x128 on the GPT-2 1.5B training step (T=1024/D=64) by 39% end to end
    in a self-reported sweep of an earlier round (8,495 vs 6,138 tok/s; the
    benchmark read 8,599 at 512 before PR 25 and never ran 128): bigger
    tiles mean fewer grid steps and longer MXU bursts; 1024 tiles regressed
    there. On the chip (PR 25, tools/flash_bench.py) a 512x512 score tile
    takes 0.92 us in the forward, where its two matmuls at D = 64 (half an
    MXU pass each) cannot take under 0.68. 512 caps the float32 S^T tile at
    512*512*4B = 1 MiB of VMEM beside the (1, T, D) K and V blocks (2 x T x
    128 lanes x 2 B, double-buffered: 8 MiB at T = 8192 in bf16).
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


class KeptForward:
    """What a walk over rematerialised blocks
    (``models/layers.py:scan_blocks``) is to the kernel calls of the block
    it traces (``with KeptForward(...)``): the place a call's forward pass
    is handed out to and taken back from, so that the backward pass's
    recomputation of the block does not run it again. A flash call hands
    over its forward kernel's ``(o, lse)``, the block top-k attention
    (``ops/pallas/block_topk_attention.py``) its own ``(o, lse)`` and, in a
    hand-over of its own, the chosen sets: whatever tuple of arrays a call
    gives (:func:`hand_over`).

    Recording (``saved=None``; the walk's forward pass): a call runs its
    forward alone and leaves the tuple in ``kept``. Replaying (``saved``:
    what the recording of the same block kept; the recomputation under
    ``jax.vjp``): the calls take their tuples back in order and are the
    call from a saved forward, no forward kernel and today's backward
    kernel. Only a call traced where the context was entered takes part
    (:func:`hand_over`): the branches of a ``lax.cond``, an inner loop or
    ``jit`` can hand no array out, so a call in one runs as it does outside
    any walk, in both passes, unless its caller does the hand-over around
    the ``cond`` (``models/layers.py:gqa_heads``)."""

    def __init__(self, saved=None):
        self.saved = saved
        self.kept = []

    def __enter__(self):
        self._trace = jax.core.get_opaque_trace_state()
        self._token = _KEPT.set(self)
        return self

    def __exit__(self, *exc):
        _KEPT.reset(self._token)


_KEPT: contextvars.ContextVar[Optional[KeptForward]] = \
    contextvars.ContextVar("tepdist_flash_kept_forward", default=None)


@contextlib.contextmanager
def nothing_kept():
    """Calls traced inside run as outside any walk: a block whose recipe
    pins the rematerialisation of all of it."""
    token = _KEPT.set(None)
    try:
        yield
    finally:
        _KEPT.reset(token)


def hand_over(attend):
    """``attend(forward)`` is one call whose forward pass a walk may keep: a
    flash call, or a ``lax.cond`` over flash calls of one shape, as
    :func:`flash_attention_kept` takes ``forward``; any other kernel family
    under the same three cases. Outside a :class:`KeptForward` (or under
    another trace than the one it was entered in) this is ``attend(None)``;
    recording, ``attend(())`` gives a tuple of arrays whose first entry is
    the call's result, all of it is kept and the result returned; replaying,
    ``attend(saved)`` of the next saved tuple, in the recording's order."""
    keep = _KEPT.get()
    if keep is None or keep._trace != jax.core.get_opaque_trace_state():
        return attend(None)
    if keep.saved is None:
        kept = tuple(attend(()))
        keep.kept.append(kept)
        return kept[0]
    saved = keep.saved[len(keep.kept)]
    keep.kept.append(saved)
    return attend(saved)


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """q [B, H, T, D], k and v [B, Hkv, T, D] -> [B, H, T, D].
    Differentiable (custom VJP).

    ``Hkv`` divides ``H`` (grouped-query attention): query head ``h`` reads
    key/value head ``h // (H / Hkv)`` through the kernels' index maps, so no
    broadcast copy of k or v is made; their gradients are the sums over each
    group. ``window`` (causal only): key j is visible to query i iff ``0 <=
    i - j < window``; both kernels skip the blocks wholly outside it. A
    window that reaches every earlier key (``window >= T``) is no window.
    Equal tiles that divide the window (the benchmark's: 512 in 2048) mask
    its far edge at a fixed place; the computed mask serves every other
    call: a sequence whose tile, the largest divisor of ``T`` up to 512,
    does not divide the window (``T = 6400`` under 2048: tiles of 400), and
    ``models/afmoe.py``'s ``test`` preset (window 8 inside one tile).
    With ``window=None`` and ``Hkv == H`` the kernels, their names and their
    operands are what they were before either existed.

    Inside a block that ``models/layers.py:scan_blocks`` walks, a call
    whose kernels run hands its forward pass to the walk
    (:class:`KeptForward`); the values and the backward kernel are the
    same."""
    def attend(forward):
        return flash_attention_kept(q, k, v, forward, causal, scale, block_q,
                                    block_k, interpret, window)

    if not causal and _resolve_blocks(q.shape[2], block_q, block_k) is None:
        return attend(None)   # the dense fallback: no kernel, nothing to keep
    return hand_over(attend)


def flash_attention_kept(q, k, v, forward, causal: bool = True,
                         scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         interpret: Optional[bool] = None,
                         window: Optional[int] = None):
    """:func:`flash_attention` in the part a :class:`KeptForward` asks of a
    call, for a caller that does the :func:`hand_over` itself. ``forward``:

    - ``None``: the whole of it, ``o`` with its custom VJP, the program of a
      call outside any walk;
    - ``()``: the forward kernel alone, ``(o [B, H, T, D], lse [B, H, T]
      float32)``, not differentiable;
    - ``(o, lse)`` as that gave them for the same ``q, k, v``: attention
      from its saved forward. The primal is ``o`` (no kernel runs); its VJP
      runs the backward kernel on ``(q, k, v, o, lse)`` as
      ``flash_attention``'s does, under the same name, bit for bit."""
    B, H, T, D = q.shape
    if k.shape != v.shape or k.shape[0] != B or k.shape[2:] != (T, D) \
            or H % k.shape[1]:
        raise ValueError(f"flash_attention: q {q.shape}, k {k.shape}, "
                         f"v {v.shape}")
    if window is not None:
        if not causal or window < 1:
            raise ValueError("flash_attention: a window needs causal=True "
                             f"and window >= 1 (got {window})")
        if window >= T:
            window = None
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    blocks = _resolve_blocks(T, block_q, block_k)
    if blocks is None:
        if causal:
            # Pad T up to the next multiple of 128 and slice the result:
            # under the causal mask real queries (pos < T) never attend
            # padded keys (pos >= T), and padded query rows are sliced
            # off (their cotangents are zero), so numerics are exact and
            # memory stays O(T*block) instead of the dense O(T^2). (A saved
            # forward is padded with zeros: a padded row's scores, dO and
            # delta are 0, so its part of every gradient is too.)
            Tp = -(-T // 128) * 128

            def pad(x):
                return jnp.pad(x, ((0, 0), (0, 0), (0, Tp - T))
                               + ((0, 0),) * (x.ndim - 3))

            out = flash_attention_kept(
                pad(q), pad(k), pad(v),
                forward and tuple(pad(x) for x in forward), causal=True,
                scale=scale, interpret=interpret, window=window)
            return jax.tree_util.tree_map(lambda x: x[:, :, :T], out)
        if forward is not None:
            raise ValueError("flash_attention_kept: no kernel runs at "
                             f"non-causal T={T}, so no forward is kept")
        # Non-causal: padded keys would be attended; dense is the only
        # exact fallback (rare — awkward T with bidirectional attention).
        _log_dense_fallback(T)
        group = H // k.shape[1]
        return _dense_attention(q, jnp.repeat(k, group, axis=1),
                                jnp.repeat(v, group, axis=1), causal, scale)
    block_q, block_k = blocks
    static = (causal, scale, block_q, block_k, _interpret(interpret), window)
    if forward == ():
        return _fwd_call(q, k, v, *static)
    static += (traced.stood_for(),)
    if forward is None:
        return _flash(q, k, v, *static)
    return _flash_from(q, k, v, *forward, *static)
