"""Attention over key blocks chosen per query token (InfLLM-v2, the sparse
layer of MiniCPM4 / MiniCPM-SALA): the choice, and Pallas TPU kernels that
visit only the chosen blocks, forward and backward.

**The choice** (:func:`select_blocks`, XLA; it carries no gradient). Keys
are cut into blocks of ``block_size``. For a key/value group ``g`` (its
``R`` query heads share one set a token):

1. compressed keys ``kbar_j = mean(k[stride * j : stride * j + kernel_size])``;
2. ``p[h, t, :] = softmax_j(q[h, t] . kbar_j / sqrt(D))`` over the ``j``
   whose last key is at or before ``t`` (all zero where there is none); the
   group's score is the sum of ``p`` over its heads, a block's score the
   largest group score among the compressed positions that overlap it
   (``j`` from ``ratio * b - pad`` on, ``pool`` of them: ``ratio =
   block_size / stride``, ``pad = kernel_size / stride - 1``, ``pool = ratio
   + pad``; 4, 1 and 5 at 64 / 32 / 16);
3. the first ``init_blocks`` blocks and the blocks that hold any of the
   ``window_size`` keys behind ``t`` (its own among them) score ``+inf``,
   blocks after ``t``'s own ``-inf``; the ``topk`` highest are ``t``'s set,
   ties to the lower block, fewer causal blocks than ``topk`` all of them.

The set comes **sorted ascending** ``[batch, groups, T, topk]`` int32, so
``t``'s own block is the last valid entry; entries past the valid ones
repeat it. A query then sees of its ``topk * block_size`` gathered key slots
exactly the first ``visible_keys(t)``: one scalar a token masks a set.

**The attention** (:func:`topk_attention`): ``o[h, t] = softmax over the keys
s <= t in t's set (q[h, t] . k[g, s] / sqrt(D)) v[g, s]``. Grid ``(batch,
groups, blocks of 128 query tokens)``, sequential. A group's keys and
values, side by side ``[T, 2 D]``, are copied into VMEM once a (batch,
group) and stay for the sweep (16 MiB at 32,768 x 256 bf16; the chip has
128), so "gathering" a chosen block is a vector load at a dynamic, aligned
row offset: no block crosses HBM twice, and only chosen blocks are ever read
out of VMEM. A query token brings its group's ``R`` rows (16) to the matrix
unit and walks its set
``KEYS_A_TRIP`` key slots at a time with an online softmax, stopping at its
last visible slot. A trip's matmuls are independent of each other and the
running maximum chains trip to trip, so few long trips beat many short
ones: on the chip a query and group took 9.6 us forward at 128 slots a trip,
2.5 at 512, 0.84 at 4,096, the whole set in one trip, which is the default.
The backward (one kernel) recomputes the probabilities from the saved
log-sum-exp, makes ``dq`` a token and adds each visited block's
``dk | dv`` into a float32 ``[T, 2 D]`` accumulator in VMEM (32 MiB here),
written out once a sweep. No ``[T, T]`` array exists anywhere.

The VMEM-resident form bounds the sequence: keys, values and their float32
gradients of one group must fit (``T * D * 12`` bytes under about 100 MiB:
65,536 tokens at D = 128 forward, 32,768 with the backward). Longer
sequences are refused, not run some other way.

Kernel names ``tepdist_topk_attn_fwd`` / ``tepdist_topk_attn_bwd``. Gauges,
set while a step is traced (``telemetry/traced.py``): ``topk_attn_calls``
and ``topk_attn_keys_per_query``, declared below.

**Inside a walked block** (``ops/pallas/flash_attention.py:KeptForward``)
the walk keeps what the layer's forward pass made and the backward pass's
recomputation of the block takes it back: :func:`topk_attention` hands over
the forward kernel's ``(o, lse)`` and is then the attention from a saved
forward (the primal is ``o``, no kernel; the backward kernel on the same
operands, bit for bit), and :func:`kept_choice` hands over the sets, which
carry no gradient. Neither the forward kernel nor the choice runs twice.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tepdist_tpu.ops.pallas import _interpret
from tepdist_tpu.ops.pallas.flash_attention import hand_over
from tepdist_tpu.telemetry import traced

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_NEG = -1e30
QUERY_BLOCK = 128           # query tokens a grid step
KEYS_A_TRIP = 4096          # gathered key slots a trip of a query's loop
SCORE_CHUNK = 1024          # query tokens a step of the choice
_VMEM_MOST = 110 * 1024 * 1024      # of 128 MiB


class BlockGeometry(NamedTuple):
    """MiniCPM4's ``sparse_config``."""
    block_size: int = 64
    kernel_size: int = 32
    kernel_stride: int = 16
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    @property
    def ratio(self) -> int:
        return self.block_size // self.kernel_stride

    @property
    def pad(self) -> int:
        return self.kernel_size // self.kernel_stride - 1


def _visible(t, block_size: int, topk: int, xp=jnp):
    blocks = xp.minimum(topk, t // block_size + 1)
    return (blocks - 1) * block_size + t % block_size + 1


def visible_keys(t, geo: BlockGeometry):
    """Key slots of ``t``'s sorted set that ``t`` sees (``t`` an array or a
    traced scalar): all of every valid block (``min(topk, t // block_size +
    1)`` of them) but the last, its own, which it sees up to itself."""
    return _visible(t, geo.block_size, geo.topk)


def mean_keys_per_query(T: int, geo: BlockGeometry) -> float:
    return float(np.mean(_visible(np.arange(T), geo.block_size, geo.topk,
                                  np)))


traced.declare(
    "topk_attn_calls", "forward block top-k attention kernel calls a micro "
    "batch (a block rematerialised under jax.checkpoint runs the kernel "
    "again and counts twice; a walked block hands its forward to the walk "
    "and counts once)")
traced.declare(
    "topk_attn_keys_per_query", "mean keys a query of the block top-k "
    "attention visits (a function of T and the geometry)")


# -- the choice -------------------------------------------------------------

def compressed_keys(k, geo: BlockGeometry):
    """k [B, T, G, D] -> float32 [B, n, G, D], ``n = (T - kernel_size) //
    stride + 1`` means over ``kernel_size`` keys, ``stride`` apart."""
    T = k.shape[1]
    ks, st = geo.kernel_size, geo.kernel_stride
    n = (T - ks) // st + 1
    # kernel_size is a whole number of strides: sum the strides' sums.
    per = k.astype(_F32)[:, :(n - 1) * st + ks].reshape(
        k.shape[0], -1, st, *k.shape[2:]).sum(2)
    return sum(per[:, i:i + n] for i in range(ks // st)) / ks


def block_scores(q, kbar, start, geo: BlockGeometry, n_blocks: int):
    """Scores of every block for queries ``start ..`` : q [B, Tc, G, R, D],
    kbar [B, n, G, D] -> [B, G, Tc, n_blocks] float32 (forced ``+inf``,
    after the query's own block ``-inf``)."""
    B, Tc, G, R, D = q.shape
    n = kbar.shape[1]
    t = start + jnp.arange(Tc)
    s = jnp.einsum("btgrd,bngd->bgrtn", q.astype(_F32), kbar,
                   precision=_HIGHEST) / math.sqrt(D)
    seen = (jnp.arange(n) * geo.kernel_stride + geo.kernel_size - 1)[None, :] \
        <= t[:, None]                                          # [Tc, n]
    s = jnp.where(seen, s, _NEG)
    e = jnp.where(seen, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    p = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
    group = p.sum(2)                                           # [B, G, Tc, n]
    # Max-pool over the compressed positions that overlap a block.
    pool = geo.ratio + geo.pad
    width = (n_blocks - 1) * geo.ratio + pool
    padded = jnp.pad(group, ((0, 0), (0, 0), (0, 0),
                             (geo.pad, max(width - geo.pad - n, 0))))
    score = functools.reduce(jnp.maximum, (
        padded[..., i:i + (n_blocks - 1) * geo.ratio + 1:geo.ratio]
        for i in range(pool)))
    b = jnp.arange(n_blocks)[None, :]
    own = (t // geo.block_size)[:, None]
    first = jnp.floor_divide(t - geo.window_size + 1, geo.block_size)[:, None]
    forced = (b < geo.init_blocks) | (b >= first)
    return jnp.where(b > own, -jnp.inf, jnp.where(forced, jnp.inf, score))


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' (``-inf``
    lowest, ``+inf`` highest)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def highest(score, K: int):
    """bool like ``score``: its ``K`` highest entries along the last axis,
    ties to the lower index: ``lax.top_k``'s set without its sort (a sort
    of 512 scores a query took 127 ms a call at 32,768 positions). The
    ``K``-th highest value is found bit by bit, 32 counts of the entries at
    or over a candidate."""
    key = _ordered_bits(score)

    def bit(i, kth):
        candidate = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= candidate, axis=-1, keepdims=True) >= K
        return jnp.where(enough, candidate, kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(score.shape[:-1] + (1,), jnp.uint32))
    over, level = key > kth, key == kth
    room = K - jnp.sum(over, axis=-1, keepdims=True)
    return over | (level & (jnp.cumsum(level, axis=-1) <= room))


def sorted_set(score, start, geo: BlockGeometry):
    """[.., Tc, n_blocks] scores -> the ``topk`` highest blocks a query,
    ascending, entries past the valid ones the query's own block."""
    Tc, n_blocks = score.shape[-2:]
    K = min(geo.topk, n_blocks)
    own = ((start + jnp.arange(Tc)) // geo.block_size)[:, None]
    chosen = highest(score, K) & (jnp.arange(n_blocks)[None, :] <= own)
    # Entry j is the block at which the count of chosen blocks reaches
    # j + 1: as many blocks as have a count of at most j come before it.
    count = jnp.cumsum(chosen, axis=-1)
    idx = jnp.sum(count[..., None, :] <= jnp.arange(K)[:, None], axis=-1)
    return jnp.where(idx < n_blocks, idx, own).astype(jnp.int32)


def select_blocks(q, k, geo: BlockGeometry):
    """q [B, T, H, D], k [B, T, G, D] -> int32 [B, G, T, min(topk, blocks)],
    each query's set sorted ascending (module docstring). No gradient."""
    B, T, H, D = q.shape
    G = k.shape[2]
    if T % geo.block_size or geo.block_size % geo.kernel_stride \
            or geo.kernel_size % geo.kernel_stride or T < geo.kernel_size:
        raise ValueError(f"select_blocks: T {T}, {geo}")
    q, k = jax.lax.stop_gradient((q, k))
    kbar = compressed_keys(k, geo)
    n_blocks = T // geo.block_size
    Tc = SCORE_CHUNK if T % SCORE_CHUNK == 0 else T
    q = q.reshape(B, T // Tc, Tc, G, H // G, D)

    def chunk(args):
        start, qc = args
        return sorted_set(block_scores(qc, kbar, start, geo, n_blocks),
                          start, geo)

    idx = jax.lax.map(chunk, (jnp.arange(0, T, Tc), jnp.moveaxis(q, 1, 0)))
    return jnp.moveaxis(idx, 0, 2).reshape(B, G, T, -1)   # [n,B,G,Tc,K] ->


def kept_choice(q, k, geo: BlockGeometry):
    """:func:`select_blocks`, handed to the walk of the block it is traced
    in (``flash_attention.hand_over``): the recomputation takes the sets
    back and does not choose again. Outside any walk the choice itself."""
    def choose(saved):
        if saved:
            return saved[0]
        idx = select_blocks(q, k, geo)
        return idx if saved is None else (idx,)
    return hand_over(choose)


# -- the kernels ------------------------------------------------------------

def _dot(a, b, dims):
    if a.dtype == _F32 and b.dtype == _F32:
        return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                                   preferred_element_type=_F32)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _load_kv(kv_hbm, kv_scr, sem):
    """A (batch, group)'s keys and values into VMEM, at its sweep's start."""
    b, g = pl.program_id(0), pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _():
        copy = pltpu.make_async_copy(kv_hbm.at[b, g], kv_scr, sem)
        copy.start()
        copy.wait()


def _gathered(idx_ref, kv_scr, at, bs: int, bpi: int):
    """``bpi`` chosen blocks from slot ``at`` on, stacked [bpi * bs, 2 D]."""
    blocks = [kv_scr[pl.ds(pl.multiple_of(idx_ref[at + u] * bs, bs), bs), :]
              for u in range(bpi)]
    return blocks[0] if bpi == 1 else jnp.concatenate(blocks, axis=0)


def _seen(t, p, R: int, kb: int, bs: int, topk: int):
    """The mask of trip ``p``'s ``kb`` slots for token ``t``."""
    slot = jax.lax.broadcasted_iota(jnp.int32, (R, kb), 1) + p * kb
    return slot < _visible(t, bs, topk)


def _trips(t, kb: int, bs: int, topk: int):
    return (_visible(t, bs, topk) + kb - 1) // kb


def _with_column(tile, i, column):
    """``tile`` [R, n] with column ``i`` replaced by ``column`` [R, 1]."""
    at = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.where(at == i, column, tile)


def _column(tile, i):
    at = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.sum(jnp.where(at == i, tile, 0.0), axis=1, keepdims=True)


def _fwd_kernel(idx_ref, q_ref, kv_hbm, o_ref, lse_ref, kv_scr, sem, *,
                bs, K, bpi, scale):
    tq, R, D = q_ref.shape
    kb = bs * bpi
    _load_kv(kv_hbm, kv_scr, sem)
    first = pl.program_id(2) * tq

    def token(i, lse):
        t = first + i
        q = q_ref[i]

        def trip(p, carry):
            m, l, acc = carry
            kv = _gathered(idx_ref, kv_scr, i * K + p * bpi, bs, bpi)
            s = _dot(q, kv[:, :D], _NT) * scale
            s = jnp.where(_seen(t, p, R, kb, bs, K), s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            e = jnp.exp(s - m_new)
            return (m_new, alpha * l + e.sum(axis=1, keepdims=True),
                    alpha * acc + _dot(e.astype(kv.dtype), kv[:, D:], _NN))

        m, l, acc = jax.lax.fori_loop(
            0, _trips(t, kb, bs, K), trip,
            (jnp.full((R, 1), _NEG, _F32), jnp.zeros((R, 1), _F32),
             jnp.zeros((R, D), _F32)))
        o_ref[i] = (acc / l).astype(o_ref.dtype)
        return _with_column(lse, i, m + jnp.log(l))

    lse_ref[...] = jax.lax.fori_loop(0, tq, token,
                                     jnp.zeros(lse_ref.shape, _F32))


def _bwd_kernel(idx_ref, q_ref, do_ref, o_ref, lse_ref, kv_hbm, dq_ref,
                dkv_hbm, kv_scr, acc_scr, sem, *, bs, K, bpi, scale):
    tq, R, D = q_ref.shape
    kb = bs * bpi
    _load_kv(kv_hbm, kv_scr, sem)

    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_scr[...] = jnp.zeros(acc_scr.shape, _F32)

    first = pl.program_id(2) * tq
    lse_tile = lse_ref[...]

    def token(i, carry):
        t = first + i
        q, do = q_ref[i], do_ref[i]
        delta = jnp.sum(do.astype(_F32) * o_ref[i].astype(_F32), axis=1,
                        keepdims=True)
        lse = _column(lse_tile, i)

        def trip(p, dq):
            at = i * K + p * bpi
            kv = _gathered(idx_ref, kv_scr, at, bs, bpi)
            kk, vv = kv[:, :D], kv[:, D:]
            s = _dot(q, kk, _NT) * scale
            e = jnp.where(_seen(t, p, R, kb, bs, K), jnp.exp(s - lse), 0.0)
            ds = e * (_dot(do, vv, _NT) - delta) * scale
            e, ds = e.astype(kv.dtype), ds.astype(kv.dtype)
            d_kv = jnp.concatenate([_dot(ds, q, _TN), _dot(e, do, _TN)],
                                   axis=1)                    # [kb, 2 D]
            for u in range(bpi):
                rows = pl.ds(pl.multiple_of(idx_ref[at + u] * bs, bs), bs)
                acc_scr[rows, :] += d_kv[u * bs:(u + 1) * bs]
            return dq + _dot(ds, kk, _NN)

        dq = jax.lax.fori_loop(0, _trips(t, kb, bs, K), trip,
                               jnp.zeros((R, D), _F32))
        dq_ref[i] = dq.astype(dq_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tq, token, None)

    b, g = pl.program_id(0), pl.program_id(1)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        copy = pltpu.make_async_copy(acc_scr, dkv_hbm.at[b, g], sem)
        copy.start()
        copy.wait()


class _Plan(NamedTuple):
    """A call's shapes, tiling, block specs and operands as the kernels read
    them."""
    grid: tuple
    static: dict            # the kernels' keyword arguments
    work: int               # (query row, key slot) pairs
    qr: jax.Array           # q [B, T, G, R, D]
    kv: jax.Array           # k | v [B, G, T, 2 D]
    flat: jax.Array         # the sets, flat
    rows: pl.BlockSpec      # tq tokens of one group: q, o and their like
    sets: pl.BlockSpec      # those tokens' sets, in SMEM
    lse: pl.BlockSpec       # their log-sum-exp [R, tq]


_ANYWHERE = pl.BlockSpec(memory_space=pl.ANY)


def _plan(q, k, v, idx, bs: int) -> _Plan:
    B, T, H, D = q.shape
    G, K = k.shape[2], idx.shape[-1]
    R = H // G
    tq = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    nt = T // tq
    bpi = max(1, min(K, KEYS_A_TRIP // bs))
    while K % bpi:
        bpi -= 1
    return _Plan(
        grid=(B, G, nt),
        static=dict(bs=bs, K=K, bpi=bpi, scale=1.0 / math.sqrt(D)),
        work=B * T * H * K * bs,
        qr=q.reshape(B, T, G, R, D),
        kv=jnp.moveaxis(jnp.concatenate([k, v], axis=-1), 2, 1),
        flat=idx.reshape(-1),
        rows=pl.BlockSpec((None, tq, None, R, D),
                          lambda b, g, i: (b, i, g, 0, 0)),
        sets=pl.BlockSpec((tq * K,), lambda b, g, i: ((b * G + g) * nt + i,),
                          memory_space=pltpu.SMEM),
        lse=pl.BlockSpec((None, None, R, tq), lambda b, g, i: (b, g, 0, i)))


def _params(resident_bytes: int, T: int):
    need = resident_bytes + 16 * 1024 * 1024
    if need > _VMEM_MOST:
        raise ValueError(
            f"topk_attention: {T} positions want {need} bytes of VMEM for a "
            f"group's keys, values and their gradients; {_VMEM_MOST} at most")
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=max(need, 32 * 1024 * 1024))


def forward(q, k, v, idx, *, block_size: int, interpret):
    """``(o [B, T, H, D], lse [B, G, R, T])`` by the forward kernel."""
    p = _plan(q, k, v, idx, block_size)
    B, G, _ = p.grid
    T, D = q.shape[1], q.shape[3]
    size = q.dtype.itemsize
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, **p.static),
        name="tepdist_topk_attn_fwd",
        grid=p.grid,
        in_specs=[p.sets, p.rows, _ANYWHERE],
        out_specs=[p.rows, p.lse],
        out_shape=[jax.ShapeDtypeStruct(p.qr.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, G, p.qr.shape[3], T), _F32)],
        scratch_shapes=[pltpu.VMEM((T, 2 * D), p.kv.dtype),
                        pltpu.SemaphoreType.DMA(())],
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * D * p.work, transcendentals=p.work,
            bytes_accessed=size * (2 * p.qr.size + p.kv.size)
            + 4 * p.flat.size),
        compiler_params=_params(T * 2 * D * size, T),
        interpret=interpret,
    )(p.flat, p.qr, p.kv)
    return o.reshape(q.shape), lse


def backward(q, k, v, idx, o, lse, do, *, block_size: int, interpret):
    """``(dq, dk, dv)`` by the backward kernel, in the operands' dtypes."""
    p = _plan(q, k, v, idx, block_size)
    T, D = q.shape[1], q.shape[3]
    size = q.dtype.itemsize
    dq, dkv = pl.pallas_call(
        functools.partial(_bwd_kernel, **p.static),
        name="tepdist_topk_attn_bwd",
        grid=p.grid,
        in_specs=[p.sets, p.rows, p.rows, p.rows, p.lse, _ANYWHERE],
        out_specs=[p.rows, _ANYWHERE],
        out_shape=[jax.ShapeDtypeStruct(p.qr.shape, q.dtype),
                   jax.ShapeDtypeStruct(p.kv.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((T, 2 * D), p.kv.dtype),
                        pltpu.VMEM((T, 2 * D), _F32),
                        pltpu.SemaphoreType.DMA(())],
        cost_estimate=pl.CostEstimate(
            flops=2 * 5 * D * p.work, transcendentals=p.work,
            bytes_accessed=size * (4 * p.qr.size + p.kv.size)
            + 4 * (p.kv.size + p.flat.size)),
        compiler_params=_params(T * 2 * D * (size + 4), T),
        interpret=interpret,
    )(p.flat, p.qr, do.reshape(p.qr.shape), o.reshape(p.qr.shape), lse, p.kv)
    dkv = jnp.moveaxis(dkv, 1, 2)                              # [B,T,G,2D]
    return (dq.reshape(q.shape), dkv[..., :D].astype(k.dtype),
            dkv[..., D:].astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attend(q, k, v, idx, block_size, interpret):
    return forward(q, k, v, idx, block_size=block_size,
                   interpret=interpret)[0]


def _attend_fwd(q, k, v, idx, block_size, interpret):
    o, lse = forward(q, k, v, idx, block_size=block_size,
                     interpret=interpret)
    return o, (q, k, v, idx, o, lse)


def _attend_bwd(block_size, interpret, res, do):
    q, k, v, idx, o, lse = res
    return backward(q, k, v, idx, o, lse, do, block_size=block_size,
                    interpret=interpret) \
        + (np.zeros(idx.shape, jax.dtypes.float0),)


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _attend_from(q, k, v, idx, o, lse, block_size, interpret):
    """``_attend`` where the forward kernel's two outputs are already in
    hand: the primal is ``o`` as given (no kernel), the backward is
    ``_attend``'s on the residuals ``_attend_fwd`` would have saved."""
    return o


def _attend_from_fwd(q, k, v, idx, o, lse, block_size, interpret):
    return o, (q, k, v, idx, o, lse)


def _attend_from_bwd(block_size, interpret, res, do):
    return _attend_bwd(block_size, interpret, res, do) + (None, None)


_attend_from.defvjp(_attend_from_fwd, _attend_from_bwd)


def topk_attention(q, k, v, idx, geo: BlockGeometry, *,
                   interpret: Optional[bool] = None):
    """q [B, T, H, D], k, v [B, T, G, D] (query head ``h`` reads group ``h
    // (H / G)``), ``idx`` [B, G, T, K] from :func:`select_blocks` (sorted,
    module docstring) -> [B, T, H, D] in ``q``'s dtype. Differentiable in
    ``q, k, v``. Inside a walked block the call hands its forward pass to
    the walk (module docstring); the values and the backward kernel are the
    same."""
    B, T, H, D = q.shape
    if k.shape != v.shape or k.shape[:2] != (B, T) or k.shape[3] != D \
            or H % k.shape[2] or idx.shape[:3] != (B, k.shape[2], T) \
            or T % geo.block_size or idx.shape[3] > geo.topk:
        raise ValueError(f"topk_attention: q {q.shape}, k {k.shape}, "
                         f"v {v.shape}, idx {idx.shape}, {geo}")
    traced.note("topk_attn_keys_per_query",
                mean_keys_per_query(T, geo._replace(topk=idx.shape[3])))
    bs, interpret = geo.block_size, _interpret(interpret)

    def attend(saved):
        if saved:
            # Other arrays to the compiler than the forward pass's: what it
            # made of those there (``o`` laid out for the output projection,
            # as large again) is made anew here and not held all the while.
            o, lse = jax.lax.optimization_barrier(saved)
            return _attend_from(q, k, v, idx, o, lse, bs, interpret)
        traced.count("topk_attn_calls")
        if saved is None:
            return _attend(q, k, v, idx, bs, interpret)
        return forward(q, k, v, idx, block_size=bs, interpret=interpret)
    return hand_over(attend)
