"""Pallas TPU Gated DeltaNet (the gated delta rule with **one decay a value
head and token**, value heads in groups over a key head; Gated Delta
Networks, arXiv:2412.06464; Qwen3-Next's linear layers), chunked, forward and
backward.

For one value head ``h`` with ``K`` key and ``V`` value channels, reading key
head ``h // r`` (``r`` value heads a key head): a sequence ``q_t, k_t [K]``,
``v_t [V]``, a log decay ``g_t <= 0`` (``alpha_t = exp(g_t)``, a scalar) and
a write strength ``beta_t`` in [0, 1]:

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                  S [K, V],  S_0 = 0

(decay the state, read ``r = S^T k_t`` from the decayed state, add ``beta_t
k_t (v_t - r)^T``): ``kda_attention.py``'s rule with every key channel of a
head decayed alike. With ``G_i`` the running sum of ``g`` inside a chunk of
``C`` tokens and ``S`` the state before it:

    D_ij = exp(G_i - G_j) (j <= i, else 0)    A = Diag(beta) ((k k^T) * D)
                                                  strictly lower
    Tm   = (I + A)^-1 Diag(beta)     W = Tm (k * exp(G))     U = Tm v
    v'   = U - W S       o = exp(G) * (q S) + ((q k^T) * D) v'
    S'   = exp(G_C) S + (k * exp(G_C - G))^T v'

**The decay is a mask, not a factor of the operands.** ``q k^T`` and ``k
k^T`` are products of the operands as they come (bf16 on the chip), made
once a key head for its ``r`` value heads; ``D`` multiplies the ``[C, C]``
result. Every exponent is at most 0 wherever the mask keeps it (``G_i - G_j``
for ``j <= i``, ``G``, ``G_C - G``), so nothing overflows at any decay and
there is no reference row, no sub-block and no second part of ``q`` and
``k``, which is what ``kda_attention.py`` pays for a decay a channel.

**The grid is ``(batch, chunks, key heads)``** (``_delta_rule.sweep``), a
step the ``r`` value heads of its key head: ``q, k`` ``[batch, T, Hk * K]``
and ``v, o`` ``[batch, T, Hv * V]`` are a projection's own layout, value head
``h`` lane block ``h`` beside its sibling; ``g``, ``beta`` and their
gradients are ``[chunk, Hv]`` float32 blocks. In the backward ``dq`` and
``dk`` are summed over the key head's value heads before they are written,
and the two gradients of the shared products are summed as ``[C, C]``
matrices first, so their products with ``q`` and ``k`` are made once a key
head too. In the forward the value heads' ``D`` and ``A`` are made first and
their inverses side by side in one ``_delta_rule._inverse`` (a chain of
dependent products, which leaves the matrix unit waiting when it walks
alone), then each head's ``W``, ``U``, ``v'``, ``o`` and state.

**The forward is made once**, as ``kda_attention.py``'s: differentiated, it
writes the state before every chunk (``[batch, chunks, Hv, V, K]`` float32)
and the chunk's ``(I + A)^-1`` (``[batch, chunks, Hv, C, C]`` float32) beside
``o``; ``tepdist_gdn_bwd`` walks the chunks last to first with ``dS``
carried and reads them; inside a walked block the three go to the walk.

Precision (``_linear.py``): ``G``, ``D``, the state and every accumulation
float32; a float32 operand of a matmul goes to the matrix unit as two bf16
parts, four passes a product of two of them and three inside the inverse;
``G`` by doubling over sublane rolls. With float32 operands (the CPU
tests) every matmul is float32. Any ``T``: the last chunk is padded with zero
rows (``g`` = 0, ``beta`` = 0). ``K = V``, a multiple of 128 on the chip.
Kernel names ``tepdist_gdn_fwd`` / ``tepdist_gdn_bwd``. :func:`chunked` is
the chunked form in plain ``jax.numpy`` under a ``lax.scan``;
``tools/gdn_bench.py`` times the kernels alone."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tepdist_tpu.ops.pallas import _interpret
from tepdist_tpu.ops.pallas._delta_rule import (
    _col,
    _column,
    _ij,
    _inverse,
    _prefix,
    _row,
    _through_inverse,
    differentiable,
    sweep,
)
from tepdist_tpu.ops.pallas._linear import (
    _BF16,
    _F32,
    _HIGHEST,
    _NN,
    _NT,
    _TN,
    _carried,
    _dot,
    _padded,
)
from tepdist_tpu.telemetry import traced

CHUNK = 64                  # tokens a grid step

traced.declare(
    "gdn_calls", "forward scalar-decay delta-rule kernel calls a micro batch")


def _products(q, k, narrow):
    """``q k^T`` and ``k k^T`` [C, C], the operands as they come."""
    if not narrow:
        return _dot(q, k, _NT, narrow), _dot(k, k, _NT, narrow)
    return (jax.lax.dot_general(q, k, _NT, preferred_element_type=_F32),
            jax.lax.dot_general(k, k, _NT, preferred_element_type=_F32))


def _system(kk, G):
    """A value head's ``D`` and ``A`` before ``beta`` [C, C] from the key
    head's ``k k^T`` and the head's ``G`` [C, 1]."""
    i, j = _ij(kk.shape[0])
    D = jnp.where(j <= i, jnp.exp(jnp.minimum(G - _row(G), 0.0)), 0.0)
    return D, jnp.where(j < i, kk * D, 0.0)


def _chunk(qk, q, k, v, G, beta, state_t, narrow, D, A, inv):
    """What the forward and the backward both make of a chunk and one value
    head: a dict. ``G`` and ``beta`` the head's columns [C, 1]; ``D, A``
    its :func:`_system`; ``inv``: ``(I + A beta)^-1`` as the forward made
    it."""
    C, K = k.shape
    last = G[C - 1:C]
    gamma, tail = jnp.exp(G), jnp.exp(last - G)
    k32, v32 = k.astype(_F32), v.astype(_F32)
    kg = k32 * gamma
    WU = _dot(inv, jnp.concatenate([kg * beta, v32 * beta], axis=1), _NN,
              narrow)
    W, U = WU[:, :K], WU[:, K:]
    vp = U - _dot(W, state_t, _NT, narrow)
    return dict(D=D, last=last, gamma=gamma, tail=tail, P=qk * D, A=A,
                inv=inv, kg=kg, v32=v32, kd=k32 * tail, W=W, U=U, vp=vp)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, *rest, want, narrow,
                state_dtype):
    """First chunk to last, every value head's state carried; the key
    head's value heads' inverses in one :func:`_inverse`, side by side.
    ``want``: which of the output ``"o"``, the state before the chunk
    ``"states"`` and the chunk's ``(I + A)^-1`` ``"inv"`` are the results,
    in that order."""
    outs, s_scr = dict(zip(want, rest)), rest[-1]
    V = s_scr.shape[1]
    r = v_ref.shape[1] // V
    first = pl.program_id(2) * r

    @pl.when(pl.program_id(1) == 0)
    def _():
        for n in range(r):
            s_scr[first + n] = jnp.zeros(s_scr.shape[1:], _F32)

    q, k = q_ref[...], k_ref[...]
    qk, kk = _products(q, k, narrow)
    G = _prefix(g_ref[...].astype(_F32))
    b = b_ref[...]
    Gs = [_column(G, first + n) for n in range(r)]
    betas = [_column(b, first + n) for n in range(r)]
    systems = [_system(kk, G) for G in Gs]
    invs = _inverse([A * beta for (_, A), beta in zip(systems, betas)],
                    narrow)
    for n in range(r):
        h = first + n
        state_t = s_scr[h]
        c = _chunk(qk, q, k, v_ref[:, n * V:(n + 1) * V], Gs[n], betas[n],
                   state_t, narrow, *systems[n], invs[n])
        if "states" in outs:
            outs["states"][n] = state_t
        if "inv" in outs:
            outs["inv"][n] = c["inv"]
        if "o" in outs:
            out = c["gamma"] * _dot(q, state_t, _NT, narrow) \
                + _dot(c["P"], c["vp"], _NN, narrow)
            outs["o"][:, n * V:(n + 1) * V] = out.astype(outs["o"].dtype)
        s_scr[h] = _carried(state_t * jnp.exp(c["last"])
                            + _dot(c["vp"], c["kd"], _TN, narrow),
                            state_dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, s_ref, inv_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *, narrow,
                state_dtype):
    """Last chunk to first with every value head's ``dS`` carried; the state
    before the chunk and its ``(I + A)^-1`` as the forward's sweep wrote
    them."""
    V = ds_scr.shape[1]
    r = v_ref.shape[1] // V
    hk = pl.program_id(2)
    first = hk * r

    @pl.when(pl.program_id(1) == 0)
    def _():
        for n in range(r):
            ds_scr[first + n] = jnp.zeros(ds_scr.shape[1:], _F32)

    @pl.when(hk == 0)
    def _():
        dg_ref[...] = jnp.zeros(dg_ref.shape, dg_ref.dtype)
        db_ref[...] = jnp.zeros(db_ref.shape, db_ref.dtype)

    q, k = q_ref[...], k_ref[...]
    q32, k32 = q.astype(_F32), k.astype(_F32)
    C = q.shape[0]
    i, j = _ij(C)
    qk, kk = _products(q, k, narrow)
    G_all = _prefix(g_ref[...].astype(_F32))
    b = b_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, dg_ref.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    # The gradients of the shared products, summed over the value heads.
    d_qk = jnp.zeros((C, C), _F32)
    d_kk = jnp.zeros((C, C), _F32)
    dq = jnp.zeros(q32.shape, _F32)
    dk = jnp.zeros(k32.shape, _F32)
    for n in range(r):
        h = first + n
        beta = _column(b, h)
        v, do = v_ref[:, n * V:(n + 1) * V], do_ref[:, n * V:(n + 1) * V]
        state_t, d_next = s_ref[n], ds_scr[h]
        G = _column(G_all, h)
        c = _chunk(qk, q, k, v, G, beta, state_t, narrow, *_system(kk, G),
                   inv_ref[n])
        decay = jnp.exp(c["last"])                          # [1, 1]
        qg = q32 * c["gamma"]

        d_vp = _dot(c["P"], do, _TN, narrow) \
            + _dot(c["kd"], d_next, _NT, narrow)
        dP = jnp.where(j <= i, _dot(do, c["vp"], _NT, narrow), 0.0)
        d_qg = _dot(do, state_t, _NN, narrow)
        d_kd = _dot(c["vp"], d_next, _NN, narrow)
        d_last = decay * jnp.sum(state_t * d_next, keepdims=True)
        dW = -_dot(d_vp, state_t, _NN, narrow)
        dBw, dBu, dA = _through_inverse(c["inv"], dW, d_vp, c["W"], c["U"],
                                        narrow)
        d_beta = jnp.sum(dBw * c["kg"] + dBu * c["v32"], axis=1,
                         keepdims=True) \
            + jnp.sum(dA * c["A"], axis=1, keepdims=True)
        d_kg = dBw * beta
        dA = dA * beta
        # D_ij = exp(G_i - G_j): a row's sum to G_i, a column's from G_j.
        d_diff = (dP * qk + dA * kk) * c["D"]
        d_qk = d_qk + dP * c["D"]
        d_kk = d_kk + dA * c["D"]
        dq = dq + d_qg * c["gamma"]
        dk = dk + d_kg * c["gamma"] + d_kd * c["tail"]
        through_tail = jnp.sum(d_kd * c["kd"], axis=1, keepdims=True)
        dG = jnp.sum(d_diff, axis=1, keepdims=True) \
            - _col(jnp.sum(d_diff, axis=0, keepdims=True)) \
            + jnp.sum(d_kg * c["kg"] + d_qg * qg, axis=1, keepdims=True) \
            - through_tail
        dG = dG + jnp.where(
            row == C - 1, d_last + jnp.sum(through_tail, keepdims=True), 0.0)

        dv_ref[:, n * V:(n + 1) * V] = (dBu * beta).astype(dv_ref.dtype)
        dg_ref[...] = jnp.where(lane == h, dG.astype(dg_ref.dtype),
                                dg_ref[...])
        db_ref[...] = jnp.where(lane == h, d_beta.astype(db_ref.dtype),
                                db_ref[...])
        ds_scr[h] = _carried(
            d_next * decay + _dot(do, qg, _TN, narrow)
            - _dot(d_vp, c["W"], _TN, narrow), state_dtype)

    dq_ref[...] = (dq + _dot(d_qk, k, _NN, narrow)).astype(dq_ref.dtype)
    dk_ref[...] = (dk + _dot(d_qk, q, _TN, narrow)
                   + _dot(d_kk, k, _NN, narrow)
                   + _dot(d_kk, k, _TN, narrow)).astype(dk_ref.dtype)

    @pl.when(hk == pl.num_programs(2) - 1)
    def _():
        dg_ref[...] = _prefix(dg_ref[...], reverse=True)


def _call(kernel, name, operands, outs, *, chunk, reverse, matmuls,
          interpret, state_dtype=None):
    """One sweep over the chunks (``_delta_rule.sweep``), a key head's value
    heads a grid step. ``operands``: ``(kind, array)`` each, the kinds
    ``key`` ``[B, T, Hk * K]``, ``wide`` ``[B, T, Hv * V]``, ``beta`` ``[B,
    T, Hv]``, ``states`` and ``inv``; ``outs``: ``(kind, dtype)`` of each
    result."""
    q, v, beta = operands[0][1], operands[2][1], operands[4][1]
    B, T, Hv = beta.shape
    K = v.shape[2] // Hv
    return sweep(
        functools.partial(kernel, narrow=q.dtype == _BF16,
                          state_dtype=state_dtype),
        name, operands, outs, chunk=chunk, reverse=reverse,
        flops=2 * matmuls * B * Hv * T * K * (chunk + K) // 2,
        transcendentals=B * Hv * T * (chunk + 2),
        interpret=interpret, group=Hv // (q.shape[2] // K))


def _operands(chunk, q, k, v, g, beta, *more):
    """The sweeps' operands by kind, padded to whole chunks."""
    kinds = ("key", "key", "wide", "beta", "beta") + ("wide",) * len(more)
    arrays = (q, k, v, g.astype(_F32), beta.astype(_F32)) + more
    return [(kind, _padded(x, chunk)) for kind, x in zip(kinds, arrays)]


def forward(q, k, v, g, beta, *, chunk: int = CHUNK, interpret=None,
            out_dtype=None, state_dtype=None, states: bool = False):
    """The forward kernel alone; with ``states`` ``(o, states, inv)``: also
    the state before every chunk, ``[B, chunks, Hv, V, K]``, and the chunk's
    ``(I + A)^-1``, ``[B, chunks, Hv, chunk, chunk]``, both float32 (what the
    backward kernel reads), as two more results of the same sweep. A check's
    ``out_dtype`` (the result in float32, not rounded to the operands' dtype)
    and ``state_dtype`` (the carried state through a narrower dtype: the
    check's control)."""
    want = ("o", "states", "inv") if states else ("o",)
    out = _call(functools.partial(_fwd_kernel, want=want),
                "tepdist_gdn_fwd", _operands(chunk, q, k, v, g, beta),
                [("wide", out_dtype or q.dtype), ("states", _F32),
                 ("inv", _F32)][:len(want)],
                chunk=chunk, reverse=False, matmuls=12,
                interpret=_interpret(interpret), state_dtype=state_dtype)
    o = out[0][:, :q.shape[1]]
    return (o, *out[1:]) if states else o


def backward(q, k, v, g, beta, do, *, kept=None, chunk: int = CHUNK,
             interpret=None, out_dtype=None, state_dtype=None):
    """``(dq, dk, dv, dg, dbeta)``; ``dg`` and ``dbeta`` float32 ``[B, T,
    Hv]``. ``kept``: ``(states, inv)`` as :func:`forward` hands them over
    (``states=True``); None runs that sweep first."""
    interpret = _interpret(interpret)
    if kept is None:
        kept = forward(q, k, v, g, beta, chunk=chunk, interpret=interpret,
                       state_dtype=state_dtype, states=True)[1:]
    states, inv = kept
    dtype = out_dtype or q.dtype
    out = _call(_bwd_kernel, "tepdist_gdn_bwd",
                _operands(chunk, q, k, v, g, beta, do)
                + [("states", states), ("inv", inv)],
                [("key", dtype)] * 2 + [("wide", dtype)]
                + [("beta", _F32)] * 2,
                chunk=chunk, reverse=True, matmuls=24, interpret=interpret,
                state_dtype=state_dtype)
    return tuple(x[:, :q.shape[1]] for x in out)


_attention = differentiable(forward, backward, "gdn_calls")


def gdn_attention(q, k, v, g, beta, *, chunk: int = CHUNK,
                  interpret: Optional[bool] = None):
    """The scalar-decay gated delta rule over ``q, k`` [batch, T, Hk * K],
    ``v`` [batch, T, Hv * K] (``V = K``; ``Hk`` divides ``Hv``: value head
    ``h`` reads key head ``h // (Hv / Hk)``), ``g`` [batch, T, Hv] (float32
    log decays, at most 0) and ``beta`` [batch, T, Hv] -> ``o`` [batch, T,
    Hv * K] in ``q``'s dtype. Differentiable in all five. No scale and no
    norm is applied: the caller's ``q`` and ``k`` carry them. The state
    starts at zero for every row of the batch.

    Inside a block that ``models/layers.py:scan_blocks`` walks the call
    hands its forward pass, ``(o, states, inv)``, to the walk. Counts, while
    it is traced, each forward kernel call in ``gdn_calls``
    (``telemetry/traced.py``)."""
    Hv = beta.shape[-1]
    if q.shape != k.shape or q.ndim != 3 or v.ndim != 3 \
            or g.shape != beta.shape or beta.shape != v.shape[:2] + (Hv,) \
            or v.shape[:2] != q.shape[:2] or v.shape[2] % Hv \
            or q.shape[2] % (v.shape[2] // Hv) \
            or Hv % (q.shape[2] // (v.shape[2] // Hv)) or chunk % 8:
        raise ValueError(
            f"gdn_attention: q {q.shape}, k {k.shape}, v {v.shape}, g "
            f"{g.shape}, beta {beta.shape}, chunk {chunk}")
    chunk = min(chunk, -(-q.shape[1] // 8) * 8)
    return _attention(q, k, v, g, beta, chunk, _interpret(interpret))


def chunked(q, k, v, g, beta, *, chunk: int = CHUNK):
    """:func:`gdn_attention` in plain ``jax.numpy``: the chunked form above
    under a ``lax.scan`` over the chunks, float32 at the highest matmul
    precision, the key heads repeated for their value heads and the
    triangular system by ``solve_triangular``. Differentiable by autodiff;
    what the kernels are held to beside the recurrence."""
    B, T, _ = q.shape
    Hv = beta.shape[2]
    K = v.shape[2] // Hv
    r = Hv // (q.shape[2] // K)
    C = min(chunk, T)
    nc = -(-T // C)

    def heads(x, repeat=1):  # [B, T, H * n] -> [chunks, B, H * repeat, C, n]
        x = _padded(x.astype(_F32), C)
        x = x.reshape(B, nc, C, Hv // repeat, -1).transpose(1, 0, 3, 2, 4)
        return jnp.repeat(x, repeat, axis=2)

    i, j = _ij(C)
    eye = jnp.eye(C, dtype=_F32)
    dot = functools.partial(jnp.einsum, precision=_HIGHEST)

    def step(S, xs):         # S [B, H, K, V]
        q, k, v, g, b = xs   # g, b [B, H, C, 1]
        G = jnp.cumsum(g, axis=-2)
        D = jnp.exp(jnp.where(j <= i, G - G.swapaxes(-1, -2), -jnp.inf))
        P = dot("bhik,bhjk->bhij", q, k) * D
        A = jnp.where(j < i, dot("bhik,bhjk->bhij", k, k) * D, 0.0) * b
        gamma = jnp.exp(G)
        WU = jax.scipy.linalg.solve_triangular(
            eye + A, jnp.concatenate([k * gamma * b, v * b], axis=-1),
            lower=True)
        W, U = WU[..., :K], WU[..., K:]
        vp = U - dot("bhck,bhkv->bhcv", W, S)
        o = gamma * dot("bhck,bhkv->bhcv", q, S) \
            + dot("bhij,bhjv->bhiv", P, vp)
        last = G[..., -1:, :]
        S = S * jnp.exp(last) \
            + dot("bhck,bhcv->bhkv", k * jnp.exp(last - G), vp)
        return S, o

    _, o = jax.lax.scan(
        step, jnp.zeros((B, Hv, K, K), _F32),
        (heads(q, r), heads(k, r), heads(v), heads(g), heads(beta)))
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, nc * C, Hv * K)
    return o[:, :T].astype(q.dtype)
