"""Pallas TPU Kimi Delta Attention (the gated delta rule with a decay for
every key channel; Kimi Linear, arXiv:2510.26692), chunked, forward and
backward.

For one head with ``K`` key and ``V`` value channels, a sequence ``q_t, k_t
[K]``, ``v_t [V]``, log decays ``g_t [K] <= 0`` (``alpha_t = exp(g_t)``) and
write strengths ``beta_t`` in [0, 1]:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                  S [K, V],  S_0 = 0

(decay the state's rows, read ``r = S^T k_t`` from the decayed state, add
``beta_t k_t (v_t - r)^T``). The state is read before it is written, so a
chunk of ``C`` tokens is a triangular system. With ``G_i`` the running sum
of ``g`` inside the chunk, ``Gamma = exp(G)`` and ``S`` the state before it:

    A_ij  = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)   (j < i)
    Tm    = (I + A)^-1 Diag(beta)
    W     = Tm (k * Gamma)        U = Tm v            v' = U - W S
    o     = (q * Gamma) S + P v'  P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)
                                                      (j <= i)
    S'    = Diag(Gamma_C) S + (k * Gamma_C / Gamma)^T v'

**No factor overflows.** ``exp(G_i - G_j)`` does not split into ``Gamma_i /
Gamma_j`` over a whole chunk (``1 / Gamma`` passes float32 at a decay of
``exp(-1.4)`` a token over 64). The scores are made a sub-block of ``SUB`` =
16 query rows at a time against a reference row of their own, the
sub-block's first: the rows carry ``exp(G_i - G_ref) <= 1``, the keys
``exp(min(G_ref - G_j, 80))``, which is at most 1 for a key before the
sub-block, at most ``exp(15 |g|)`` inside it (``g`` = -5 a token still fits)
and whatever it is for a key after it, where the mask drops the product.
Everything else carries ``Gamma`` or ``Gamma_C / Gamma``, at most 1.

**Which rule is where.** This file computes the rule with a decay for every
key channel (``g [T, H * K]``, as many key heads as value heads). The rule
with ONE decay a value head (``g [T, H]``; Gated DeltaNet), value heads in
groups over a key head, is ``gdn_attention.py``: there the decay is a ``[C,
C]`` mask on a plain product and none of the sub-blocks below is needed. A
call here with ``g`` spread over a head's channels and ``q, k`` repeated
computes that rule too (a test holds the two equal); it pays for the general
case. What the two share is ``_delta_rule.py``: the running sum, the inverse
by doubling (``A`` is strictly lower, so ``(I + A)^-1 = (I - A)(I + A^2)(I +
A^4) ...`` up to ``A^(C/2)``: two matmuls a factor, which share their right
operand and go to the matrix unit as one, no substitution row by row) and the
way back through it, a head's column of a ``[chunk, H]`` block, the sweep and
the call's three forms for a walk.

**The grid is ``(batch, chunks, heads)``** (``_delta_rule.sweep``), the chunks
sequential and the heads innermost, every head's state ``[V, K]`` (the
transpose, so that a decay of the key channels scales lanes) in one float32
VMEM scratch ``[H, V, K]`` from chunk to chunk: ``beta`` and its gradient are
then ``[chunk, H]`` blocks as the projection leaves them, read and written
once a chunk. **The forward's sweep takes two heads a grid step** (the
backward's one): the two heads' scores are made first, then their two
inverses side by side in one ``_delta_rule._inverse``, whose chain of
dependent products is the forward's longest piece and leaves the matrix unit
waiting when it walks alone, then each head's ``W``, ``U``, ``v'``, ``o`` and
state. An odd head count runs one head a step.
``q, k, v, g`` are ``[batch, T, H * K]``, a projection's own layout, head
``h`` lane block ``h``.

**The forward is made once.** A forward pass that is differentiated writes,
beside ``o``, the state before every chunk (``[batch, chunks, H, V, K]``
float32) and the chunk's inverse ``(I + A)^-1`` (``[batch, chunks, H, C,
C]`` float32; 134e6 bytes each at 8,192 tokens of 32 heads in chunks of 128)
as a second and a third result of its one sweep, and the backward keeps them
with the operands. ``tepdist_kda_bwd`` walks the chunks last to first with
the state's gradient carried, makes the chunk's ``G``, scores, ``W``, ``U``,
``v'`` again from the operands, that state and that inverse (making it
again was 3.6 of the kernel's 11.0 ms a call at the cell's shape), and
writes ``dq, dk, dv, dg, dbeta``. ``d Tm`` needs no inverse of its own: with
``X = [W | U] = (I + A)^-1 B``, ``dB = (I + A)^-T dX`` and ``dA = -dB X^T``.
(:func:`backward`
without the pair makes both again by the forward's sweep under the name
``tepdist_kda_bwd_states``.) Inside a block that
``models/layers.py:scan_blocks`` walks the call hands ``(o, states, inv)`` to
the walk (``flash_attention.hand_over``), so the recomputation of the block
in the backward pass runs no forward kernel: 335.5e6 bytes a layer and micro
batch at that shape, held from the micro batch's forward to the layer's
backward.

Precision (``_linear.py``): the state, ``G``, every decay factor and every
accumulation are float32; a float32 operand goes to the matrix unit as two
bf16 parts, four passes a product of two of them and three inside the
inverse. ``G`` is summed by doubling over sublane rolls, float32 adds.
With float32 operands (the CPU tests) every matmul is float32.

Any ``T``: the last chunk is padded with zero rows (``g`` = 0, ``beta`` = 0:
the state passes through them). Kernel names ``tepdist_kda_fwd`` /
``tepdist_kda_bwd`` (and ``tepdist_kda_bwd_states``) show in a device trace
and in the compiled HLO. Interpret mode off the TPU (tests), compiled on it (``K
= V = 128`` there). :func:`chunked` is the same chunked form in plain
``jax.numpy`` under a ``lax.scan`` (what the kernels were written from and
are held to, with the token-by-token recurrence of
``benchmark/reference/kimi_linear.py``); ``tools/kda_bench.py`` times the
kernels alone.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tepdist_tpu.ops.pallas import _interpret
from tepdist_tpu.ops.pallas._delta_rule import (
    _column,
    _ij,
    _inverse,
    _prefix,
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
SUB = 16                    # query rows that share a reference row
_CAP = 80.0                 # exp(80) fits float32

traced.declare(
    "kda_calls", "forward delta-rule kernel calls a micro batch")


def _factors(G, lo: int):
    """Sub-block ``lo``'s decays against its reference row: the query rows'
    [SUB, K] and every key row's [C, K]."""
    ref = G[lo:lo + 1]
    return jnp.exp(G[lo:lo + SUB] - ref), \
        jnp.exp(jnp.minimum(ref - G, _CAP))


def _scores(q, k, G, narrow):
    """``P`` and ``kk`` [C, C]: ``sum_c x_ic k_jc exp(G_ic - G_jc)`` for ``x``
    the queries and the keys; right where ``j <= i``, finite elsewhere."""
    k32 = k.astype(_F32)
    rows_q, rows_k = [], []
    for lo in range(0, k.shape[0], SUB):
        rows, keys = _factors(G, lo)
        both = jnp.concatenate([q[lo:lo + SUB].astype(_F32) * rows,
                                k32[lo:lo + SUB] * rows], axis=0)
        s = _dot(both, k32 * keys, _NT, narrow)             # [2 SUB, C]
        rows_q.append(s[:SUB])
        rows_k.append(s[SUB:])
    return jnp.concatenate(rows_q, axis=0), jnp.concatenate(rows_k, axis=0)


def _scores_backward(q, k, G, dP, dkk, narrow):
    """The gradients of :func:`_scores`' two results (masked already) in
    ``q``, ``k`` and ``G``. The reference rows are held fixed: a score does
    not depend on its reference."""
    k32 = k.astype(_F32)
    dq, dk_rows = [], []
    dk = jnp.zeros(k32.shape, _F32)
    dG_rows = []
    dG = jnp.zeros(k32.shape, _F32)
    for lo in range(0, k.shape[0], SUB):
        rows, keys = _factors(G, lo)
        qt = q[lo:lo + SUB].astype(_F32) * rows
        kr = k32[lo:lo + SUB] * rows
        kc = k32 * keys
        d_both = _dot(jnp.concatenate([dP[lo:lo + SUB], dkk[lo:lo + SUB]],
                                      axis=0), kc, _NN, narrow)  # [2 SUB, K]
        d_qt, d_kr = d_both[:SUB], d_both[SUB:]
        d_kc = _dot(dP[lo:lo + SUB], qt, _TN, narrow) \
            + _dot(dkk[lo:lo + SUB], kr, _TN, narrow)            # [C, K]
        dq.append(d_qt * rows)
        dk_rows.append(d_kr * rows)
        dk = dk + d_kc * keys
        dG_rows.append(d_qt * qt + d_kr * kr)
        dG = dG - d_kc * kc
    return jnp.concatenate(dq, axis=0), \
        dk + jnp.concatenate(dk_rows, axis=0), \
        dG + jnp.concatenate(dG_rows, axis=0)


def _scored(q, k, g, narrow):
    """A chunk's running sum, its decays and its masked scores: a dict.
    ``kk * beta`` is the chunk's ``A``."""
    C = q.shape[0]
    G = _prefix(g.astype(_F32))
    last = G[C - 1:C]
    P, kk = _scores(q, k, G, narrow)
    i, j = _ij(C)
    return dict(G=G, last=last, gamma=jnp.exp(G), tail=jnp.exp(last - G),
                P=jnp.where(j <= i, P, 0.0), kk=jnp.where(j < i, kk, 0.0))


def _chunk(c, q, k, v, beta, state_t, narrow, inv):
    """What the forward and the backward both make of a chunk: ``c``, its
    :func:`_scored`, and what follows from ``inv``, ``(I + A)^-1`` as the
    forward made it."""
    k32 = k.astype(_F32)
    kg = k32 * c["gamma"]
    W = _dot(inv, kg * beta, _NN, narrow)
    U = _dot(inv, v.astype(_F32) * beta, _NN, narrow)
    vp = U - _dot(W, state_t, _NT, narrow)
    return dict(c, inv=inv, kg=kg, qg=q.astype(_F32) * c["gamma"],
                kd=k32 * c["tail"], W=W, U=U, vp=vp)


def _next_state(c, state_t, narrow, state_dtype):
    return _carried(state_t * jnp.exp(c["last"])
                    + _dot(c["vp"], c["kd"], _TN, narrow), state_dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, *rest, want, narrow,
                state_dtype):
    """First chunk to last, every head's state carried; the grid step's
    heads' inverses in one :func:`_inverse`, side by side. ``want``: which
    of the output ``"o"``, the state before the chunk ``"states"`` and the
    chunk's ``(I + A)^-1`` ``"inv"`` are the results, in that order."""
    outs, s_scr = dict(zip(want, rest)), rest[-1]
    K = s_scr.shape[2]
    r = q_ref.shape[1] // K
    first = pl.program_id(2) * r

    @pl.when(pl.program_id(1) == 0)
    def _():
        for n in range(r):
            s_scr[first + n] = jnp.zeros(s_scr.shape[1:], _F32)

    b = b_ref[...]
    lanes = [slice(n * K, (n + 1) * K) for n in range(r)]
    betas = [_column(b, first + n) for n in range(r)]
    scored = [_scored(q_ref[:, at], k_ref[:, at], g_ref[:, at], narrow)
              for at in lanes]
    invs = _inverse([c["kk"] * beta for c, beta in zip(scored, betas)],
                    narrow)
    for n, at in enumerate(lanes):
        h = first + n
        state_t = s_scr[h]
        c = _chunk(scored[n], q_ref[:, at], k_ref[:, at], v_ref[:, at],
                   betas[n], state_t, narrow, invs[n])
        if "states" in outs:
            outs["states"][n] = state_t
        if "inv" in outs:
            outs["inv"][n] = c["inv"]
        if "o" in outs:
            out = _dot(c["qg"], state_t, _NT, narrow) \
                + _dot(c["P"], c["vp"], _NN, narrow)
            outs["o"][:, at] = out.astype(outs["o"].dtype)
        s_scr[h] = _next_state(c, state_t, narrow, state_dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, s_ref, inv_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *, narrow,
                state_dtype):
    """Last chunk to first with the state's gradient carried; the state
    before the chunk and its ``(I + A)^-1`` as the forward's sweep wrote
    them."""
    h = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[h] = jnp.zeros(ds_scr.shape[1:], _F32)

    @pl.when(h == 0)
    def _():
        db_ref[...] = jnp.zeros(db_ref.shape, db_ref.dtype)

    q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
    beta = _column(b_ref[...], h)
    state_t, d_next = s_ref[...], ds_scr[h]
    c = _chunk(_scored(q, k, g_ref[...], narrow), q, k, v, beta, state_t,
               narrow, inv_ref[...])
    C = q.shape[0]
    i, j = _ij(C)
    decay = jnp.exp(c["last"])                              # Gamma_C [1, K]

    d_vp = _dot(c["P"], do, _TN, narrow) + _dot(c["kd"], d_next, _NT, narrow)
    dP = jnp.where(j <= i, _dot(do, c["vp"], _NT, narrow), 0.0)
    d_qg = _dot(do, state_t, _NN, narrow)
    d_kd = _dot(c["vp"], d_next, _NN, narrow)
    d_last = decay * jnp.sum(state_t * d_next, axis=0, keepdims=True)
    dW = -_dot(d_vp, state_t, _NN, narrow)
    dBw, dBu, dA = _through_inverse(c["inv"], dW, d_vp, c["W"], c["U"],
                                    narrow)
    v32 = v.astype(_F32)
    d_beta = jnp.sum(dBw * c["kg"], axis=1, keepdims=True) \
        + jnp.sum(dBu * v32, axis=1, keepdims=True) \
        + jnp.sum(dA * c["kk"], axis=1, keepdims=True)
    d_kg = dBw * beta
    dq, dk, dG = _scores_backward(q, k, c["G"], dP, dA * beta, narrow)
    dq = dq + d_qg * c["gamma"]
    dk = dk + d_kg * c["gamma"] + d_kd * c["tail"]
    through_tail = d_kd * c["kd"]
    dG = dG + d_kg * c["kg"] + d_qg * c["qg"] - through_tail
    row = jax.lax.broadcasted_iota(jnp.int32, dG.shape, 0)
    dG = dG + jnp.where(
        row == C - 1,
        d_last + jnp.sum(through_tail, axis=0, keepdims=True), 0.0)

    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = (dBu * beta).astype(dv_ref.dtype)
    dg_ref[...] = _prefix(dG, reverse=True).astype(dg_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, db_ref.shape, 1)
    db_ref[...] = jnp.where(lane == h, d_beta.astype(db_ref.dtype),
                            db_ref[...])
    ds_scr[h] = _carried(
        d_next * decay + _dot(do, c["qg"], _TN, narrow)
        - _dot(d_vp, c["W"], _TN, narrow), state_dtype)


def _pairs(H: int):
    """The heads a grid step of the forward's sweep: two, whose inverses
    walk side by side; an odd count's one."""
    return 1 if H % 2 else 2


def _call(kernel, name, operands, outs, *, chunk, reverse, matmuls,
          interpret, state_dtype=None, group=None):
    """One sweep over the chunks (``_delta_rule.sweep``), ``group`` heads a
    grid step (None: one, its axis squeezed out of a block). ``operands``:
    ``(kind, array)`` each, the kinds ``wide`` ``[B, T, H * K]``, ``beta``
    ``[B, T, H]``, ``states`` ``[B, chunks, H, V, K]`` and ``inv`` ``[B,
    chunks, H, chunk, chunk]`` (whole chunks: the caller pads); ``outs``:
    ``(kind, dtype)`` of each result."""
    q, beta = operands[0][1], operands[4][1]
    B, T, HK = q.shape
    K = HK // beta.shape[2]
    return sweep(
        functools.partial(kernel, narrow=q.dtype == _BF16,
                          state_dtype=state_dtype),
        name, operands, outs, chunk=chunk, reverse=reverse,
        flops=2 * matmuls * B * T * HK * (chunk + K) // 2,
        transcendentals=B * T * HK * (3 + chunk // SUB),
        interpret=interpret, group=group)


def _operands(chunk, q, k, v, g, beta, *more):
    """The sweeps' operands by kind, padded to whole chunks."""
    wide = [q, k, v, g.astype(_F32)] + list(more)
    return [("wide", _padded(x, chunk)) for x in wide[:4]] \
        + [("beta", _padded(beta.astype(_F32), chunk))] \
        + [("wide", _padded(x, chunk)) for x in wide[4:]]


def forward(q, k, v, g, beta, *, chunk: int = CHUNK, interpret=None,
            out_dtype=None, state_dtype=None, states: bool = False):
    """The forward kernel alone; with ``states`` ``(o, states, inv)``: also
    the state before every chunk, ``[B, chunks, H, V, K]``, and the chunk's
    ``(I + A)^-1``, ``[B, chunks, H, chunk, chunk]``, both float32 (what the
    backward kernel reads), as two more results of the same sweep. A check's
    ``out_dtype`` (the result in float32, not rounded to the operands' dtype)
    and ``state_dtype`` (the carried state through a narrower dtype: the
    check's control)."""
    want = ("o", "states", "inv") if states else ("o",)
    out = _call(functools.partial(_fwd_kernel, want=want),
                "tepdist_kda_fwd", _operands(chunk, q, k, v, g, beta),
                [("wide", out_dtype or q.dtype), ("states", _F32),
                 ("inv", _F32)][:len(want)],
                chunk=chunk, reverse=False, matmuls=23,
                interpret=_interpret(interpret), state_dtype=state_dtype,
                group=_pairs(beta.shape[2]))
    o = out[0][:, :q.shape[1]]
    return (o, *out[1:]) if states else o


def backward(q, k, v, g, beta, do, *, kept=None, chunk: int = CHUNK,
             interpret=None, out_dtype=None, state_dtype=None):
    """``(dq, dk, dv, dg, dbeta)``; ``dg`` and ``dbeta`` float32. ``kept``:
    ``(states, inv)`` as :func:`forward` hands them over (``states=True``);
    None makes both again from the operands, a sweep of its own
    (``tepdist_kda_bwd_states``)."""
    interpret = _interpret(interpret)
    operands = _operands(chunk, q, k, v, g, beta, do)
    if kept is None:
        kept = _call(functools.partial(_fwd_kernel, want=("states", "inv")),
                     "tepdist_kda_bwd_states", operands[:5],
                     [("states", _F32), ("inv", _F32)], chunk=chunk,
                     reverse=False, matmuls=21, interpret=interpret,
                     state_dtype=state_dtype, group=_pairs(beta.shape[2]))
    states, inv = kept
    dtype = out_dtype or q.dtype
    out = _call(_bwd_kernel, "tepdist_kda_bwd",
                operands + [("states", states), ("inv", inv)],
                [("wide", dtype)] * 3 + [("wide", _F32), ("beta", _F32)],
                chunk=chunk, reverse=True, matmuls=33, interpret=interpret,
                state_dtype=state_dtype)
    return tuple(x[:, :q.shape[1]] for x in out)


_attention = differentiable(forward, backward, "kda_calls")


def kda_attention(q, k, v, g, beta, *, chunk: int = CHUNK,
                  interpret: Optional[bool] = None):
    """The gated delta rule over ``q, k, v`` [batch, T, heads * K] (``V =
    K``), ``g`` [batch, T, heads * K] (float32 log decays, at most 0) and
    ``beta`` [batch, T, heads] -> ``o`` [batch, T, heads * K] in ``q``'s
    dtype. Differentiable in all five. No scale and no norm is applied: the
    caller's ``q`` and ``k`` carry them. The state starts at zero for every
    row of the batch.

    Inside a block that ``models/layers.py:scan_blocks`` walks the call
    hands its forward pass, ``(o, states, inv)``, to the walk
    (``flash_attention.KeptForward``): the values and the backward kernel
    are the same. Counts, while it is traced, each forward kernel call in
    ``kda_calls`` (``telemetry/traced.py``)."""
    if not (q.shape == k.shape == v.shape == g.shape) or q.ndim != 3 \
            or beta.shape[:2] != q.shape[:2] or beta.ndim != 3 \
            or q.shape[2] % beta.shape[2] or chunk % SUB:
        raise ValueError(
            f"kda_attention: q {q.shape}, k {k.shape}, v {v.shape}, g "
            f"{g.shape}, beta {beta.shape}, chunk {chunk}")
    chunk = min(chunk, -(-q.shape[1] // SUB) * SUB)
    return _attention(q, k, v, g, beta, chunk, _interpret(interpret))


def chunked(q, k, v, g, beta, *, chunk: int = CHUNK):
    """:func:`kda_attention` in plain ``jax.numpy``: the chunked form above
    under a ``lax.scan`` over the chunks, float32 at the highest matmul
    precision, the decays of a chunk's pairs as one ``[C, C, K]`` array a
    head (masked before the exponential, so nothing overflows) and the
    triangular system by ``solve_triangular``. Differentiable by autodiff;
    what the kernels are held to beside the recurrence."""
    B, T, HK = q.shape
    H = beta.shape[2]
    C = min(chunk, T)
    nc = -(-T // C)

    def heads(x):            # [B, T, H * n] -> [chunks, B, H, C, n]
        x = _padded(x.astype(_F32), C)
        return x.reshape(B, nc, C, H, -1).transpose(1, 0, 3, 2, 4)

    i, j = _ij(C)
    eye = jnp.eye(C, dtype=_F32)
    dot = functools.partial(jnp.einsum, precision=_HIGHEST)

    def step(S, xs):         # S [B, H, K, V]
        q, k, v, g, b = xs
        G = jnp.cumsum(g, axis=-2)
        diff = G[..., :, None, :] - G[..., None, :, :]       # [.., C, C, K]
        decay = jnp.exp(jnp.where((j <= i)[..., None], diff, -jnp.inf))
        P = dot("bhik,bhjk,bhijk->bhij", q, k, decay)
        A = jnp.where(j < i, dot("bhik,bhjk,bhijk->bhij", k, k, decay),
                      0.0) * b
        gamma = jnp.exp(G)
        WU = jax.scipy.linalg.solve_triangular(
            eye + A, jnp.concatenate([k * gamma * b, v * b], axis=-1),
            lower=True)
        W, U = WU[..., :k.shape[-1]], WU[..., k.shape[-1]:]
        vp = U - dot("bhck,bhkv->bhcv", W, S)
        o = dot("bhck,bhkv->bhcv", q * gamma, S) \
            + dot("bhij,bhjv->bhiv", P, vp)
        last = G[..., -1:, :]
        S = S * jnp.exp(last).swapaxes(-1, -2) \
            + dot("bhck,bhcv->bhkv", k * jnp.exp(last - G), vp)
        return S, o

    K = HK // H
    _, o = jax.lax.scan(     # a chunk's [C, C, K] decays are made again
        jax.checkpoint(step), jnp.zeros((B, H, K, K), _F32),
        tuple(map(heads, (q, k, v, g, beta))))
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, nc * C, HK)
    return o[:, :T].astype(q.dtype)
