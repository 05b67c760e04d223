"""Pallas TPU state-space duality (Mamba-2's rule, arXiv:2405.21060: a linear
recurrence with **one decay a head and token**, a value scaled by the step
``Delta``, a skip ``D`` and ``B``/``C`` shared by a group of heads;
Nemotron-H's ``M`` layers), chunked, forward and backward.

For one head ``h`` of ``P`` channels over ``N`` states, reading group ``h //
r``'s ``B_t, C_t [N]`` (``r`` heads a group): a sequence ``u_t [P]``, a step
``Delta_t > 0`` and ``A < 0``:

    S_t = exp(Delta_t A) S_{t-1} + Delta_t u_t B_t^T         S [P, N], S_0 = 0
    y_t = S_t C_t + D u_t

With ``Gc_i`` the running sum of ``Delta A`` inside a chunk of ``C`` tokens
and ``S`` the state before it:

    y  = ((C B^T) * M) (Delta u) + exp(Gc) * (C S^T) + D u
                                  M_ij = exp(Gc_i - Gc_j) for j <= i, else 0
    S' = exp(Gc_end) S + sum_j exp(Gc_end - Gc_j) (Delta_j u_j) B_j^T

**The decay is a mask, not a factor of the operands** (as
``gdn_attention.py``'s): ``C B^T`` is a product of the operands as they come,
made once a group for its ``r`` heads, and ``M`` and ``Delta_j`` multiply the
``[C, C]`` result, so ``u`` reaches the matrix unit as it comes too. Every
exponent is at most 0 wherever the mask keeps it: nothing overflows at any
decay.

**Heads of 64 lanes lie two a lane block.** ``u, y`` are ``[batch, T, H *
P]``, a projection's own layout, and the kernels never cut a block of 128
lanes: a block's heads share every product with the state (``C S^T`` is one
product for the pair, the state ``[2 P, N]`` a block in a float32 VMEM
scratch, its rows decayed a head), and the product with the mask, which is a
head's own, takes ``u`` with the other head's lanes zeroed and is added. ``P``
a multiple of 128 is a head a block and no mask of lanes.

**The grid is ``(batch, chunks, groups)``** (``_delta_rule.sweep``), a step
the ``r`` heads of its group: ``B, C`` ``[batch, T, G * N]``; ``Delta`` and
``Delta A`` and their gradients ``[chunk, H]`` float32 blocks. A forward that
is differentiated writes the state before every chunk (``[batch, chunks, H P
/ lanes, lanes, N]`` float32) beside ``y``; ``tepdist_ssd_bwd`` walks the
chunks last to first with ``dS`` carried and reads them. The gradients of
``C B^T`` are summed over the group's heads as ``[C, C]`` matrices first, so
``dB`` and ``dC`` take their products with it once a group; ``dD`` leaves the
kernel a number a lane and chunk.

Precision (``_linear.py``): ``Gc``, ``M``, the state and every accumulation
float32; a float32 operand of a matmul goes to the matrix unit as two bf16
parts. With float32 operands (the CPU tests) every matmul is float32. Any
``T``: the last chunk is padded with zero rows (``Delta`` = 0: the state
stands and nothing is added). Kernel names ``tepdist_ssd_fwd`` /
``tepdist_ssd_bwd``. :func:`chunked` is the chunked form in plain
``jax.numpy`` under a ``lax.scan``; ``tools/ssd_bench.py`` times the kernels
alone."""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tepdist_tpu.ops.pallas import _interpret
from tepdist_tpu.ops.pallas._delta_rule import (
    _col,
    _column,
    _ij,
    _prefix,
    _row,
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

CHUNK = 128                 # tokens a grid step
LANES = 128

traced.declare(
    "ssd_calls", "forward state-space kernel calls a micro batch (a "
    "rematerialised layer's second run counted)")


def heads_a_block(P: int, r: int) -> int:
    """How many heads of ``P`` channels share a lane block: as many as 128
    lanes hold and a group of ``r`` heads divides into."""
    return math.gcd(r, max(1, LANES // P))


def _picked(mask, new, old):
    return new if mask is None else jnp.where(mask, new, old)


def _heads(Gc_all, dl_all, first, hb, P, C):
    """What a lane block's heads ``first ..`` give its products: a dict a
    head (``dl`` its column [C, 1], ``last = Gc_end`` [1, 1], ``M`` [C, C],
    ``mine`` its lanes of the block and ``rows`` its rows of the state, None
    for a head a block) and, a lane the head's, ``gamma
    = exp(Gc)``, ``tail = exp(Gc_end - Gc)`` and ``dl`` ``[C, hb P]``, with
    ``decay = exp(Gc_end)`` ``[hb P, 1]`` a row of the state."""
    i, j = _ij(C)
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, hb * P), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (hb * P, 1), 0)
    heads = []
    gamma = tail = dl_lanes = jnp.zeros((C, hb * P), _F32)
    decay = jnp.zeros((hb * P, 1), _F32)
    for e in range(hb):
        Gc, dl = _column(Gc_all, first + e), _column(dl_all, first + e)
        last = Gc[C - 1:C]
        mine = None if hb == 1 else (lane >= e * P) & (lane < (e + 1) * P)
        rows = None if hb == 1 else (row >= e * P) & (row < (e + 1) * P)
        M = jnp.where(j <= i, jnp.exp(jnp.minimum(Gc - _row(Gc), 0.0)), 0.0)
        heads.append(dict(dl=dl, last=last, M=M, mine=mine, rows=rows))
        gamma = _picked(mine, jnp.exp(Gc), gamma)
        tail = _picked(mine, jnp.exp(last - Gc), tail)
        dl_lanes = _picked(mine, dl, dl_lanes)
        decay = _picked(rows, jnp.exp(last), decay)
    return heads, gamma, tail, dl_lanes, decay


def _own(x, mine):
    """``x`` [C, lanes] with the other heads' lanes zeroed."""
    return x if mine is None else jnp.where(mine, x, jnp.zeros_like(x))


def _cb(Cm, Bm, narrow):
    """``C B^T`` [C, C], the operands as they come."""
    if not narrow:
        return _dot(Cm, Bm, _NT, narrow)
    return jax.lax.dot_general(Cm, Bm, _NT, preferred_element_type=_F32)


def _fwd_kernel(u_ref, b_ref, c_ref, dl_ref, a_ref, d_ref, *rest, hb, narrow,
                state_dtype):
    """First chunk to last, every lane block's state carried. The results:
    ``y`` and, where the call is differentiated, the state before the
    chunk."""
    y_ref, *states_ref, s_scr = rest
    lanes = s_scr.shape[1]
    P = lanes // hb
    n = u_ref.shape[1] // lanes
    first = pl.program_id(2) * n

    @pl.when(pl.program_id(1) == 0)
    def _():
        for m in range(n):
            s_scr[first + m] = jnp.zeros(s_scr.shape[1:], _F32)

    Bm, Cm = b_ref[...], c_ref[...]
    C = Bm.shape[0]
    cb = _cb(Cm, Bm, narrow)
    Gc_all = _prefix(a_ref[...].astype(_F32))
    dl_all = dl_ref[...].astype(_F32)
    for m in range(n):
        at = slice(m * lanes, (m + 1) * lanes)
        u = u_ref[:, at]
        state = s_scr[first + m]
        heads, gamma, tail, dl_lanes, decay = _heads(
            Gc_all, dl_all, (first + m) * hb, hb, P, C)
        y = gamma * _dot(Cm, state, _NT, narrow) \
            + d_ref[:, at].astype(_F32) * u.astype(_F32)
        for h in heads:
            y = y + _dot(cb * h["M"] * _row(h["dl"]), _own(u, h["mine"]),
                         _NN, narrow)
        if states_ref:
            states_ref[0][m] = state
        y_ref[:, at] = y.astype(y_ref.dtype)
        s_scr[first + m] = _carried(
            decay * state + _dot(u.astype(_F32) * (dl_lanes * tail), Bm, _TN,
                                 narrow), state_dtype)


def _bwd_kernel(u_ref, b_ref, c_ref, dl_ref, a_ref, d_ref, dy_ref, s_ref,
                du_ref, db_ref, dc_ref, ddl_ref, da_ref, dd_ref, ds_scr, *,
                hb, narrow, state_dtype):
    """Last chunk to first with every lane block's ``dS`` carried; the state
    before the chunk as the forward's sweep wrote it."""
    lanes = ds_scr.shape[1]
    P = lanes // hb
    n = u_ref.shape[1] // lanes
    g = pl.program_id(2)
    first = g * n

    @pl.when(pl.program_id(1) == 0)
    def _():
        for m in range(n):
            ds_scr[first + m] = jnp.zeros(ds_scr.shape[1:], _F32)

    @pl.when(g == 0)
    def _():
        ddl_ref[...] = jnp.zeros(ddl_ref.shape, ddl_ref.dtype)
        da_ref[...] = jnp.zeros(da_ref.shape, da_ref.dtype)

    Bm, Cm = b_ref[...], c_ref[...]
    C = Bm.shape[0]
    cb = _cb(Cm, Bm, narrow)
    Gc_all = _prefix(a_ref[...].astype(_F32))
    dl_all = dl_ref[...].astype(_F32)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, ddl_ref.shape, 1)
    token = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    d_cb = jnp.zeros((C, C), _F32)      # summed over the group's heads
    dB = jnp.zeros(Bm.shape, _F32)
    dC = jnp.zeros(Cm.shape, _F32)
    for m in range(n):
        at = slice(m * lanes, (m + 1) * lanes)
        u, dy = u_ref[:, at], dy_ref[:, at]
        u32, dy32 = u.astype(_F32), dy.astype(_F32)
        state, d_next = s_ref[m], ds_scr[first + m]
        heads, gamma, tail, dl_lanes, decay = _heads(
            Gc_all, dl_all, (first + m) * hb, hb, P, C)

        b_ds = _dot(Bm, d_next, _NT, narrow)        # B dS'^T, [C, lanes]
        dx = tail * b_ds                            # to Delta u
        d_gc = []                                   # each head's, from M
        for h in heads:
            own = _own(dy, h["mine"])
            d_m = _dot(own, u, _NT, narrow) * _row(h["dl"]) * h["M"]
            d_cb = d_cb + d_m
            through = d_m * cb
            d_gc.append(jnp.sum(through, axis=1, keepdims=True)
                        - _col(jnp.sum(through, axis=0, keepdims=True)))
            dx = dx + _dot(cb * h["M"], own, _TN, narrow)
        dyg = dy32 * gamma
        x_tail = u32 * (dl_lanes * tail)
        # Per token and lane, what a head's lanes sum to: the step through
        # ``Delta u``; ``Gc_i`` through ``gamma_i``; ``Gc_j`` through the tail.
        to_dl = dx * u32
        to_gamma = dyg * _dot(Cm, state, _NT, narrow)
        to_tail = x_tail * b_ds
        kept = state * d_next                       # [lanes, N]
        for e, h in enumerate(heads):
            through_tail = jnp.sum(_own(to_tail, h["mine"]), axis=1,
                                   keepdims=True)
            d_last = jnp.exp(h["last"]) * jnp.sum(
                kept if h["rows"] is None
                else jnp.where(h["rows"], kept, 0.0), keepdims=True)
            dG = d_gc[e] + jnp.sum(_own(to_gamma, h["mine"]), axis=1,
                                   keepdims=True) - through_tail
            dG = dG + jnp.where(
                token == C - 1,
                d_last + jnp.sum(through_tail, keepdims=True), 0.0)
            d_dl = jnp.sum(_own(to_dl, h["mine"]), axis=1, keepdims=True)
            lane = head_lane == (first + m) * hb + e
            da_ref[...] = jnp.where(lane, dG.astype(da_ref.dtype),
                                    da_ref[...])
            ddl_ref[...] = jnp.where(lane, d_dl.astype(ddl_ref.dtype),
                                     ddl_ref[...])

        d_lanes = d_ref[:, at].astype(_F32)
        du_ref[:, at] = (dx * dl_lanes + dy32 * d_lanes).astype(du_ref.dtype)
        dd_ref[:, at] = jnp.sum(dy32 * u32, axis=0, keepdims=True)
        dC = dC + _dot(dyg, state, _NN, narrow)
        dB = dB + _dot(x_tail, d_next, _NN, narrow)
        ds_scr[first + m] = _carried(
            decay * d_next + _dot(dyg, Cm, _TN, narrow), state_dtype)

    db_ref[...] = (dB + _dot(d_cb, Cm, _TN, narrow)).astype(db_ref.dtype)
    dc_ref[...] = (dC + _dot(d_cb, Bm, _NN, narrow)).astype(dc_ref.dtype)

    @pl.when(g == pl.num_programs(2) - 1)
    def _():
        da_ref[...] = _prefix(da_ref[...], reverse=True)


def _call(kernel, name, operands, outs, *, groups, chunk, reverse, matmuls,
          interpret, state_dtype=None):
    """One sweep over the chunks (``_delta_rule.sweep``), a group's heads a
    grid step. ``operands``: ``(kind, array)`` each, the kinds ``wide`` ``[B,
    T, H * P]``, ``key`` ``[B, T, G * N]``, ``beta`` ``[B, T, H]``, ``lanes``
    ``[1, H * P]`` and ``states``; ``outs``: ``(kind, dtype)`` of each
    result."""
    u, Bm, delta = operands[0][1], operands[1][1], operands[3][1]
    B, T, H = delta.shape
    P, N = u.shape[2] // H, Bm.shape[2] // groups
    r = H // groups
    hb = heads_a_block(P, r)
    return sweep(
        functools.partial(kernel, hb=hb, narrow=u.dtype == _BF16,
                          state_dtype=state_dtype),
        name, operands, outs, chunk=chunk, reverse=reverse,
        flops=2 * matmuls * B * H * T * P * (chunk + 2 * N) // 3,
        transcendentals=B * H * T * (chunk + 3),
        interpret=interpret, group=r, state=(r // hb, hb * P, N))


def _operands(chunk, u, Bm, Cm, delta, A, D, *more):
    """The sweeps' operands by kind, padded to whole chunks: ``Delta`` and
    ``Delta A`` float32 ``[B, T, H]``, ``D`` a number a lane."""
    H = delta.shape[2]
    delta = delta.astype(_F32)
    kinds = ("wide", "key", "key", "beta", "beta") + ("wide",) * len(more)
    arrays = (u, Bm, Cm, delta, delta * A.astype(_F32)) + more
    ops = [(kind, _padded(x, chunk)) for kind, x in zip(kinds, arrays)]
    return ops[:5] + [("lanes", jnp.repeat(
        D.astype(_F32), u.shape[2] // H)[None])] + ops[5:]


def forward(u, Bm, Cm, delta, A, D, *, groups: int, chunk: int = CHUNK,
            interpret=None, out_dtype=None, state_dtype=None,
            states: bool = False):
    """The forward kernel alone; with ``states`` ``(y, states)``: also the
    state before every chunk, float32 (what the backward kernel reads), as
    one more result of the same sweep. A check's ``out_dtype`` (the result
    in float32, not rounded to the operands' dtype) and ``state_dtype`` (the
    carried state through a narrower dtype: the check's control)."""
    out = _call(_fwd_kernel, f"tepdist_ssd_fwd__g{groups}",
                _operands(chunk, u, Bm, Cm, delta, A, D),
                [("wide", out_dtype or u.dtype), ("states", _F32)][
                    :1 + states],
                groups=groups, chunk=chunk, reverse=False, matmuls=6,
                interpret=_interpret(interpret), state_dtype=state_dtype)
    y = out[0][:, :u.shape[1]]
    return (y, *out[1:]) if states else y


def backward(u, Bm, Cm, delta, A, D, dy, *, groups: int, kept=None,
             chunk: int = CHUNK, interpret=None, out_dtype=None,
             state_dtype=None):
    """``(du, dB, dC, dDelta, dA, dD)``; the last three float32 (``[B, T,
    H]``, ``[H]``, ``[H]``). ``kept``: the states as :func:`forward` hands
    them over (``states=True``); None runs that sweep first."""
    interpret = _interpret(interpret)
    if kept is None:
        kept = forward(u, Bm, Cm, delta, A, D, groups=groups, chunk=chunk,
                       interpret=interpret, state_dtype=state_dtype,
                       states=True)[1]
    dtype = out_dtype or u.dtype
    T, H = u.shape[1], delta.shape[2]
    du, dB, dC, d_dl, da, dd = _call(
        _bwd_kernel, f"tepdist_ssd_bwd__g{groups}",
        _operands(chunk, u, Bm, Cm, delta, A, D, dy) + [("states", kept)],
        [("wide", dtype)] + [("key", out_dtype or Bm.dtype)] * 2
        + [("beta", _F32)] * 2 + [("lanes_out", _F32)],
        groups=groups, chunk=chunk, reverse=True, matmuls=16,
        interpret=interpret, state_dtype=state_dtype)
    d_dl, da = d_dl[:, :T], da[:, :T]
    A32 = A.astype(_F32)
    return (du[:, :T], dB[:, :T], dC[:, :T], d_dl + da * A32,
            jnp.sum(da * delta.astype(_F32), axis=(0, 1)),
            jnp.sum(dd.reshape(-1, H, u.shape[2] // H), axis=(0, 2)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _attend(u, Bm, Cm, delta, A, D, groups, chunk, interpret, layers):
    traced.count("ssd_calls", layers=layers)
    return forward(u, Bm, Cm, delta, A, D, groups=groups, chunk=chunk,
                   interpret=interpret)


def _attend_fwd(u, Bm, Cm, delta, A, D, groups, chunk, interpret, layers):
    traced.count("ssd_calls", layers=layers)
    y, states = forward(u, Bm, Cm, delta, A, D, groups=groups, chunk=chunk,
                        interpret=interpret, states=True)
    return y, (u, Bm, Cm, delta, A, D, states)


def _attend_bwd(groups, chunk, interpret, layers, res, dy):
    *operands, states = res
    delta, A, D = operands[3:]
    du, dB, dC, d_delta, dA, dD = backward(
        *operands, dy, groups=groups, kept=states, chunk=chunk,
        interpret=interpret)
    return du, dB, dC, d_delta.astype(delta.dtype), dA.astype(A.dtype), \
        dD.astype(D.dtype)


_attend.defvjp(_attend_fwd, _attend_bwd)


def _checked(u, Bm, Cm, delta, A, D, groups, chunk):
    H = delta.shape[-1]
    if u.ndim != 3 or Bm.shape != Cm.shape or Bm.shape[:2] != u.shape[:2] \
            or delta.shape != u.shape[:2] + (H,) or A.shape != (H,) \
            or D.shape != (H,) or u.shape[2] % H or H % groups \
            or Bm.shape[2] % groups or chunk % 8:
        raise ValueError(
            f"ssd_attention: u {u.shape}, B {Bm.shape}, C {Cm.shape}, Delta "
            f"{delta.shape}, A {A.shape}, D {D.shape}, groups {groups}, "
            f"chunk {chunk}")
    return min(chunk, -(-u.shape[1] // 8) * 8)


def ssd_attention(u, Bm, Cm, delta, A, D, *, groups: int, chunk: int = CHUNK,
                  interpret: Optional[bool] = None):
    """The state-space rule over ``u`` [batch, T, H * P], ``Bm, Cm`` [batch,
    T, groups * N] (head ``h`` reads group ``h // (H / groups)``), ``delta``
    [batch, T, H] (the steps, float32, positive), ``A`` [H] (negative) and
    ``D`` [H] -> ``y`` [batch, T, H * P] in ``u``'s dtype. Differentiable in
    all six. The state starts at zero for every row of the batch.

    Counts, while it is traced, each forward kernel call in ``ssd_calls``
    (``telemetry/traced.py``)."""
    chunk = _checked(u, Bm, Cm, delta, A, D, groups, chunk)
    return _attend(u, Bm, Cm, delta, A, D, groups, chunk,
                   _interpret(interpret), traced.stood_for())


def chunked(u, Bm, Cm, delta, A, D, *, groups: int, chunk: int = CHUNK):
    """:func:`ssd_attention` in plain ``jax.numpy``: the chunked form above
    under a ``lax.scan`` over the chunks, float32 at the highest matmul
    precision, the groups' ``B`` and ``C`` repeated for their heads.
    Differentiable by autodiff; what the kernels are held to beside the
    recurrence."""
    B, T, _ = u.shape
    H = delta.shape[2]
    C = min(chunk, T)
    nc = -(-T // C)
    A32, D32 = A.astype(_F32), D.astype(_F32)

    def heads(x, n, repeat=1):
        # [B, T, n * w] -> [chunks, B, n * repeat, C, w]
        x = _padded(x.astype(_F32), C)
        x = x.reshape(B, nc, C, n, -1).transpose(1, 0, 3, 2, 4)
        return jnp.repeat(x, repeat, axis=2)

    i, j = _ij(C)
    dot = functools.partial(jnp.einsum, precision=_HIGHEST)

    def step(S, xs):         # S [B, H, P, N]
        u, Bh, Ch, dl = xs   # dl [B, H, C, 1]
        Gc = jnp.cumsum(dl * A32[:, None, None], axis=-2)
        M = jnp.exp(jnp.where(j <= i, Gc - Gc.swapaxes(-1, -2), -jnp.inf))
        x = u * dl
        y = dot("bhij,bhjp->bhip", dot("bhin,bhjn->bhij", Ch, Bh) * M, x) \
            + jnp.exp(Gc) * dot("bhin,bhpn->bhip", Ch, S) \
            + D32[:, None, None] * u
        last = Gc[..., -1:, :]
        S = S * jnp.exp(last) \
            + dot("bhjp,bhjn->bhpn", x * jnp.exp(last - Gc), Bh)
        return S, y

    P, N = u.shape[2] // H, Bm.shape[2] // groups
    _, y = jax.lax.scan(
        step, jnp.zeros((B, H, P, N), _F32),
        (heads(u, H), heads(Bm, groups, H // groups),
         heads(Cm, groups, H // groups), heads(delta, H)))
    y = y.transpose(1, 0, 3, 2, 4).reshape(B, nc * C, H * P)
    return y[:, :T].astype(u.dtype)
