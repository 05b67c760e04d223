"""Plain float32 Jamba (AI21 ``model_type: jamba``): forward, loss and
gradients.

The yardstick the Jamba cells' ``correct`` is decided against. Straight
``jax.numpy`` following the published ``config.json`` of
ai21labs/AI21-Jamba2-3B and, for what the config does not say, the ``jamba``
modelling code's conventions (the configuration file lists each such item
under ``assumed``): token embedding, no positional encoding of any kind,
pre-RMSNorm blocks without biases, layer ``i`` an attention layer iff ``i %
attn_layer_period == attn_layer_offset`` and a Mamba-1 layer otherwise, a
SwiGLU MLP in every layer (``num_experts: 1``), a final RMSNorm, logits
through the tied embedding, mean next-token cross entropy.

    x = x + mixer(rms(x; input_ln));  a = rms(x; ff_ln)
    x = x + W_down (silu(W_gate a) * (W_up a))

    attention:  q = a Wq (H heads), k = a Wk, v = a Wv (Hkv heads, query
                head h reading key/value head h // (H / Hkv)), causal softmax
                at head_dim ** -0.5, no rotary embedding, no QK-norm, o Wo
    Mamba:      [u, z] = a W_in
                c_t = silu(b_conv + sum_j w_conv[j] * u_{t-(K-1)+j})
                [r, B, C] = c W_x, each through its own RMSNorm
                delta = softplus(r W_dt + b_dt);  A = -exp(A_log)
                h_t = exp(delta_t A) * h_{t-1} + (delta_t * c_t) B_t^T
                y_t = h_t C_t + D * c_t;  out = (y * silu(z)) W_out

**The recurrence is a plain ``lax.scan`` over time steps**, one step a token,
the state ``[N, Di]`` carried from each to the next: no chunked form, no
kernel, no import from the program under test. Attention is an explicit mask
over explicit scores.

Everything is computed in float32 with ``precision=highest``. Departures
from a textbook implementation, all about memory and none about arithmetic:

* weights arrive in the dtype they are trained in and are widened where they
  are used; one sequence at a time; each block, each block of queries and
  each block of the loss's rows is wrapped in ``jax.checkpoint``, queries are
  taken ``QUERY_BLOCK`` and logits ``LOSS_BLOCK`` rows at a time;
* the time loop is cut into stretches of ``TIME_BLOCK`` steps, each under
  ``jax.checkpoint`` (a scan over stretches of a scan over steps: the same
  steps in the same order), so that the backward pass holds one stretch's
  states and not the sequence's (2.7e9 bytes a layer at 8192 x 5120 x 16);
* ``cast`` is applied to both operands of every matmul. The identity gives
  the reference; the control (``reference/gpt2.py:fp8_cast``) puts the
  reference in the program's place one precision step below bf16. The
  recurrence has no matmul and is not cast.

Parameter layout: ``tok_emb`` [V, d], ``norm_f`` [d], and the layers a run
of consecutive layers of one kind, in the model's order, every leaf stacked
over the run's layers and in one of the run's groups (``split_groups``):
``run0``, ``run1``, ... the matrices, ``vec0``, ... the per-channel leaves
(norm gains, conv, ``dt_bias``, ``D``), ``decay0``, ... a Mamba run's
``A_log``; or as ``layers``, a list of per-layer dicts. Every layer: ``input_ln``, ``ff_ln`` [d], ``w_gate``, ``w_up`` [d, f],
``w_down`` [f, d]. Attention: ``wq`` [d, H hd], ``wk``, ``wv`` [d, Hkv hd],
``wo`` [H hd, d]. Mamba: ``in_proj`` [d, 2 Di], ``conv_w`` [K, Di],
``conv_b`` [Di], ``x_proj`` [Di, R + 2 N], ``dt_norm`` [R], ``b_norm``,
``c_norm`` [N], ``dt_proj`` [R, Di], ``dt_bias`` [Di], ``A_log`` [Di, N],
``D`` [Di], ``out_proj`` [Di, d].
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
TIME_BLOCK = 256
LOSS_BLOCK = 1024


class Hyper(NamedTuple):
    """What the arithmetic needs beyond the weights' shapes."""
    n_head: int
    n_kv_head: int
    attn_layer_period: int
    attn_layer_offset: int
    d_state: int
    dt_rank: int
    eps: float = 1e-6


def identity(x):
    return x


def _mm(a, b, cast):
    return jnp.matmul(cast(a.astype(F32)), cast(b.astype(F32)),
                      precision=HIGHEST)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


def _attention(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> [T, d]."""
    T = a.shape[0]
    H, Hkv = hp.n_head, hp.n_kv_head
    hd = blk["wq"].shape[-1] // H

    def heads(t, n):
        return t.reshape(T, n, hd).transpose(1, 0, 2)

    q = heads(_mm(a, blk["wq"], cast), H)
    k, v = (jnp.repeat(heads(_mm(a, blk[w], cast), Hkv), H // Hkv, axis=0)
            for w in ("wk", "wv"))
    qb = min(QUERY_BLOCK, T)
    if T % qb:
        raise ValueError(f"{T} positions do not split into blocks of {qb}")
    keys = jnp.arange(T)

    @jax.checkpoint
    def query_block(args):
        start, qs = args                                   # qs [H, qb, hd]
        seen = (start + jnp.arange(qb))[:, None] >= keys[None, :]
        s = jnp.einsum("hqd,hkd->hqk", cast(qs), cast(k),
                       precision=HIGHEST) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", cast(p), cast(v),
                          precision=HIGHEST)

    o = jax.lax.map(query_block, (
        jnp.arange(0, T, qb),
        q.reshape(H, T // qb, qb, hd).transpose(1, 0, 2, 3)))
    o = o.transpose(0, 2, 1, 3).reshape(T, H * hd)
    return _mm(o, blk["wo"], cast)


def recurrence(c, delta, A, B, C):
    """``y_t = h_t C_t`` of ``h_t = exp(delta_t A) * h_{t-1} + (delta_t *
    c_t) B_t^T`` from ``h_0 = 0``, one step a token: c, delta [T, Di],
    A [Di, N], B, C [T, N] -> [T, Di]. The state is held ``[N, Di]``."""
    T, Di = c.shape
    At = A.T

    def step(h, x):
        c_t, d_t, B_t, C_t = x
        h = jnp.exp(d_t[None, :] * At) * h \
            + B_t[:, None] * (d_t * c_t)[None, :]
        return h, jnp.sum(h * C_t[:, None], axis=0)

    @jax.checkpoint
    def stretch(h, xs):
        return jax.lax.scan(step, h, xs)

    n = TIME_BLOCK if T % TIME_BLOCK == 0 else T
    _, y = jax.lax.scan(
        stretch, jnp.zeros(At.shape, F32),
        tuple(x.reshape(T // n, n, -1) for x in (c, delta, B, C)))
    return y.reshape(T, Di)


def _mamba(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> [T, d]."""
    T = a.shape[0]
    N, R = hp.d_state, hp.dt_rank
    u, z = jnp.split(_mm(a, blk["in_proj"], cast), 2, axis=-1)
    w = blk["conv_w"].astype(F32)
    K = w.shape[0]
    before = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    c = jax.nn.silu(blk["conv_b"].astype(F32)
                    + sum(w[j] * before[j:j + T] for j in range(K)))
    r, B, C = jnp.split(_mm(c, blk["x_proj"], cast), (R, R + N), axis=-1)
    r = _rms_norm(r, blk["dt_norm"], hp.eps)
    B = _rms_norm(B, blk["b_norm"], hp.eps)
    C = _rms_norm(C, blk["c_norm"], hp.eps)
    delta = jax.nn.softplus(_mm(r, blk["dt_proj"], cast)
                            + blk["dt_bias"].astype(F32))
    y = recurrence(c, delta, -jnp.exp(blk["A_log"].astype(F32)), B, C) \
        + blk["D"].astype(F32) * c
    return _mm(y * jax.nn.silu(z), blk["out_proj"], cast)


def _block(blk, x, hp: Hyper, attention: bool, cast):
    mixer = _attention if attention else _mamba
    x = x + mixer(blk, _rms_norm(x, blk["input_ln"], hp.eps), hp, cast)
    a = _rms_norm(x, blk["ff_ln"], hp.eps)
    return x + _mm(jax.nn.silu(_mm(a, blk["w_gate"], cast))
                   * _mm(a, blk["w_up"], cast), blk["w_down"], cast)


GROUPS = ("run", "vec", "decay")
VEC = ("input_ln", "ff_ln", "conv_w", "conv_b", "dt_norm", "b_norm",
       "c_norm", "dt_bias", "D")
DECAY = ("A_log",)


def split_groups(stack: dict, r: int) -> dict:
    """Run ``r``'s stacked leaves under their groups' names."""
    out = {}
    for k, v in stack.items():
        group = "vec" if k in VEC else "decay" if k in DECAY else "run"
        out.setdefault(f"{group}{r}", {})[k] = v
    return out


def layers_of(params) -> list:
    """Per-layer dicts in the model's order, whichever layout came."""
    if "layers" in params:
        return list(params["layers"])
    out, r = [], 0
    while f"run{r}" in params:
        stack = {k: v for g in GROUPS
                 for k, v in params.get(f"{g}{r}", {}).items()}
        n = next(iter(stack.values())).shape[0]
        out.extend({k: v[i] for k, v in stack.items()} for i in range(n))
        r += 1
    return out


def hidden(params, tokens, hp: Hyper, cast=identity):
    """One sequence: tokens int32 [T] -> final normalised hidden [T, d]."""
    x = params["tok_emb"][tokens].astype(F32)
    for i, blk in enumerate(layers_of(params)):
        attention = i % hp.attn_layer_period == hp.attn_layer_offset
        if attention != ("wq" in blk):
            raise ValueError(f"layer {i}: the period rule says attention "
                             f"is {attention}, its weights say otherwise")
        x = jax.checkpoint(
            lambda b, h, a=attention: _block(b, h, hp, a, cast))(blk, x)
    return _rms_norm(x, params["norm_f"], hp.eps)


def logits(params, tokens, hp: Hyper, cast=identity):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    return jnp.stack([_mm(hidden(params, t, hp, cast),
                          params["tok_emb"].T, cast) for t in tokens])


def loss(params, tokens, hp: Hyper, cast=identity, weights=None):
    """Next-token cross entropy of tokens [B, T+1]: the mean over the batch
    of each sequence's own, or with ``weights`` [B] the sum weighted by them
    (a batch that repeats sequences is then computed from the distinct
    ones)."""
    B = tokens.shape[0]
    if weights is None:
        weights = jnp.full((B,), 1.0 / B, F32)

    @jax.checkpoint
    def rows(args):
        x, targets = args
        lg = _mm(x, params["tok_emb"].T, cast)
        gold = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    def cross_entropy(x, targets):
        T = x.shape[0]
        n = LOSS_BLOCK if T % LOSS_BLOCK == 0 else T
        return jnp.sum(jax.lax.map(rows, (
            x.reshape(T // n, n, -1), targets.reshape(T // n, n)))) / T

    total = 0.0
    for i in range(B):
        x = hidden(params, tokens[i, :-1], hp, cast)
        total = total + weights[i] * cross_entropy(x, tokens[i, 1:])
    return total
