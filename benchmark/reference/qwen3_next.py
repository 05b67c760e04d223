"""Plain float32 Qwen3-Next (Qwen ``Qwen3-Next-80B-A3B``, ``model_type:
qwen3_next``): forward, loss and gradients.

The yardstick the qwen3-next-80b-a3b cell's ``correct`` is decided against.
Straight ``jax.numpy`` following the published ``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct and, for what its keys name and do not spell
out, the model's own published ``modeling_qwen3_next.py`` and the publication
behind its linear layers (Gated Delta Networks, arXiv:2412.06464); the
configuration file lists under ``assumed`` what neither fixes. Every norm but
the gated one is zero-centred: ``rms0(x; w) = x / rms(x) * (1 + w)``. Layer
``i`` (from 0) is full attention where ``(i + 1) % full_attention_interval
== 0`` and Gated DeltaNet otherwise; ``a = rms0(x; input_ln)``:

a Gated-DeltaNet layer (``Hk`` key heads under ``Hv`` value heads of ``K =
V`` channels; value head ``h`` reads key head ``h // (Hv / Hk)``)

    [q~|k~|v~] = silu(conv(a Wqkv))   one depth-wise causal conv over the
                        joined Hk K + Hk K + Hv V channels, taps [taps, C],
                        no bias, zeros before the sequence
    z, [b | al] = a Wz, a Wba
    q_j    = q~_j / sqrt(|q~_j|^2 + 1e-6) * K^-0.5
    k_j    = k~_j / sqrt(|k~_j|^2 + 1e-6)
    g_h    = -exp(A_h) * softplus(al_h + dt_h)      one number a value head
    beta_h = sigmoid(b_h)
    S_t    = exp(g_t) S_{t-1};  r = S_t^T k_t
    S_t    = S_t + beta_t k_t (v_t - r)^T;  o_t = S_t^T q_t      S_0 = 0
    y_h    = o_h / rms(o_h) * o_norm * silu(z_h)    (not zero-centred)
    x      = x + concat_h(y_h) Wo

a gated attention layer (``H`` query heads over ``Hkv`` key/value heads of
``hd`` channels, the first ``rotary_dim`` of them rotated)

    q, gate, k, v = a Wq, a Wa, a Wk, a Wv
    q_h, k_j = rope(rms0(q_h; q_norm)), rope(rms0(k_j; k_norm))
    o_h    = softmax_causal(q_h k_j^T hd^-0.5) v_j * sigmoid(gate_h)
                                                  j = h // (H / Hkv)
    x      = x + concat_h(o_h) Wo

then in every layer

    h      = rms0(x; post_attn_ln)
    p      = softmax(h Wr);  e = top_k(p);  w = p[e] / sum p[e]
    x      = x + sum_j w_j expert_{e_j}(h) + sigmoid(h w_sg) * shared(h)

the final ``rms0``, the untied head and the cross entropy; no auxiliary loss
and no multi-token-prediction block. No kernel, no chunked form, no sort, no
layout, no grouped matmul, no import from the program under test: **the delta
rule is the recurrence above a token at a time** (one ``lax.scan`` step a
token, all value heads at once, the key heads repeated for them), the conv a
sum of shifted copies, attention an explicit mask over explicit scores, and
**every held expert is applied to every token**, its result multiplied by
the router's weight where the expert is among the token's top k, by zero
elsewhere.

Everything is computed in float32 with ``precision=highest``. Departures
from the published description:

* memory, not arithmetic: weights arrive in the dtype they are trained in
  and are widened where they are used; each block, each block of queries and
  each expert is wrapped in ``jax.checkpoint``, queries are taken
  ``QUERY_BLOCK`` at a time (``lax.map``), the head with its loss
  ``TOKEN_BLOCK`` tokens at a time, experts are walked one at a time
  (``lax.scan``), and the recurrence's scan is checkpointed ``SCAN_BLOCK``
  tokens at a time;
* **the share of the experts**: the weights that come are the held experts'
  (``Hyper.held = (first, count)`` of the router's). What an expert
  elsewhere would add is left out, and that partial result goes on to the
  next layer, in the program alike. ``(0, E)`` is the uncut layer;
* the columns of ``Wqkv``, ``Wz`` and ``Wba`` lie a kind after a kind (all
  of q, then k, then v; b, then al) where the published ``in_proj_qkvz`` and
  ``in_proj_ba`` interleave them a key head, and ``Wq`` / ``Wa`` are the
  query and the gate halves of the published ``q_proj``, which interleaves
  them a head: permutations of columns;
* ``cast`` is applied to both operands of every matmul, the router's and
  the recurrence's products with the state included, and to the conv's
  operands. The identity gives the reference; the control
  (``reference/gpt2.py:fp8_cast``) puts the reference in the program's
  place one precision step below bf16.

Parameter layout: ``tok_emb`` [V, d], ``norm_f`` [d], ``lm_head`` [V, d], and
the layers as ``run0``, ``run1``, ...: a run of consecutive layers of one
kind one dict of leaves stacked on a leading layer axis, in the model's
order; or as ``layers``, a list of per-layer dicts. A layer is a
Gated-DeltaNet layer where it has ``conv``.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
SCAN_BLOCK = 128


class Hyper(NamedTuple):
    """What the arithmetic needs beyond the weights' shapes."""
    key_heads: int                       # Gated DeltaNet's
    value_heads: int
    n_head: int                          # the attention layers'
    n_kv_head: int
    rotary_dim: int
    top_k: int
    held: Tuple[int, int]                # (first, count) of the router's E
    rope_theta: float = 1e7
    eps: float = 1e-6


def identity(x):
    return x


def _mm(a, b, cast):
    return jnp.matmul(cast(a.astype(F32)), cast(b.astype(F32)),
                      precision=HIGHEST)


def _rms0(x, w, eps):
    """The zero-centred RMSNorm: the gain is ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w.astype(F32))


def _swiglu(h, w_gate, w_up, w_down, cast):
    return _mm(jax.nn.silu(_mm(h, w_gate, cast)) * _mm(h, w_up, cast),
               w_down, cast)


def conv_silu(u, taps, cast=identity):
    """u [T, C], taps [n, C] -> ``silu(sum_j taps[j] * u[t - (n - 1) +
    j])``, rows before the sequence zeros."""
    n, T = taps.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((n - 1, u.shape[1]), F32),
                              cast(u.astype(F32))])
    taps = cast(taps.astype(F32))
    return jax.nn.silu(sum(taps[j] * padded[j:j + T] for j in range(n)))


def recurrence(q, k, v, g, beta, cast=identity):
    """The scalar-decay gated delta rule a token at a time: q, k [T, Hk, K],
    v [T, Hv, V], g, beta [T, Hv] -> o [T, Hv, V], float32; value head ``h``
    reads key head ``h // (Hv / Hk)``. ``S`` [Hv, K, V] starts at 0."""
    T, Hk, K = q.shape
    Hv, V = v.shape[1:]
    q, k = (jnp.repeat(x, Hv // Hk, axis=1) for x in (q, k))

    def token(S, x):
        q, k, v, g, b = x
        S = S * jnp.exp(g)[:, None, None]
        r = jnp.einsum("hkv,hk->hv", cast(S), cast(k), precision=HIGHEST)
        S = S + (b[:, None] * k)[..., None] * (v - r)[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", cast(S), cast(q),
                             precision=HIGHEST)

    @jax.checkpoint
    def tokens(S, xs):
        return jax.lax.scan(token, S, xs)

    n = SCAN_BLOCK if T % SCAN_BLOCK == 0 else T
    xs = tuple(x.astype(F32).reshape(T // n, n, *x.shape[1:])
               for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(tokens, jnp.zeros((Hv, K, V), F32), xs)
    return o.reshape(T, Hv, V)


def _gdn(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> the Gated-DeltaNet
    mixer's output [T, d]."""
    T, Hk, Hv = a.shape[0], hp.key_heads, hp.value_heads
    V = blk["wz"].shape[-1] // Hv
    K = (blk["wqkv"].shape[-1] - Hv * V) // (2 * Hk)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    mixed = conv_silu(_mm(a, blk["wqkv"], cast), blk["conv"], cast)
    q = l2(mixed[:, :Hk * K].reshape(T, Hk, K)) * K ** -0.5
    k = l2(mixed[:, Hk * K:2 * Hk * K].reshape(T, Hk, K))
    v = mixed[:, 2 * Hk * K:].reshape(T, Hv, V)
    ba = _mm(a, blk["wba"], cast)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(blk["A_log"].astype(F32)) * jax.nn.softplus(
        ba[:, Hv:] + blk["dt_bias"].astype(F32))
    o = recurrence(q, k, v, g, beta, cast)
    z = _mm(a, blk["wz"], cast).reshape(T, Hv, V)
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + hp.eps) \
        * blk["o_norm"].astype(F32) * jax.nn.silu(z)
    return _mm(y.reshape(T, -1), blk["wo"], cast)


def _rope(x, theta: float, rotary_dim: int):
    """x [H, T, hd]: the first ``rotary_dim`` channels rotated (half-split
    pairs, channel ``i`` with ``i + rotary_dim / 2``), the rest as they
    are."""
    T = x.shape[1]
    half = rotary_dim // 2
    inv_freq = 1.0 / theta ** (jnp.arange(0, half, dtype=F32) / half)
    angles = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           axis=-1)


def _attention(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> the gated attention's
    output [T, d]."""
    T = a.shape[0]
    H, Hkv = hp.n_head, hp.n_kv_head
    hd = blk["wk"].shape[-1] // Hkv

    def heads(t, n):
        return t.reshape(T, n, -1).transpose(1, 0, 2)

    q, gate = heads(_mm(a, blk["wq"], cast), H), _mm(a, blk["wa"], cast)
    k = heads(_mm(a, blk["wk"], cast), Hkv)
    v = heads(_mm(a, blk["wv"], cast), Hkv)
    q = _rope(_rms0(q, blk["q_norm"], hp.eps), hp.rope_theta, hp.rotary_dim)
    k = _rope(_rms0(k, blk["k_norm"], hp.eps), hp.rope_theta, hp.rotary_dim)
    k, v = (jnp.repeat(t, H // Hkv, axis=0) for t in (k, v))
    qb = min(QUERY_BLOCK, T)
    if T % qb:
        raise ValueError(f"{T} positions do not split into blocks of {qb}")
    keys = jnp.arange(T)

    @jax.checkpoint
    def query_block(args):
        start, qs = args                                 # qs [H, qb, hd]
        seen = (start + jnp.arange(qb))[:, None] >= keys[None, :]
        s = jnp.einsum("hqd,hkd->hqk", cast(qs), cast(k),
                       precision=HIGHEST) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", cast(p), cast(v),
                          precision=HIGHEST)

    o = jax.lax.map(query_block, (
        jnp.arange(0, T, qb),
        q.reshape(H, T // qb, qb, hd).transpose(1, 0, 2, 3)))
    o = o.transpose(0, 2, 1, 3).reshape(T, H * hd) * jax.nn.sigmoid(gate)
    return _mm(o, blk["wo"], cast)


def route(blk, h, hp: Hyper, cast):
    """h [T, d] -> (weights [T, k], expert ids [T, k]): the top k of the
    softmax over all experts, normalised over the k chosen."""
    p = jax.nn.softmax(_mm(h, blk["router"], cast), axis=-1)
    chosen, experts = jax.lax.top_k(p, hp.top_k)
    return chosen / jnp.sum(chosen, axis=-1, keepdims=True), experts


def _moe(blk, h, hp: Hyper, cast):
    """One sequence: h [T, d] -> (the held routed experts' part plus the
    gated shared expert's output [T, d], expert ids [T, k])."""
    E = blk["router"].shape[-1]
    first, count = hp.held
    weights, experts = route(blk, h, hp, cast)
    chosen = jax.nn.one_hot(experts, E, dtype=F32)                # [T, k, E]
    gate = jnp.sum(chosen * weights[..., None], axis=1)           # [T, E]
    gate = gate[:, first:first + count]      # an expert elsewhere: left out

    @jax.checkpoint
    def expert(w_gate, w_up, w_down, g):
        return _swiglu(h, w_gate, w_up, w_down, cast) * g[:, None]

    def step(y, e):
        return y + expert(*e), None

    y, _ = jax.lax.scan(step, jnp.zeros_like(h),
                        (blk["w_gate"], blk["w_up"], blk["w_down"], gate.T))
    shared = _swiglu(h, blk["shared_gate"], blk["shared_up"],
                     blk["shared_down"], cast)
    return y + jax.nn.sigmoid(_mm(h, blk["shared_expert_gate"], cast)) \
        * shared, experts


def _block(blk, x, hp: Hyper, cast):
    mixer = _gdn if "conv" in blk else _attention
    x = x + mixer(blk, _rms0(x, blk["input_ln"], hp.eps), hp, cast)
    y, experts = _moe(blk, _rms0(x, blk["post_attn_ln"], hp.eps), hp, cast)
    return x + y, experts


def layers_of(params) -> list:
    """Per-layer dicts in the model's order, whichever layout came."""
    if "layers" in params:
        return list(params["layers"])
    out, r = [], 0
    while f"run{r}" in params:
        stack = params[f"run{r}"]
        n = next(iter(stack.values())).shape[0]
        out += [{k: v[i] for k, v in stack.items()} for i in range(n)]
        r += 1
    return out


def hidden(params, tokens, hp: Hyper, cast=identity):
    """One sequence: tokens int32 [T] -> (final normalised hidden [T, d],
    the layers' expert ids [layers, T, k])."""
    x = params["tok_emb"][tokens].astype(F32)
    chosen = []
    for blk in layers_of(params):
        x, experts = jax.checkpoint(
            lambda b, h: _block(b, h, hp, cast))(blk, x)
        chosen.append(experts)
    return _rms0(x, params["norm_f"], hp.eps), jnp.stack(chosen)


def logits(params, tokens, hp: Hyper, cast=identity):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    return jnp.stack([_mm(hidden(params, t, hp, cast)[0],
                          params["lm_head"].T, cast) for t in tokens])


def loss(params, tokens, hp: Hyper, cast=identity, weights=None):
    """The training loss, the cross entropy alone, of tokens [B, T+1]: the
    mean over the batch of each sequence's own, or with ``weights`` [B] the
    sum weighted by them (a batch that repeats sequences is then computed
    from the distinct ones)."""
    B = tokens.shape[0]
    if weights is None:
        weights = jnp.full((B,), 1.0 / B, F32)

    def cross_entropy(x, targets):
        T = x.shape[0]
        tb = TOKEN_BLOCK if T % TOKEN_BLOCK == 0 else T

        @jax.checkpoint
        def part(args):          # never a [T, V] array
            xc, tc = args
            lg = _mm(xc, params["lm_head"].T, cast)
            gold = jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

        return jnp.sum(jax.lax.map(part, (
            x.reshape(T // tb, tb, -1), targets.reshape(T // tb, tb)))) / T

    total = 0.0
    for i in range(B):
        x, _ = hidden(params, tokens[i, :-1], hp, cast)
        total = total + weights[i] * cross_entropy(x, tokens[i, 1:])
    return total
