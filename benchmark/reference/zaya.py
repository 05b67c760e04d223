"""Plain float32 ZAYA (Zyphra ZAYA1-8B): forward, loss, gradients and the
router bias's update.

The yardstick the zaya1-8b cell's ``correct`` is decided against. Straight
``jax.numpy`` following the published ``config.json`` of Zyphra/ZAYA1-8B
(``model_type: zaya``) and, for what its keys name and do not spell out, the
two publications they are the keys of: Compressed Convolutional Attention
(Zyphra, arXiv:2510.04476; ``cca_time0``, ``cca_time1``, the latents'
widths) and the ZAYA1 technical report (arXiv:2511.17127;
``router_hidden_size``, one expert a token). The configuration file lists
under ``assumed`` what neither fixes. Per layer, one sequence ``x`` [T, d],
``a_{-1} = 0``, ``r_{-1} = 0``, zeros before the sequence in every conv:

    a    = rms(x; attn_ln)
    q0   = a Wq   -> [T, H, D];   k0 = a Wk   -> [T, Hkv, D]
    v_t  = [a_t Wva ; a_{t-1} Wvb]                 [T, Hkv, D]
    u    = [q0 ; k0]                                [T, N, D], N = H + Hkv
    c1_t = b1 + w1[0] * u_{t-1} + w1[1] * u_t       depth-wise, 2 taps
    c2_t,h = b2_h + c1_{t-1,h} W2[0,h] + c1_{t,h} W2[1,h]   (c1_{-1} = 0)
    m_q,h = (q0_h + k0_g(h)) / 2;  m_k,g = (mean_{h in g} q0_h + k0_g) / 2
    q    = c2[:H] + m_q;   k = c2[H:] + m_k
    q    = q / |q|_2 sqrt(D);   k = k / |k|_2 sqrt(D) tau_g
    q, k = rope(q), rope(k)    the first ``rotary_dim`` channels of a head
    o_h  = softmax_causal(q_h k_g(h)^T D^-0.5) v_g(h)
    x    = x + concat_h(o_h) Wo

    h    = rms(x; moe_ln)
    r    = rms(h Wrd; router_ln) + gamma * r_{l-1}
    z    = gelu(gelu(r W1) W2) W3;  p = softmax(z);  e = argmax(p + b)
    x    = x + p[e] * Wd[e] (silu(Wg[e] h) * Wu[e] h)

then the final RMSNorm, the tied head and the cross entropy; no auxiliary
loss. No kernel, no sort, no layout, no grouped matmul, no import from the
program under test: the shifts are a row of zeros joined before the rows,
the convs shifted sums, attention an explicit mask over explicit scores, and
**every expert is applied to every token** and the result multiplied by the
gate where the expert is the token's choice, by zero elsewhere.

Everything is computed in float32 with ``precision=highest``. Departures
from the published description:

* memory, not arithmetic: weights arrive in the dtype they are trained in
  and are widened where they are used; each block, each block of queries and
  each expert is wrapped in ``jax.checkpoint``, queries are taken
  ``QUERY_BLOCK`` at a time (``lax.map``), the head with its loss
  ``TOKEN_BLOCK`` tokens at a time, and experts are walked one at a time
  (``lax.scan``);
* the bias's update is ``reference/afmoe.py``'s (``bias_update``; the
  report's own controller is no key of the config);
* ``cast`` is applied to both operands of every matmul, the convs' and the
  router's included. The identity gives the reference; the control
  (``reference/gpt2.py:fp8_cast``) puts the reference in the program's
  place one precision step below bf16.

Parameter layout: ``tok_emb`` [V, d], ``norm_f`` [d], and the layers as
``blocks``, one dict of leaves stacked on a leading layer axis, or as
``layers``, a list of per-layer dicts: ``attn_ln``, ``moe_ln`` [d], ``wq``
[d, H D], ``wk`` [d, Hkv D], ``wva``, ``wvb`` [d, Hkv D / 2], ``wo`` [H D,
d], ``conv_w1`` [2, N D], ``conv_b1``, ``conv_b2`` [N D], ``conv_w2`` [2, N,
D, D], ``tau`` [Hkv], ``router_down`` [d, R], ``router_ln``,
``router_gamma`` [R], ``router_w1``, ``router_w2`` [R, R], ``router_w3`` [R,
E], ``router_bias`` [E], ``w_gate``, ``w_up`` [E, d, f], ``w_down`` [E, f,
d].
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import bias_update  # noqa: F401 (the same)

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048


class Hyper(NamedTuple):
    """What the arithmetic needs beyond the weights' shapes."""
    head_dim: int
    rotary_dim: int
    rope_theta: float = 5e6
    eps: float = 1e-5
    l2_eps: float = 1e-12


def identity(x):
    return x


def _mm(a, b, cast):
    return jnp.matmul(cast(a.astype(F32)), cast(b.astype(F32)),
                      precision=HIGHEST)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


def _before(x):
    """[T, ...] -> row ``t - 1`` at row ``t``, a row of zeros first."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def _rope(x, hp: Hyper):
    """[.., T, D] -> the first ``rotary_dim`` channels of position t rotated
    (``x * cos + rotate_half(x) * sin`` over those channels), the rest as
    they are."""
    T, n = x.shape[-2], hp.rotary_dim
    inv_freq = 1.0 / hp.rope_theta ** (jnp.arange(0, n, 2, dtype=F32) / n)
    angles = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)           # [T, n]
    turned, kept = x[..., :n], x[..., n:]
    half = jnp.concatenate([-turned[..., n // 2:], turned[..., :n // 2]],
                           axis=-1)
    return jnp.concatenate(
        [turned * jnp.cos(angles) + half * jnp.sin(angles), kept], axis=-1)


def _mixed(blk, q0, k0, cast):
    """The latents [T, H, D], [T, Hkv, D] -> q [T, H, D], k [T, Hkv, D]: the
    two convs and the q-k mean, before the norm."""
    T, H, D = q0.shape
    Hkv = k0.shape[1]
    u = jnp.concatenate([q0, k0], axis=1)                         # [T, N, D]
    w1 = blk["conv_w1"].astype(F32).reshape(2, H + Hkv, D)
    c1 = blk["conv_b1"].astype(F32).reshape(H + Hkv, D) \
        + w1[0] * _before(u) + w1[1] * u
    w2 = blk["conv_w2"].astype(F32)                               # [2,N,D,D]

    def heads_mm(c, w):
        return jnp.einsum("tnd,nde->tne", cast(c), cast(w),
                          precision=HIGHEST)

    c2 = blk["conv_b2"].astype(F32).reshape(H + Hkv, D) \
        + heads_mm(_before(c1), w2[0]) + heads_mm(c1, w2[1])
    grouped = q0.reshape(T, Hkv, H // Hkv, D)
    m_q = (grouped + k0[:, :, None, :]) / 2
    m_k = (jnp.mean(grouped, axis=2) + k0) / 2
    return c2[:, :H] + m_q.reshape(T, H, D), c2[:, H:] + m_k


def _l2(x, hp: Hyper):
    return x * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + hp.l2_eps) \
        * math.sqrt(hp.head_dim)


def _attention(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> the compressed attention
    sublayer's output [T, d]."""
    T, D = a.shape[0], hp.head_dim
    q0 = _mm(a, blk["wq"], cast).reshape(T, -1, D)
    k0 = _mm(a, blk["wk"], cast).reshape(T, -1, D)
    v = jnp.concatenate([
        _mm(a, blk["wva"], cast).reshape(T, -1, D),
        _mm(_before(a), blk["wvb"], cast).reshape(T, -1, D)], axis=1)
    H, Hkv = q0.shape[1], k0.shape[1]
    q, k = _mixed(blk, q0, k0, cast)
    q = _l2(q, hp)
    k = _l2(k, hp) * blk["tau"].astype(F32)[None, :, None]
    # Heads first; a query head reads key/value head h // (H / Hkv).
    q = _rope(q.transpose(1, 0, 2), hp)                           # [H, T, D]
    k = jnp.repeat(_rope(k.transpose(1, 0, 2), hp), H // Hkv, axis=0)
    v = jnp.repeat(v.transpose(1, 0, 2), H // Hkv, axis=0)
    qb = min(QUERY_BLOCK, T)
    if T % qb:
        raise ValueError(f"{T} positions do not split into blocks of {qb}")
    keys = jnp.arange(T)

    @jax.checkpoint
    def query_block(args):
        start, qs = args                                     # qs [H, qb, D]
        seen = (start + jnp.arange(qb))[:, None] >= keys[None, :]
        s = jnp.einsum("hqd,hkd->hqk", cast(qs), cast(k),
                       precision=HIGHEST) * D ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", cast(p), cast(v),
                          precision=HIGHEST)

    o = jax.lax.map(query_block, (
        jnp.arange(0, T, qb),
        q.reshape(H, T // qb, qb, D).transpose(1, 0, 2, 3)))
    # [blocks, H, qb, D] -> positions in order, heads side by side
    o = o.transpose(0, 2, 1, 3).reshape(T, H * D)
    return _mm(o, blk["wo"], cast)


def route(blk, h, r_before, hp: Hyper, cast):
    """h [T, d], r_before [T, R] -> (the router's state r [T, R],
    probabilities [T, E], gate [T], expert id [T])."""
    r = _rms_norm(_mm(h, blk["router_down"], cast), blk["router_ln"],
                  hp.eps) + blk["router_gamma"].astype(F32) * r_before
    z = _mm(jax.nn.gelu(_mm(jax.nn.gelu(
        _mm(r, blk["router_w1"], cast), approximate=False),
        blk["router_w2"], cast), approximate=False), blk["router_w3"], cast)
    probs = jax.nn.softmax(z, axis=-1)
    # The bias moves the choice only; no gradient reaches it.
    expert = jnp.argmax(probs + blk["router_bias"].astype(F32), axis=-1)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]
    return r, probs, gate, expert


def _moe(blk, h, r_before, hp: Hyper, cast):
    """One sequence: h [T, d] -> (the chosen experts' output [T, d], the
    router's state [T, R], expert ids [T, 1])."""
    E = blk["router_w3"].shape[-1]
    r, _, gate, expert = route(blk, h, r_before, hp, cast)
    weight = jax.nn.one_hot(expert, E, dtype=F32) * gate[:, None]   # [T, E]

    @jax.checkpoint
    def one(w_gate, w_up, w_down, g):
        act = jax.nn.silu(_mm(h, w_gate, cast)) * _mm(h, w_up, cast)
        return _mm(act, w_down, cast) * g[:, None]

    def step(y, e):
        return y + one(*e), None

    y, _ = jax.lax.scan(step, jnp.zeros_like(h), (
        blk["w_gate"], blk["w_up"], blk["w_down"], weight.T))
    return y, r, expert[:, None]


def _block(blk, x, r, hp: Hyper, cast):
    x = x + _attention(blk, _rms_norm(x, blk["attn_ln"], hp.eps), hp, cast)
    y, r, experts = _moe(blk, _rms_norm(x, blk["moe_ln"], hp.eps), r, hp,
                         cast)
    return x + y, r, experts


def layers_of(params) -> list:
    """Per-layer dicts, in order, whichever layout came."""
    if "layers" in params:
        return list(params["layers"])
    stack = params["blocks"]
    n = next(iter(stack.values())).shape[0]
    return [{k: v[i] for k, v in stack.items()} for i in range(n)]


def hidden(params, tokens, hp: Hyper, cast=identity):
    """One sequence: tokens int32 [T] -> (final normalised hidden [T, d],
    the layers' expert ids [layers, T, 1])."""
    x = params["tok_emb"][tokens].astype(F32)
    layers = layers_of(params)
    r = jnp.zeros((x.shape[0], layers[0]["router_ln"].shape[0]), F32)
    chosen = []
    for blk in layers:
        x, r, experts = jax.checkpoint(
            lambda b, h, s: _block(b, h, s, hp, cast))(blk, x, r)
        chosen.append(experts)
    return _rms_norm(x, params["norm_f"], hp.eps), jnp.stack(chosen)


def logits(params, tokens, hp: Hyper, cast=identity):
    """tokens int32 [B, T] -> float32 logits [B, T, V] (the tied head)."""
    return jnp.stack([_mm(hidden(params, t, hp, cast)[0],
                          params["tok_emb"].T, cast) for t in tokens])


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
            lg = _mm(xc, params["tok_emb"].T, cast)
            gold = jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

        return jnp.sum(jax.lax.map(part, (
            x.reshape(T // tb, tb, -1), targets.reshape(T // tb, tb)))) / T

    total = 0.0
    for i in range(B):
        x, _ = hidden(params, tokens[i, :-1], hp, cast)
        total = total + weights[i] * cross_entropy(x, tokens[i, 1:])
    return total


def expert_counts(params, tokens, hp: Hyper, cast=identity):
    """tokens [B, T+1] -> float32 [layers, E]: the assignments each expert
    got over the whole batch, what the bias's update reads."""
    E = layers_of(params)[-1]["router_w3"].shape[-1]
    chosen = jnp.stack([hidden(params, t[:-1], hp, cast)[1] for t in tokens])
    return jnp.sum(jax.nn.one_hot(chosen, E, dtype=F32), axis=(0, 2, 3))
