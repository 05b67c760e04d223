"""Plain float32 Mellum2 (JetBrains ``model_type: mellum``): forward, loss
and gradients.

The yardstick the Mellum2 cells' ``correct`` is decided against. Straight
``jax.numpy`` following the published ``config.json`` of
JetBrains/Mellum2-12B-A2.5B-Instruct and, for what the config does not say,
the Qwen3-MoE convention whose key set it follows (the configuration file
lists each such item under ``assumed``): token embedding, pre-RMSNorm
blocks without biases (``input_layernorm``, ``post_attention_layernorm``),
RMSNorm over each head of q and k, rotary positions (rotate-half) on every
layer, the plain table on the ``sliding_attention`` layers and the YaRN
table on the ``full_attention`` ones, causal softmax attention (window
layers: key j visible to query i iff ``0 <= i - j < window``), query head h
reading key/value head ``h // (H / Hkv)``, then in every layer a router
``p = softmax(h Wr)``, ``e = top_k(p)``, ``w = p[e] / sum p[e]``
(``norm_topk_prob``) over routed SwiGLU experts, no shared expert and no
dense layer; final RMSNorm, untied head, cross entropy and no auxiliary
loss. No kernel, no sort, no layout, no grouped matmul, no import from the
program under test: **every held expert is applied to every token** and the
result multiplied by the router's weight where the expert is among the
token's top k, by zero elsewhere; attention is an explicit mask over
explicit scores.

**The YaRN table** (``transformers``' ``_compute_yarn_parameters``,
``truncate`` at its default), in ``yarn_inv_freq``: pair ``i`` of a head's
``hd / 2`` has the plain frequency ``f_i = theta ** (-2i / hd)``;
``corr(r) = hd ln(L0 / (2 pi r)) / (2 ln theta)`` is the pair that turns
``r`` times over the original context ``L0``; ``low = floor(corr(beta_fast))``,
``high = ceil(corr(beta_slow))``, ``ramp_i = clip((i - low) / (high - low),
0, 1)``; ``inv_freq_i = (1 - ramp_i) f_i + ramp_i f_i / factor``; cos and
sin are both multiplied by ``attention_factor``.

Everything is computed in float32 with ``precision=highest``. Departures
from the published description:

* memory, not arithmetic: weights arrive in the dtype they are trained in
  and are widened where they are used; each block, each block of queries
  and each expert is wrapped in ``jax.checkpoint``, queries are taken
  ``QUERY_BLOCK`` at a time (``lax.map``) so that the ``[32, 16384, 16384]``
  scores never exist whole, and experts are walked one at a time
  (``lax.scan``);
* **the share of the experts**: ``Hyper.held = (first, count)`` names the
  experts whose weights are here (``w_gate`` [count, d, f] ...), as on one
  rank of an expert-parallel layout. The router is whole (its softmax, its
  top k and the normalising sum run over all its outputs); what an expert
  elsewhere would add is left out, and that partial result goes on to the
  next layer, in the program alike. ``(0, E)`` is the uncut model;
* the multi-token-prediction head the model's card mentions is not in the
  published config's keys and is left out;
* ``cast`` is applied to both operands of every matmul, the router's
  included. The identity gives the reference; the control
  (``reference/gpt2.py:fp8_cast``) puts the reference in the program's
  place one precision step below bf16.

Parameter layout: ``tok_emb`` [V, d], ``norm_f`` [d], ``lm_head`` [V, d],
and the layers as ``blocks``, one dict of leaves stacked on a leading layer
axis, or as ``layers``, a list of per-layer dicts: ``input_ln``,
``post_attn_ln`` [d], ``q_norm``, ``k_norm`` [hd], ``wq`` [d, H hd], ``wk``,
``wv`` [d, Hkv hd], ``wo`` [H hd, d], ``router`` [d, E], ``w_gate``,
``w_up`` [count, d, f], ``w_down`` [count, f, d].
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
WINDOW = "sliding_attention"


class Yarn(NamedTuple):
    """``rope_parameters.full_attention`` of the published config."""
    factor: float = 16.0
    original_max_position: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = 1.2772588722239782


class Hyper(NamedTuple):
    """What the arithmetic needs beyond the weights' shapes."""
    n_head: int
    n_kv_head: int
    top_k: int
    layer_types: Tuple[str, ...]         # one a layer
    window: int
    held: Tuple[int, int]                # (first, count) of the router's E
    rope_theta: float = 500000.0
    yarn: Yarn = Yarn()
    eps: float = 1e-6


def identity(x):
    return x


def _mm(a, b, cast):
    return jnp.matmul(cast(a.astype(F32)), cast(b.astype(F32)),
                      precision=HIGHEST)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


def plain_inv_freq(hd: int, theta: float):
    return 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)


def yarn_inv_freq(hd: int, theta: float, yarn: Yarn):
    """(inv_freq [hd / 2], what cos and sin are multiplied by)."""
    def corr(turns):
        return hd * math.log(yarn.original_max_position
                             / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    plain = plain_inv_freq(hd, theta)
    scale = yarn.attention_factor
    if scale is None:
        scale = 0.1 * math.log(yarn.factor) + 1.0 if yarn.factor > 1 else 1.0
    return plain * (1.0 - ramp) + plain / yarn.factor * ramp, scale


def _rope(x, inv_freq, scale=1.0):
    """[H, T, hd] -> the same, position t rotated by ``t * inv_freq``:
    ``x * cos + rotate_half(x) * sin`` with the frequencies repeated over
    the two halves, as ``transformers`` has it."""
    T, hd = x.shape[-2:]
    angles = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [T, hd]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * (jnp.cos(angles) * scale) + rotated * (jnp.sin(angles) * scale)


def _swiglu(h, w_gate, w_up, w_down, cast):
    return _mm(jax.nn.silu(_mm(h, w_gate, cast)) * _mm(h, w_up, cast),
               w_down, cast)


def _attention(blk, a, hp: Hyper, windowed: bool, cast):
    """One sequence: a [T, d] (the normed input) -> [T, d]."""
    T = a.shape[0]
    H, Hkv = hp.n_head, hp.n_kv_head
    hd = blk["wq"].shape[-1] // H

    def heads(t, n):
        return t.reshape(T, n, hd).transpose(1, 0, 2)

    q = _rms_norm(heads(_mm(a, blk["wq"], cast), H), blk["q_norm"], hp.eps)
    k = _rms_norm(heads(_mm(a, blk["wk"], cast), Hkv), blk["k_norm"], hp.eps)
    v = heads(_mm(a, blk["wv"], cast), Hkv)
    if windowed:
        table = (plain_inv_freq(hd, hp.rope_theta), 1.0)
    else:
        table = yarn_inv_freq(hd, hp.rope_theta, hp.yarn)
    q, k = _rope(q, *table), _rope(k, *table)
    # Query head h reads key/value head h // (H / Hkv).
    k, v = (jnp.repeat(t, H // Hkv, axis=0) for t in (k, v))
    qb = min(QUERY_BLOCK, T)
    if T % qb:
        raise ValueError(f"{T} positions do not split into blocks of {qb}")
    keys = jnp.arange(T)

    @jax.checkpoint
    def query_block(args):
        start, qs = args                                   # qs [H, qb, hd]
        ahead = (start + jnp.arange(qb))[:, None] - keys[None, :]
        seen = ahead >= 0
        if windowed:
            seen = seen & (ahead < hp.window)
        s = jnp.einsum("hqd,hkd->hqk", cast(qs), cast(k),
                       precision=HIGHEST) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", cast(p), cast(v),
                          precision=HIGHEST)

    o = jax.lax.map(query_block, (
        jnp.arange(0, T, qb),
        q.reshape(H, T // qb, qb, hd).transpose(1, 0, 2, 3)))
    # [blocks, H, qb, hd] -> positions in order, heads side by side
    o = o.transpose(0, 2, 1, 3).reshape(T, H * hd)
    return _mm(o, blk["wo"], cast)


def route(blk, h, hp: Hyper, cast):
    """h [T, d] -> (probabilities [T, E], weights [T, k], expert ids
    [T, k])."""
    probs = jax.nn.softmax(_mm(h, blk["router"], cast), axis=-1)
    chosen, experts = jax.lax.top_k(probs, hp.top_k)
    return probs, chosen / jnp.sum(chosen, axis=-1, keepdims=True), experts


def _moe(blk, h, hp: Hyper, cast):
    """One sequence: h [T, d] -> (the held routed experts' part [T, d],
    expert ids [T, k])."""
    E = blk["router"].shape[-1]
    first, count = hp.held
    _, weights, experts = route(blk, h, hp, cast)
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
    return y, experts


def _block(blk, x, hp: Hyper, windowed: bool, cast):
    a = _rms_norm(x, blk["input_ln"], hp.eps)
    x = x + _attention(blk, a, hp, windowed, cast)
    y, experts = _moe(blk, _rms_norm(x, blk["post_attn_ln"], hp.eps), hp,
                      cast)
    return x + y, experts


def layers_of(params) -> list:
    """Per-layer dicts, whichever layout came."""
    if "layers" in params:
        return list(params["layers"])
    stack = params["blocks"]
    n = next(iter(stack.values())).shape[0]
    return [{k: v[i] for k, v in stack.items()} for i in range(n)]


def hidden(params, tokens, hp: Hyper, cast=identity):
    """One sequence: tokens int32 [T] -> (final normalised hidden [T, d],
    the layers' expert ids [layers, T, k])."""
    x = params["tok_emb"][tokens].astype(F32)
    layers = layers_of(params)
    if len(layers) != len(hp.layer_types):
        raise ValueError(f"{len(layers)} layers, {len(hp.layer_types)} "
                         "layer types")
    chosen = []
    for blk, kind in zip(layers, hp.layer_types):
        x, experts = jax.checkpoint(
            lambda b, h, w=(kind == WINDOW): _block(b, h, hp, w, cast))(
                blk, x)
        chosen.append(experts)
    return _rms_norm(x, params["norm_f"], hp.eps), jnp.stack(chosen)


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

    @jax.checkpoint
    def cross_entropy(x, targets):
        lg = _mm(x, params["lm_head"].T, cast)
        gold = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)

    total = 0.0
    for i in range(B):
        x, _ = hidden(params, tokens[i, :-1], hp, cast)
        total = total + weights[i] * cross_entropy(x, tokens[i, 1:])
    return total
