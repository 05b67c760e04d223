"""Plain float32 OLMoE: forward, the three loss terms and gradients.

The yardstick the OLMoE cells' ``correct`` is decided against. Straight
``jax.numpy`` following the published model (Muennighoff et al. 2024,
arXiv:2409.02060; ``transformers`` ``modeling_olmoe.py`` for
allenai/OLMoE-1B-7B): token embedding, pre-RMSNorm blocks without biases,
RMSNorm over the whole projected q and k before the split into heads,
rotary positions (rotate-half), causal softmax attention, a router whose
softmax runs over all experts and whose top-k weights are used as they are
(``norm_topk_prob`` false), SwiGLU experts, final RMSNorm, untied head. No
kernel, no sort, no grouped matmul, no import from the program under test:
**every expert is applied to every token** and the result multiplied by the
router's weight where the expert is among the token's top k, by zero
elsewhere.

Everything is computed in float32 with ``precision=highest``. Departures
from the published description:

* memory, not arithmetic: weights arrive in the dtype they are trained in
  and are widened where they are used; each block, each head's attention
  and each expert is wrapped in ``jax.checkpoint`` and heads and experts
  are walked one at a time (``lax.map`` / ``lax.scan``), so 4096 positions
  and 64 experts fit beside the weights;
* the load-balancing loss ``E . sum_e f_e . pbar_e`` (paper, eq. 3-4) and
  the router z-loss ``mean_t logsumexp(logits_t)^2`` are taken over each
  sequence and averaged over the batch, where OLMoE's trainer takes them
  over one rank's micro batch. A batch's loss is then the weighted sum of
  its sequences' losses, which ``weights`` needs and which makes a step
  independent of how gradient accumulation splits the batch. The program
  does the same. ``f_e`` is the share of a sequence's ``T x k`` assignments
  that went to expert e (``transformers`` sums the k slots instead of
  averaging them: k times this) and carries no gradient;
* ``cast`` is applied to both operands of every matmul, the router's
  included. The identity gives the reference; the control
  (``reference/gpt2.py:fp8_cast``) puts the reference in the program's
  place one precision step below bf16.

Parameter layout: ``tok_emb`` [V, d], ``norm_f`` [d], ``lm_head`` [V, d]
and ``blocks``: a list of per-layer dicts, or one dict of the same leaves
stacked on a leading layer axis (``attn_norm``, ``wq``, ``wk``, ``wv``,
``wo`` [d, d], ``q_norm``, ``k_norm`` [d], ``ffn_norm``, ``router`` [d, E],
``w_gate``, ``w_up`` [E, d, f], ``w_down`` [E, f, d]).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


class Hyper(NamedTuple):
    """What the arithmetic needs beyond the weights' shapes."""
    n_head: int
    top_k: int
    rope_theta: float = 10000.0
    eps: float = 1e-5
    lb_coef: float = 0.01
    z_coef: float = 0.001


def identity(x):
    return x


def _mm(a, b, cast):
    return jnp.matmul(cast(a.astype(F32)), cast(b.astype(F32)),
                      precision=HIGHEST)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


def _rope(x, theta):
    """[H, T, hd] -> the same, position t rotated by t * theta^(-2i/hd):
    ``x * cos + rotate_half(x) * sin`` with the frequencies repeated over
    the two halves, as ``transformers`` has it."""
    T, hd = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    angles = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [T, hd]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def _attention(blk, h, hp: Hyper, cast):
    """One sequence: h [T, d] -> [T, d]."""
    T, d = h.shape
    hd = d // hp.n_head

    def heads(t):
        return t.reshape(T, hp.n_head, hd).transpose(1, 0, 2)

    q = _rope(heads(_rms_norm(_mm(h, blk["wq"], cast), blk["q_norm"],
                              hp.eps)), hp.rope_theta)
    k = _rope(heads(_rms_norm(_mm(h, blk["wk"], cast), blk["k_norm"],
                              hp.eps)), hp.rope_theta)
    v = heads(_mm(h, blk["wv"], cast))
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv
        s = jnp.matmul(cast(qh), cast(kh).T, precision=HIGHEST) \
            / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.matmul(cast(p), cast(vh), precision=HIGHEST)

    o = jax.lax.map(one_head, (q, k, v))                          # [H, T, hd]
    return _mm(o.transpose(1, 0, 2).reshape(T, d), blk["wo"], cast)


def route(blk, h, hp: Hyper, cast):
    """h [T, d] -> (logits [T, E], probabilities [T, E], top-k weights
    [T, k], expert ids [T, k])."""
    logits = _mm(h, blk["router"], cast)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, hp.top_k)
    return logits, probs, weights, experts


def _moe(blk, h, hp: Hyper, cast):
    """One sequence: h [T, d] -> (output [T, d], LB, ZL, expert ids
    [T, k])."""
    E = blk["router"].shape[-1]
    logits, probs, weights, experts = route(blk, h, hp, cast)
    chosen = jax.nn.one_hot(experts, E, dtype=F32)                # [T, k, E]
    gate = jnp.sum(chosen * weights[..., None], axis=1)           # [T, E]
    share = jax.lax.stop_gradient(jnp.mean(chosen, axis=(0, 1)))  # f_e
    lb = E * jnp.sum(share * jnp.mean(probs, axis=0))
    zl = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    @jax.checkpoint
    def expert(w_gate, w_up, w_down, g):
        act = jax.nn.silu(_mm(h, w_gate, cast)) * _mm(h, w_up, cast)
        return _mm(act, w_down, cast) * g[:, None]

    def step(y, e):
        w_gate, w_up, w_down, g = e
        return y + expert(w_gate, w_up, w_down, g), None

    y, _ = jax.lax.scan(step, jnp.zeros_like(h),
                        (blk["w_gate"], blk["w_up"], blk["w_down"], gate.T))
    return y, lb, zl, experts


def _block(blk, x, hp: Hyper, cast):
    x = x + _attention(blk, _rms_norm(x, blk["attn_norm"], hp.eps), hp, cast)
    y, *aux = _moe(blk, _rms_norm(x, blk["ffn_norm"], hp.eps), hp, cast)
    return x + y, tuple(aux)


def hidden(params, tokens, hp: Hyper, cast=identity):
    """One sequence: tokens int32 [T] -> (final normalised hidden [T, d],
    LB and ZL averaged over layers, every layer's expert ids [L, T, k])."""
    x = params["tok_emb"][tokens].astype(F32)
    block = jax.checkpoint(lambda h, blk: _block(blk, h, hp, cast))
    if isinstance(params["blocks"], dict):      # one [L, ...] array a leaf
        x, (lbs, zls, experts) = jax.lax.scan(block, x, params["blocks"])
    else:
        aux = []
        for blk in params["blocks"]:
            x, layer_aux = block(x, blk)
            aux.append(layer_aux)
        lbs, zls, experts = (jnp.stack(a) for a in zip(*aux))
    return (_rms_norm(x, params["norm_f"], hp.eps), jnp.mean(lbs),
            jnp.mean(zls), experts)


def logits(params, tokens, hp: Hyper, cast=identity):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    return jnp.stack([_mm(hidden(params, t, hp, cast)[0],
                          params["lm_head"].T, cast) for t in tokens])


def loss_terms(params, tokens, hp: Hyper, cast=identity, weights=None):
    """(cross entropy, load-balancing loss, router z-loss) of tokens
    [B, T+1]: each the mean over the batch of the sequence's own value, or
    with ``weights`` [B] the sum weighted by them (a batch that repeats
    sequences is then computed from the distinct ones)."""
    B = tokens.shape[0]
    if weights is None:
        weights = jnp.full((B,), 1.0 / B, F32)

    @jax.checkpoint
    def cross_entropy(x, targets):
        lg = _mm(x, params["lm_head"].T, cast)
        gold = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)

    ce = lb = zl = 0.0
    for i in range(B):
        x, lb_i, zl_i, _ = hidden(params, tokens[i, :-1], hp, cast)
        ce = ce + weights[i] * cross_entropy(x, tokens[i, 1:])
        lb = lb + weights[i] * lb_i
        zl = zl + weights[i] * zl_i
    return ce, lb, zl


def loss(params, tokens, hp: Hyper, cast=identity, weights=None):
    """The training loss: CE + lb_coef x LB + z_coef x ZL."""
    ce, lb, zl = loss_terms(params, tokens, hp, cast, weights)
    return ce + hp.lb_coef * lb + hp.z_coef * zl
