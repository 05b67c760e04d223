"""Plain float32 AFMoE (arcee-ai Trinity): forward, loss, gradients and
the router bias's update.

The yardstick the Trinity cells' ``correct`` is decided against. Straight
``jax.numpy`` following the published ``config.json`` of
arcee-ai/Trinity-Mini (``model_type: afmoe``) and, for what the config does
not say, ``transformers``' ``modeling_afmoe.py`` as remembered by the author
of the issue that added it (the configuration file lists each such item
under ``assumed``): the embedding scaled by sqrt(hidden) (``mup_enabled``),
four RMSNorms a layer (input, after attention, before and after the MLP),
RMSNorm over each head of q and k, rotary positions (rotate-half) on the
window layers and no position encoding on the global ones, causal softmax
attention (window layers: key j visible to query i iff ``0 <= i - j <
window``), query head h reading key/value head ``h // (H / Hkv)``, the
heads' output multiplied by ``sigmoid(a Wa)``, leading dense SwiGLU layers,
then layers with a router ``s = sigmoid(h Wr)``, ``e = top_k(s + b)``,
``w = s[e] / (sum s[e] + 1e-20) * route_scale``, routed SwiGLU experts and
one shared expert on every token; final RMSNorm, untied head, cross entropy
and no auxiliary loss. No kernel, no sort, no layout, no grouped matmul, no
import from the program under test: **every held expert is applied to every
token** and the result multiplied by the router's weight where the expert
is among the token's top k, by zero elsewhere; attention is an explicit
mask over explicit scores.

Everything is computed in float32 with ``precision=highest``. Departures
from the published description:

* memory, not arithmetic: weights arrive in the dtype they are trained in
  and are widened where they are used; each block, each block of queries
  and each expert is wrapped in ``jax.checkpoint``, queries are taken
  ``QUERY_BLOCK`` at a time (``lax.map``) so that the ``[32, 8192, 8192]``
  scores never exist whole, and experts are walked one at a time
  (``lax.scan``);
* **the share of the experts**: ``Hyper.held = (first, count)`` names the
  experts whose weights are here (``w_gate`` [count, d, f] ...), as on one
  rank of an expert-parallel layout. The router is whole (its scores, its
  top k and the normalising sum run over all its outputs); what an expert
  elsewhere would add is left out, and that partial result goes on to the
  next layer, in the program alike. ``(0, E)`` is the uncut model;
* the bias's update follows torchtitan's trainer (the config's
  ``load_balance_coeff``, ``score_func``, ``route_norm``, ``route_scale``
  are its ``MoEArgs``): once an optimizer step, from the assignments of the
  whole step's batch, ``b += delta - mean(delta)``, ``delta = rate *
  sign(mean(n) - n)``, each layer its own (``bias_update``). On a
  multi-rank run ``n`` is summed over the data-parallel ranks; one rank's
  batch is the whole batch here;
* ``cast`` is applied to both operands of every matmul, the router's
  included. The identity gives the reference; the control
  (``reference/gpt2.py:fp8_cast``) puts the reference in the program's
  place one precision step below bf16.

Parameter layout: ``tok_emb`` [V, d], ``norm_f`` [d], ``lm_head`` [V, d],
and the layers as ``dense`` and ``blocks``, each one dict of leaves stacked
on a leading layer axis (the leading dense layers, then the expert layers),
or as ``layers``, a list of per-layer dicts. Every layer has ``input_ln``,
``post_attn_ln``, ``pre_mlp_ln``, ``post_mlp_ln`` [d], ``q_norm``,
``k_norm`` [hd], ``wq``, ``wa`` [d, H hd], ``wk``, ``wv`` [d, Hkv hd],
``wo`` [H hd, d]; a dense layer ``w_gate``, ``w_up`` [d, I], ``w_down``
[I, d]; an expert layer ``router`` [d, E], ``router_bias`` [E],
``shared_gate``, ``shared_up`` [d, f], ``shared_down`` [f, d], ``w_gate``,
``w_up`` [count, d, f], ``w_down`` [count, f, d].
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
WINDOW = "sliding_attention"


class Hyper(NamedTuple):
    """What the arithmetic needs beyond the weights' shapes."""
    n_head: int
    n_kv_head: int
    top_k: int
    layer_types: Tuple[str, ...]         # one a layer, dense ones included
    window: int
    held: Tuple[int, int]                # (first, count) of the router's E
    route_scale: float = 2.826
    rope_theta: float = 10000.0
    eps: float = 1e-5


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
    if windowed:                 # a global layer has no position encoding
        q, k = _rope(q, hp.rope_theta), _rope(k, hp.rope_theta)
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
    o = o * jax.nn.sigmoid(_mm(a, blk["wa"], cast))        # gated attention
    return _mm(o, blk["wo"], cast)


def route(blk, h, hp: Hyper, cast):
    """h [T, d] -> (scores [T, E], weights [T, k], expert ids [T, k])."""
    scores = jax.nn.sigmoid(_mm(h, blk["router"], cast))
    # The bias moves the choice only; no gradient reaches it.
    _, experts = jax.lax.top_k(scores + blk["router_bias"].astype(F32),
                               hp.top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) \
        * hp.route_scale
    return scores, weights, experts


def _moe(blk, h, hp: Hyper, cast):
    """One sequence: h [T, d] -> (shared expert's output plus the held
    routed experts' part [T, d], expert ids [T, k])."""
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
    shared = _swiglu(h, blk["shared_gate"], blk["shared_up"],
                     blk["shared_down"], cast)
    return shared + y, experts


def _block(blk, x, hp: Hyper, windowed: bool, cast):
    a = _rms_norm(x, blk["input_ln"], hp.eps)
    x = x + _rms_norm(_attention(blk, a, hp, windowed, cast),
                      blk["post_attn_ln"], hp.eps)
    h = _rms_norm(x, blk["pre_mlp_ln"], hp.eps)
    if "router" in blk:
        y, experts = _moe(blk, h, hp, cast)
    else:
        y, experts = _swiglu(h, blk["w_gate"], blk["w_up"], blk["w_down"],
                             cast), None
    return x + _rms_norm(y, blk["post_mlp_ln"], hp.eps), experts


def layers_of(params) -> list:
    """Per-layer dicts, dense layers first, whichever layout came."""
    if "layers" in params:
        return list(params["layers"])
    out = []
    for name in ("dense", "blocks"):
        stack = params.get(name)
        if stack:
            n = next(iter(stack.values())).shape[0]
            out += [{k: v[i] for k, v in stack.items()} for i in range(n)]
    return out


def hidden(params, tokens, hp: Hyper, cast=identity):
    """One sequence: tokens int32 [T] -> (final normalised hidden [T, d],
    the expert layers' expert ids [layers, T, k])."""
    d = params["tok_emb"].shape[-1]
    x = params["tok_emb"][tokens].astype(F32) * math.sqrt(d)   # mup_enabled
    layers = layers_of(params)
    if len(layers) != len(hp.layer_types):
        raise ValueError(f"{len(layers)} layers, {len(hp.layer_types)} "
                         "layer types")
    chosen = []
    for blk, kind in zip(layers, hp.layer_types):
        x, experts = jax.checkpoint(
            lambda b, h, w=(kind == WINDOW): _block(b, h, hp, w, cast))(
                blk, x)
        if experts is not None:
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


def expert_counts(params, tokens, hp: Hyper, cast=identity):
    """tokens [B, T+1] -> float32 [layers, E]: the assignments each router
    output got over the whole batch, what the bias's update reads."""
    E = layers_of(params)[-1]["router"].shape[-1]
    chosen = jnp.stack([hidden(params, t[:-1], hp, cast)[1] for t in tokens])
    return jnp.sum(jax.nn.one_hot(chosen, E, dtype=F32), axis=(0, 2, 3))


def bias_update(bias, counts, rate: float):
    """One optimizer step's update of a selection bias [.., E] from the
    step's ``counts`` [.., E] (torchtitan's form; the module docstring)."""
    delta = rate * jnp.sign(jnp.mean(counts, axis=-1, keepdims=True)
                            - counts)
    return bias + delta - jnp.mean(delta, axis=-1, keepdims=True)
