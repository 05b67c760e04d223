"""Plain float32 Sarvam MLA (sarvamai sarvam-105b): forward, loss, gradients
and the router bias's update.

The yardstick the sarvam-105b cell's ``correct`` is decided against.
Straight ``jax.numpy`` following the published ``config.json`` of
sarvamai/sarvam-105b (``model_type: sarvam_mla``) and, for what its keys
name and do not spell out, the DeepSeek-V2 attention they are the keys of
(``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``rope_scaling.type: deepseek_yarn``; no ``q_lora_rank``, so
the query is one projection, as in DeepSeek-V2-Lite); the configuration file
lists under ``assumed`` what neither fixes. Per layer, pre-norm:

    a      = rms(x; input_ln)
    q      = a Wq          -> per head q_nope [T, Dn] beside q_rope [T, Dr]
    [c, r] = a Wkva        -> the latent c [T, R], the rotary key r [T, Dr]
    [k_nope, v] = rms(c; kv_ln) Wkvb   -> per head [T, Dn] beside [T, Dv]
    q_rope, k_rope = rope(q_rope), rope(r)   (the deepseek_yarn table; one
                                              k_rope, every head's)
    k_h    = [k_nope_h, k_rope],  q_h = [q_nope_h, q_rope_h]
    o_h    = softmax_causal(q_h k_h^T (Dn + Dr)^-0.5 m^2) v_h
    x      = x + concat_h(o_h) Wo
    h      = rms(x; post_attn_ln)
    x      = x + Wd (silu(Wg h) * Wu h)                     a dense layer
    x      = x + shared(h) + sum_j w_j expert_{e_j}(h)      an expert layer
             s = sigmoid(h Wr);  e = top_k(s + b);  w = s[e] / sum s[e] * 2.5

then the final RMSNorm, the untied head and the cross entropy; no auxiliary
loss. No kernel, no sort, no layout, no grouped matmul, no import from the
program under test: the rotary key is **joined to every head's keys** and a
head is plain attention over ``Dn + Dr`` channels, by an explicit mask over
explicit scores; **every held expert is applied to every token** and the
result multiplied by the router's weight where the expert is among the
token's top k, by zero elsewhere.

Everything is computed in float32 with ``precision=highest``. Departures
from the published description:

* memory, not arithmetic: weights arrive in the dtype they are trained in
  and are widened where they are used; each block, each block of queries and
  each expert is wrapped in ``jax.checkpoint``, queries are taken
  ``QUERY_BLOCK`` at a time (``lax.map``), the dense layer's MLP and the
  head with its loss ``TOKEN_BLOCK`` tokens at a time, and experts are walked
  one at a time (``lax.scan``);
* **the share of the heads and of the experts**: the weights that come are
  the held heads' (``wq`` [d, Hh (Dn + Dr)], ``wkvb`` [R, Hh (Dn + Dv)],
  ``wo`` [Hh Dv, d]; a head's arithmetic does not depend on which it is) and
  the held experts' (``Hyper.held = (first, count)`` of the router's, as
  ``reference/afmoe.py``). What a head or an expert elsewhere would add is
  left out, and that partial result goes on to the next layer, in the
  program alike. All heads and ``(0, E)`` are the uncut model;
* rotate-half pairs where DeepSeek's code interleaves them (a permutation of
  the rotary columns of ``wq`` and ``wkva``);
* the bias's update is ``reference/afmoe.py``'s (``bias_update``);
* ``cast`` is applied to both operands of every matmul, the router's
  included. The identity gives the reference; the control
  (``reference/gpt2.py:fp8_cast``) puts the reference in the program's
  place one precision step below bf16.

Parameter layout: ``tok_emb`` [V, d], ``norm_f`` [d], ``lm_head`` [V, d], and
the layers as ``dense`` and ``blocks``, each one dict of leaves stacked on a
leading layer axis (the leading dense layers, then the expert layers), or as
``layers``, a list of per-layer dicts. Every layer has ``input_ln``,
``post_attn_ln`` [d], ``kv_ln`` [R], ``wq``, ``wkva`` [d, R + Dr], ``wkvb``,
``wo``; a dense layer ``w_gate``, ``w_up`` [d, I], ``w_down`` [I, d]; an
expert layer ``router`` [d, E], ``router_bias`` [E], ``shared_gate``,
``shared_up`` [d, f], ``shared_down`` [f, d], ``w_gate``, ``w_up`` [count,
d, f], ``w_down`` [count, f, d].
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import bias_update  # noqa: F401 (the same)

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048


class Yarn(NamedTuple):
    """``rope_scaling`` of type ``deepseek_yarn``."""
    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0


class Hyper(NamedTuple):
    """What the arithmetic needs beyond the weights' shapes."""
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    top_k: int
    held: Tuple[int, int]                # (first, count) of the router's E
    route_scale: float = 2.5
    rope_theta: float = 10000.0
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


def mscale(factor: float, scale: float) -> float:
    """DeepSeek's ``yarn_get_mscale``."""
    return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(hp: Hyper) -> float:
    """``q_head_dim ** -0.5`` times ``mscale_all_dim``'s factor squared."""
    m = mscale(hp.yarn.factor, hp.yarn.mscale_all_dim)
    return (hp.qk_nope_head_dim + hp.qk_rope_head_dim) ** -0.5 * m * m


def yarn_inv_freq(dim: int, theta: float, yarn: Yarn):
    """DeepSeek's ``DeepseekV2YarnRotaryEmbedding``: pair ``i`` of ``dim /
    2`` turns by ``theta ** (-2 i / dim)`` a position where it completes more
    than ``beta_fast`` turns over the original context, by ``1 / factor`` of
    that where fewer than ``beta_slow``, by the linear blend between."""
    def correction(turns):
        return dim * math.log(yarn.original_max_position
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(yarn.beta_fast)), 0)
    high = min(math.ceil(correction(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pairs = jnp.arange(dim // 2, dtype=F32)
    extrapolated = 1.0 / theta ** (2 * pairs / dim)
    keep = 1.0 - jnp.clip((pairs - low) / (high - low), 0, 1)
    return extrapolated / yarn.factor * (1 - keep) + extrapolated * keep


def _rope(x, hp: Hyper):
    """[.., T, Dr] -> the same, position t rotated under the table: ``x *
    cos + rotate_half(x) * sin``, cos and sin times ``mscale(factor, mscale)
    / mscale(factor, mscale_all_dim)``."""
    T, dim = x.shape[-2:]
    angles = jnp.arange(T, dtype=F32)[:, None] \
        * yarn_inv_freq(dim, hp.rope_theta, hp.yarn)[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [T, Dr]
    by = mscale(hp.yarn.factor, hp.yarn.mscale) \
        / mscale(hp.yarn.factor, hp.yarn.mscale_all_dim)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * (jnp.cos(angles) * by) + rotated * (jnp.sin(angles) * by)


def _swiglu(h, w_gate, w_up, w_down, cast):
    return _mm(jax.nn.silu(_mm(h, w_gate, cast)) * _mm(h, w_up, cast),
               w_down, cast)


def _attention(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> the held heads' part of
    the attention output [T, d]."""
    T = a.shape[0]
    Dn, Dr, Dv, R = hp.qk_nope_head_dim, hp.qk_rope_head_dim, \
        hp.v_head_dim, hp.kv_lora_rank
    H = blk["wq"].shape[-1] // (Dn + Dr)

    def heads(t):
        return t.reshape(T, H, -1).transpose(1, 0, 2)

    q = heads(_mm(a, blk["wq"], cast))                   # [H, T, Dn + Dr]
    latent = _mm(a, blk["wkva"], cast)
    c = _rms_norm(latent[:, :R], blk["kv_ln"], hp.eps)
    kv = heads(_mm(c, blk["wkvb"], cast))                # [H, T, Dn + Dv]
    k_rope = _rope(latent[:, R:], hp)                    # [T, Dr], all heads'
    q = jnp.concatenate([q[..., :Dn], _rope(q[..., Dn:], hp)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :Dn], jnp.broadcast_to(k_rope, (H, T, Dr))], axis=-1)
    v = kv[..., Dn:]
    scale = softmax_scale(hp)
    qb = min(QUERY_BLOCK, T)
    if T % qb:
        raise ValueError(f"{T} positions do not split into blocks of {qb}")
    keys = jnp.arange(T)

    @jax.checkpoint
    def query_block(args):
        start, qs = args                                 # qs [H, qb, Dn+Dr]
        seen = (start + jnp.arange(qb))[:, None] >= keys[None, :]
        s = jnp.einsum("hqd,hkd->hqk", cast(qs), cast(k),
                       precision=HIGHEST) * scale
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", cast(p), cast(v),
                          precision=HIGHEST)

    o = jax.lax.map(query_block, (
        jnp.arange(0, T, qb),
        q.reshape(H, T // qb, qb, Dn + Dr).transpose(1, 0, 2, 3)))
    # [blocks, H, qb, Dv] -> positions in order, heads side by side
    o = o.transpose(0, 2, 1, 3).reshape(T, H * Dv)
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


def _dense(blk, h, cast):
    """One sequence's dense MLP, ``TOKEN_BLOCK`` tokens at a time."""
    T = h.shape[0]
    tb = min(TOKEN_BLOCK, T)
    if T % tb:
        raise ValueError(f"{T} tokens do not split into blocks of {tb}")
    y = jax.lax.map(jax.checkpoint(lambda hc: _swiglu(
        hc, blk["w_gate"], blk["w_up"], blk["w_down"], cast)),
        h.reshape(T // tb, tb, -1))
    return y.reshape(T, -1)


def _block(blk, x, hp: Hyper, cast):
    x = x + _attention(blk, _rms_norm(x, blk["input_ln"], hp.eps), hp, cast)
    h = _rms_norm(x, blk["post_attn_ln"], hp.eps)
    if "router" in blk:
        y, experts = _moe(blk, h, hp, cast)
    else:
        y, experts = _dense(blk, h, cast), None
    return x + y, experts


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
    x = params["tok_emb"][tokens].astype(F32)
    chosen = []
    for blk in layers_of(params):
        x, experts = jax.checkpoint(
            lambda b, h: _block(b, h, hp, cast))(blk, x)
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


def expert_counts(params, tokens, hp: Hyper, cast=identity):
    """tokens [B, T+1] -> float32 [layers, E]: the assignments each router
    output got over the whole batch, what the bias's update reads."""
    E = layers_of(params)[-1]["router"].shape[-1]
    chosen = jnp.stack([hidden(params, t[:-1], hp, cast)[1] for t in tokens])
    return jnp.sum(jax.nn.one_hot(chosen, E, dtype=F32), axis=(0, 2, 3))
