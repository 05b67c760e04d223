"""Plain float32 Kimi Linear (moonshotai Kimi-Linear-48B-A3B): forward, loss,
gradients and the router bias's update.

The yardstick the kimi-linear-48b-a3b cell's ``correct`` is decided against.
Straight ``jax.numpy`` following the published ``config.json`` of
moonshotai/Kimi-Linear-48B-A3B-Instruct (``model_type: kimi_linear``) and,
for what its keys name and do not spell out, the publication they are the
keys of (Kimi Linear, arXiv:2510.26692: Kimi Delta Attention, the gated
delta rule with a decay for every key channel; DeepSeek-V2's latent
attention for ``kv_lora_rank`` and the head widths); the configuration file
lists under ``assumed`` what neither fixes. Per layer, pre-norm, ``a =
rms(x; input_ln)``:

a KDA layer (``H`` heads of ``K = V`` channels)

    q~, k~, v = silu(conv(a Wq)), silu(conv(a Wk)), silu(conv(a Wv))
                        depth-wise causal conv along the sequence, taps
                        [taps, H K], no bias, zeros before the sequence
    q_h    = q~_h / sqrt(|q~_h|^2 + 1e-6) * K^-0.5
    k_h    = k~_h / sqrt(|k~_h|^2 + 1e-6)
    g_h    = -exp(A_h) * softplus(((a Wfa) Wfb)_h + dt_h)
    beta_h = sigmoid(a Wb)_h
    S_t    = Diag(exp(g_t)) S_{t-1};  r = S_t^T k_t
    S_t    = S_t + beta_t k_t (v_t - r)^T;  o_t = S_t^T q_t     S_0 = 0
    y_h    = rms(o_h; o_norm) * sigmoid(((a Wga) Wgb)_h)
    x      = x + concat_h(y_h) Wo

a latent-attention layer, with no position anywhere

    q      = a Wq          -> per head q_nope [T, Dn] beside q_rope [T, Dr]
    [c, r] = a Wkva        -> the latent c [T, R] and one key r [T, Dr]
    [k_nope, v] = rms(c; kv_ln) Wkvb
    o_h    = softmax_causal([q_nope_h, q_rope_h] [k_nope_h, r]^T
                            (Dn + Dr)^-0.5) v_h
    x      = x + concat_h(o_h) Wo

then in every layer

    h      = rms(x; post_attn_ln)
    x      = x + Wd (silu(Wg h) * Wu h)                     a dense layer
    x      = x + shared(h) + sum_j w_j expert_{e_j}(h)      an expert layer
             s = sigmoid(h Wr);  e = top_k(s + b);  w = s[e] / sum s[e] * f

the final RMSNorm, the untied head and the cross entropy; no auxiliary loss.
No kernel, no chunked form, no sort, no layout, no grouped matmul, no import
from the program under test: **the delta rule is the recurrence above a
token at a time** (one ``lax.scan`` step a token, all heads at once), the
conv a sum of shifted copies, a head of the latent layer plain attention
over ``Dn + Dr`` channels by an explicit mask over explicit scores, and
**every held expert is applied to every token**, its result multiplied by
the router's weight where the expert is among the token's top k, by zero
elsewhere.

Everything is computed in float32 with ``precision=highest``. Departures
from the published description:

* memory, not arithmetic: weights arrive in the dtype they are trained in
  and are widened where they are used; each block, each block of queries and
  each expert is wrapped in ``jax.checkpoint``, queries are taken
  ``QUERY_BLOCK`` at a time (``lax.map``), the dense layer's MLP and the
  head with its loss ``TOKEN_BLOCK`` tokens at a time, experts are walked
  one at a time (``lax.scan``), and the recurrence's scan is checkpointed
  ``SCAN_BLOCK`` tokens at a time;
* **the share of the experts**: the weights that come are the held experts'
  (``Hyper.held = (first, count)`` of the router's, as
  ``reference/afmoe.py``). What an expert elsewhere would add is left out,
  and that partial result goes on to the next layer, in the program alike.
  ``(0, E)`` is the uncut layer;
* the bias's update is ``reference/afmoe.py``'s (``bias_update``);
* ``cast`` is applied to both operands of every matmul, the router's and
  the recurrence's products with the state included, and to the conv's
  operands. The identity gives the reference; the control
  (``reference/gpt2.py:fp8_cast``) puts the reference in the program's
  place one precision step below bf16.

Parameter layout: ``tok_emb`` [V, d], ``norm_f`` [d], ``lm_head`` [V, d], and
the layers as ``run0``, ``run1``, ...: a run of consecutive layers of one
kind one dict of leaves stacked on a leading layer axis, in the model's
order; or as ``layers``, a list of per-layer dicts. A layer is a KDA layer
where it has ``conv_q`` and a latent one where it has ``wkva``; an expert
layer where it has ``router``.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import bias_update  # noqa: F401 (the same)

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
SCAN_BLOCK = 128


class Hyper(NamedTuple):
    """What the arithmetic needs beyond the weights' shapes."""
    kda_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    top_k: int
    held: Tuple[int, int]                # (first, count) of the router's E
    route_scale: float = 2.446
    eps: float = 1e-5


def identity(x):
    return x


def _mm(a, b, cast):
    return jnp.matmul(cast(a.astype(F32)), cast(b.astype(F32)),
                      precision=HIGHEST)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


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
    """The gated delta rule a token at a time: q, k, g [T, H, K], v [T, H,
    V], beta [T, H] -> o [T, H, V], float32. ``S`` [H, K, V] starts at 0."""
    T, H, K = q.shape
    V = v.shape[-1]

    def token(S, x):
        q, k, v, g, b = x
        S = S * jnp.exp(g)[..., None]
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
    _, o = jax.lax.scan(tokens, jnp.zeros((H, K, V), F32), xs)
    return o.reshape(T, H, V)


def log_decays(blk, a, hp: Hyper, cast=identity):
    """a [T, d] -> [T, H, K], at most 0."""
    H = hp.kda_heads
    raw = _mm(_mm(a, blk["wfa"], cast), blk["wfb"], cast) \
        + blk["dt_bias"].astype(F32)
    return -jnp.exp(blk["A_log"].astype(F32))[:, None] \
        * jax.nn.softplus(raw).reshape(a.shape[0], H, -1)


def _kda(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> the KDA mixer's output
    [T, d]."""
    T, H = a.shape[0], hp.kda_heads

    def heads(t):
        return t.reshape(T, H, -1)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q, k, v = (heads(conv_silu(_mm(a, blk[w], cast), blk[c], cast))
               for w, c in (("wq", "conv_q"), ("wk", "conv_k"),
                            ("wv", "conv_v")))
    q, k = l2(q) * q.shape[-1] ** -0.5, l2(k)
    beta = jax.nn.sigmoid(_mm(a, blk["wb"], cast))
    o = recurrence(q, k, v, log_decays(blk, a, hp, cast), beta, cast)
    gate = heads(_mm(_mm(a, blk["wga"], cast), blk["wgb"], cast))
    y = _rms_norm(o, blk["o_norm"], hp.eps) * jax.nn.sigmoid(gate)
    return _mm(y.reshape(T, -1), blk["wo"], cast)


def _attention(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> the latent attention's
    output [T, d]; no position anywhere."""
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
    k = jnp.concatenate(
        [kv[..., :Dn], jnp.broadcast_to(latent[:, R:], (H, T, Dr))], axis=-1)
    v = kv[..., Dn:]
    scale = (Dn + Dr) ** -0.5
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
    mixer = _kda if "conv_q" in blk else _attention
    x = x + mixer(blk, _rms_norm(x, blk["input_ln"], hp.eps), hp, cast)
    h = _rms_norm(x, blk["post_attn_ln"], hp.eps)
    if "router" in blk:
        y, experts = _moe(blk, h, hp, cast)
    else:
        y, experts = _dense(blk, h, cast), None
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
