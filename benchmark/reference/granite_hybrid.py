"""Plain float32 Granite 4.0-H (ibm-granite ``granite-4.0-h-small``,
``model_type: granitemoehybrid``): forward, loss and gradients.

The yardstick the granite-4.0-h-small cell's ``correct`` is decided against.
Straight ``jax.numpy`` following the ``transformers`` module of the model
(``models/granitemoehybrid/modeling_granitemoehybrid.py``, 4.57.6; the class
each part follows is named beside it) at the published ``config.json`` of
ibm-granite/granite-4.0-h-small; the configuration file lists under
``assumed`` what neither fixes. **Every layer is a mixer and an expert part**
(``GraniteMoeHybridDecoderLayer``), the mixer's kind its entry of
``layer_types``:

    x0 = embedding_multiplier * tok_emb[tokens]          (...Model.forward)
    x  = x + residual_multiplier * Mixer(rms(x; input_ln))
    h  = rms(x; post_attn_ln)
    x  = x + residual_multiplier * (Experts(h) + SharedMLP(h))
    logits = rms(x; norm_f) tok_emb^T / logits_scaling   (...ForCausalLM)

``mamba`` (``...MambaLayer.torch_forward``; ``H`` heads of ``P`` channels, ONE
group of ``N`` states), with ``a = rms(x; input_ln)``:

    z, xBC, dt = a Wz, a Wxbc, a Wdt        in_proj's columns, in its order
    [u | B | C] = silu(conv(xBC) + b)       one depth-wise causal conv over the
                        joined H P + 2 N channels, rows before the sequence 0
    Delta_h = softplus(dt_h + dt_bias_h)    time_step_limit (0, inf): no clamp
    A_h = -exp(A_log_h)
    S_t = exp(Delta_t A) S_{t-1} + Delta_t u_t B_t^T        S [P, N], S_0 = 0
    y_t = S_t C_t + D_h u_t
    r   = y * silu(z)                       (...RMSNormGated: the gate first,
    out = (r / rms(r) * g) Wout              ONE mean square over r's channels)

``attention`` (``...Attention``; ``Hq`` query heads over ``Hkv`` key/value
heads, ``position_embedding_type: nope``, no QK-norm):

    q, k, v = a Wq, a Wk, a Wv
    o_h = softmax_causal(q_h k_j^T * attention_multiplier) v_j
    out = concat_h(o_h) Wo                       j = h // (Hq / Hkv)

``Experts`` (``...TopKGating``, ``...MoE``) and ``SharedMLP`` (``...MLP``):

    l = h Wr (float32);  e = top_k(l);  w = softmax(l[e])   over the k alone
    expert(h) = (silu(h Wg) * (h Wu)) Wd                     [g | u] = h W_in
    Experts(h) = sum_j w_j expert_{e_j}(h)     SharedMLP(h) = the same form,
                                               every token, unweighted

and the next-token cross entropy alone (``output_router_logits`` false: no
auxiliary loss). No kernel, no chunked form, no sort, no layout, no grouped
matmul, no import from the program under test: the state-space rule is the
recurrence a token at a time (``reference/nemotron_h.py:recurrence``, one
``lax.scan`` step a token on a float32 ``[H, P, N]`` state), the conv a sum
of shifted copies, attention an explicit mask over explicit scores in blocks
of queries, and **every held expert is applied to every token**, its result
multiplied by the router's weight where the expert is among the token's top
k, by zero elsewhere.

Everything is computed in float32 with ``precision=highest``. Departures:

* memory, not arithmetic: as ``reference/nemotron_h.py`` (checkpointed
  layers, query blocks, experts one at a time, the head in token blocks);
* **the share of a layer**: the weights that come are one rank's of the
  ranks that share each layer: its Mamba-2 heads (their columns of ``Wz``,
  ``Wdt`` and of ``Wxbc``'s ``u``, ``B`` and ``C`` whole, their rows of
  ``Wout``), its query and key/value heads, its experts (``Hyper.held =
  (first, count)`` of the router's) and its rows of the embedding, beside
  the shared MLP whole (every rank computes it alike), and every divided
  sub-layer's result is that rank's partial sum, which goes on to the next
  layer, in the program alike. What the other ranks would add is left out. **The gated norm's mean square is then over the
  rank's own channels**, where the model's is over all ranks' (the one
  departure of the share that is not linear). Whole weights are the uncut
  model;
* ``Wz``, ``Wxbc``, ``Wdt`` are ``in_proj``'s columns cut where the module
  splits its result; ``Wg``, ``Wu`` the two halves ``input_linear``'s result
  is chunked into, of the experts and of the shared MLP;
* the module rounds the router's gates to the activations' dtype
  (``type_as``); in float32 that is no step;
* ``cast`` is applied to both operands of every matmul, the router's and
  the recurrence's products with the state included, and to the conv's
  operands. The identity gives the reference; the control
  (``reference/gpt2.py:fp8_cast``) puts the reference in the program's
  place one precision step below bf16.

Parameter layout: ``tok_emb`` [V, d] (also the head), ``norm_f`` [d], and the
layers as ``layers``, a list of per-layer dicts, or stacked a run of one
mixer, ``run{r}`` beside ``vec{r}`` and ``out{r}`` (a run's leaves in up to
three dicts, stacked on a leading axis), in the model's order.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.nemotron_h import (
    F32,
    HIGHEST,
    QUERY_BLOCK,
    TOKEN_BLOCK,
    _mm,
    _rms,
    conv_silu,
    identity,
    recurrence,
)

MAMBA, ATTN = "mamba", "attention"
GROUPS = ("run", "vec", "out")       # a stacked run's dicts, side by side


class Hyper(NamedTuple):
    """What the arithmetic needs beyond the weights' shapes."""
    heads: int                           # Mamba-2's, as many as the leaves hold
    n_head: int                          # the attention layers', likewise
    n_kv_head: int
    top_k: int
    held: Tuple[int, int]                # (first, count) of the router's E
    kinds: Tuple[str, ...]               # each layer's mixer, in order
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    eps: float = 1e-5


def _mamba(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> the Mamba-2 mixer's
    output [T, d]."""
    T, H = a.shape[0], hp.heads
    inner = blk["w_z"].shape[-1]
    P = inner // H
    N = (blk["w_xbc"].shape[-1] - inner) // 2
    xbc = conv_silu(_mm(a, blk["w_xbc"], cast), blk["conv"], blk["conv_b"],
                    cast)
    delta = jax.nn.softplus(_mm(a, blk["w_dt"], cast)
                            + blk["dt_bias"].astype(F32))
    y = recurrence(xbc[:, :inner].reshape(T, H, P),
                   xbc[:, inner:inner + N].reshape(T, 1, N),
                   xbc[:, inner + N:].reshape(T, 1, N), delta,
                   -jnp.exp(blk["A_log"].astype(F32)), blk["D"], cast)
    r = y.reshape(T, inner) * jax.nn.silu(_mm(a, blk["w_z"], cast))
    r = r * jax.lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True) + hp.eps)
    return _mm(r * blk["ssm_norm"].astype(F32), blk["w_out"], cast)


def _attention(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> the attention's output
    [T, d]."""
    T = a.shape[0]
    H, Hkv = hp.n_head, hp.n_kv_head
    hd = blk["wk"].shape[-1] // Hkv

    def heads(t, n):
        return t.reshape(T, n, -1).transpose(1, 0, 2)

    q = heads(_mm(a, blk["wq"], cast), H)
    k = heads(_mm(a, blk["wk"], cast), Hkv)
    v = heads(_mm(a, blk["wv"], cast), Hkv)
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
                       precision=HIGHEST) * hp.attention_multiplier
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", cast(p), cast(v),
                          precision=HIGHEST)

    o = jax.lax.map(query_block, (
        jnp.arange(0, T, qb),
        q.reshape(H, T // qb, qb, hd).transpose(1, 0, 2, 3)))
    return _mm(o.transpose(0, 2, 1, 3).reshape(T, H * hd), blk["wo"], cast)


def route(blk, h, hp: Hyper, cast=identity):
    """h [T, d] -> (weights [T, k], expert ids [T, k]): the k highest
    logits, the softmax over those k alone."""
    top, experts = jax.lax.top_k(_mm(h, blk["router"], cast), hp.top_k)
    return jax.nn.softmax(top, axis=-1), experts


def _swiglu(h, w_gate, w_up, w_down, cast):
    return _mm(jax.nn.silu(_mm(h, w_gate, cast)) * _mm(h, w_up, cast),
               w_down, cast)


def _moe(blk, h, hp: Hyper, cast):
    """One sequence: h [T, d] -> (the held routed experts' part plus the
    shared MLP's [T, d], expert ids [T, k])."""
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

    y, _ = jax.lax.scan(step, jnp.zeros_like(h), (
        blk["w_gate"], blk["w_up"], blk["w_down"], gate.T))
    return y + _swiglu(h, blk["shared_gate"], blk["shared_up"],
                       blk["shared_down"], cast), experts


def mixer(blk, a, kind: str, hp: Hyper, cast=identity):
    """A layer's mixer alone: a [T, d] (the normed input) -> [T, d]."""
    return (_attention if kind == ATTN else _mamba)(blk, a, hp, cast)


def after_mixer(blk, x, kind: str, hp: Hyper, cast=identity):
    """The first half of a layer whose mixer is ``kind``: the residual
    stream after the mixer's add."""
    return x + hp.residual_multiplier * mixer(
        blk, _rms(x, blk["input_ln"], hp.eps), kind, hp, cast)


def experts_input(blk, x, hp: Hyper):
    """What a layer's router and experts read of the residual stream after
    the mixer."""
    return _rms(x, blk["post_attn_ln"], hp.eps)


def after_experts(blk, x, hp: Hyper, cast=identity):
    """The second half of a layer: (the residual stream after the expert
    part's add, the layer's expert ids)."""
    y, experts = _moe(blk, experts_input(blk, x, hp), hp, cast)
    return x + hp.residual_multiplier * y, experts


def layer(blk, x, kind: str, hp: Hyper, cast=identity):
    """One layer whose mixer is ``kind``: (the residual stream after it, its
    expert ids)."""
    return after_experts(blk, after_mixer(blk, x, kind, hp, cast), hp, cast)


def layers_of(params, hp: Hyper) -> list:
    """(the layer's leaves in one dict, its mixer's kind) of every layer in
    the model's order, whichever layout came."""
    if "layers" in params:
        return list(zip(params["layers"], hp.kinds))
    out, r = [], 0
    while f"run{r}" in params:
        stack = {k: v for g in GROUPS
                 for k, v in params.get(f"{g}{r}", {}).items()}
        n = next(iter(stack.values())).shape[0]
        out += [{k: v[i] for k, v in stack.items()} for i in range(n)]
        r += 1
    return list(zip(out, hp.kinds))


def hidden(params, tokens, hp: Hyper, cast=identity):
    """One sequence: tokens int32 [T] -> (the final normalised hidden [T, d],
    the layers' expert ids [L, T, k])."""
    x = hp.embedding_multiplier * params["tok_emb"][tokens].astype(F32)
    chosen = []
    for blk, kind in layers_of(params, hp):
        x, experts = jax.checkpoint(
            lambda b, h, kind=kind: layer(b, h, kind, hp, cast))(blk, x)
        chosen.append(experts)
    return _rms(x, params["norm_f"], hp.eps), jnp.stack(chosen)


def logits(params, tokens, hp: Hyper, cast=identity):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    return jnp.stack([_mm(hidden(params, t, hp, cast)[0],
                          params["tok_emb"].T, cast) / hp.logits_scaling
                      for t in tokens])


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
            lg = _mm(xc, params["tok_emb"].T, cast) / hp.logits_scaling
            gold = jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

        return jnp.sum(jax.lax.map(part, (
            x.reshape(T // tb, tb, -1), targets.reshape(T // tb, tb)))) / T

    total = 0.0
    for i in range(B):
        x, _ = hidden(params, tokens[i, :-1], hp, cast)
        total = total + weights[i] * cross_entropy(x, tokens[i, 1:])
    return total
