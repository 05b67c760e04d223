"""Plain float32 MiniCPM-SALA (openbmb ``model_type: minicpm_sala``):
forward, loss and gradients.

The yardstick the MiniCPM-SALA cells' ``correct`` is decided against.
Straight ``jax.numpy`` following the published ``config.json`` of
openbmb/MiniCPM-SALA and, for what the config does not carry, the family's
conventions (the MiniCPM modelling code's three scalings, MiniCPM4's
``sparse_config`` for InfLLM-v2's geometry, Lightning Attention-2's decay
slopes with MiniMax-01's per-layer factor; the configuration file lists each
under ``assumed``).

    h = scale_emb * E[tokens]
    h = h + r * mixer(rms(h; input_ln));  a = rms(h; ff_ln)
    h = h + r * W_down (silu(W_gate a) * (W_up a))
                                 r = scale_depth / sqrt(published layers)
    logits = (rms(h; norm_f) / (hidden_size / dim_model_base)) W_head^T

    lightning-attn:  q, k, v = a Wq, a Wk, a Wv (H heads of D); RMSNorm over
        each head of q and k; rotary on q and k (rotate-half, the whole
        head); q / sqrt(D);  S_t = lam_h S_{t-1} + k_t^T v_t,  o_t = q_t S_t
        (no softmax);  (sigmoid(a Wg) * rms(o; o_norm, all H D channels)) Wo
        lam_h = exp(-2^(-8 (h+1) / H) * (1 - l / (published layers - 1)
        + 1e-5)) for head h = 0.. of published layer l
    minicpm4:  q (H heads), k, v (Hkv heads, query head h reading h // (H /
        Hkv)); RMSNorm over each head of q and k; no positions; softmax at
        D ** -0.5 over the keys s <= t that are visible: all of them up to
        ``dense_len`` positions, past it those in t's chosen blocks
        (:func:`chosen_blocks`);  (sigmoid(a Wg) * o) Wo

**The recurrence is a plain ``lax.scan`` over tokens**, one step a token,
the state ``[H, D, D]`` carried from each to the next by multiply and add:
no chunked form, no matmul, no kernel. **The sparse layer is an explicit
boolean mask** over explicit scores, built from the chosen sets. Nothing is
imported from the program under test.

Everything is computed in float32 with ``precision=highest``. Departures
from a textbook implementation, all about memory and none about arithmetic:

* weights arrive in the dtype they are trained in and are widened where they
  are used; one sequence at a time; each block, each block of queries, each
  block of the MLP's and the loss's rows is wrapped in ``jax.checkpoint``;
  queries are taken ``QUERY_BLOCK``, MLP rows ``ROW_BLOCK`` and logits
  ``LOSS_BLOCK`` at a time;
* the time loop is cut into stretches of ``TIME_BLOCK`` steps, each under
  ``jax.checkpoint`` (the same steps in the same order);
* ``cast`` is applied to both operands of every matmul (projections, MLP,
  head, the sparse layer's three score and value products). The identity
  gives the reference; the control (``reference/gpt2.py:fp8_cast``) puts the
  reference in the program's place one precision step below bf16. The
  recurrence has no matmul and is not cast.

Parameter layout: ``tok_emb``, ``lm_head`` [V, d], ``norm_f`` [d], and the
layers a run of consecutive layers of one kind, in the model's order, every
leaf stacked over the run's layers and in one of the run's two groups
(:func:`split_groups`): ``run0``, ``run1``, ... the matrices, ``vec0``, ...
the norm gains; or as ``layers``, a list of per-layer dicts. Every layer:
``input_ln``, ``ff_ln`` [d], ``w_gate``, ``w_up`` [d, f], ``w_down`` [f, d],
``wq``, ``wg`` [d, H D], ``wk``, ``wv`` [d, Hkv D] (a lightning layer's
``Hkv`` is its ``H``), ``wo`` [H D, d], ``q_norm``, ``k_norm`` [D]; a
lightning layer also ``o_norm`` [H D].
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128
TIME_BLOCK = 256
ROW_BLOCK = 2048
LOSS_BLOCK = 1024
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


class Hyper(NamedTuple):
    """What the arithmetic needs beyond the weights' shapes."""
    n_head: int
    n_kv_head: int
    lightning_heads: int
    mixer_types: Tuple[str, ...]
    first_layer: int = 0
    published_layers: int = 32
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    eps: float = 1e-6
    block_size: int = 64
    kernel_size: int = 32
    kernel_stride: int = 16
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192


def identity(x):
    return x


def _mm(a, b, cast):
    return jnp.matmul(cast(a.astype(F32)), cast(b.astype(F32)),
                      precision=HIGHEST)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


def _blocks_of(n: int, want: int) -> int:
    return want if n % want == 0 else n


def decays(hp: Hyper, layer: int):
    """``lam_h`` float32 [H] of published layer ``layer`` (reckoned in
    float64 on the host and rounded once: near 1 a float32 ``lam`` is
    already 1e-5 of its own slope off)."""
    H = hp.lightning_heads
    slope = 2.0 ** (-8.0 * np.arange(1, H + 1, dtype=np.float64) / H)
    return jnp.asarray(np.exp(-slope * (
        1.0 - layer / (hp.published_layers - 1) + 1e-5)), F32)


def _rope(x, theta):
    """x [T, H, D]: pair ``i`` of (x[i], x[i + D/2]) turned by ``t *
    theta ** (-i / (D/2))``."""
    T, _, D = x.shape
    half = D // 2
    angle = jnp.arange(T, dtype=F32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=F32) / half)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def recurrence(q, k, v, lam):
    """``o_t = q_t S_t`` of ``S_t = lam S_{t-1} + k_t^T v_t`` from ``S_0 =
    0``, one step a token: q, k, v [T, H, D] float32, lam [H] -> [T, H, D].
    The state is ``[H, D, D]``; multiplies and adds, no matmul."""
    T, H, D = q.shape

    def step(S, x):
        q_t, k_t, v_t = x
        S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.sum(q_t[:, :, None] * S, axis=1)

    @jax.checkpoint
    def stretch(S, xs):
        return jax.lax.scan(step, S, xs)

    n = _blocks_of(T, TIME_BLOCK)
    _, o = jax.lax.scan(stretch, jnp.zeros((H, D, D), F32),
                        tuple(x.reshape(T // n, n, H, D) for x in (q, k, v)))
    return o.reshape(T, H, D)


def _lightning(blk, a, hp: Hyper, layer: int, cast):
    """One sequence: a [T, d] (the normed input) -> [T, d]."""
    T = a.shape[0]
    H = hp.lightning_heads
    D = blk["wq"].shape[-1] // H
    q, k, v = (_mm(a, blk[w], cast).reshape(T, H, D)
               for w in ("wq", "wk", "wv"))
    q = _rope(_rms_norm(q, blk["q_norm"], hp.eps), hp.rope_theta) \
        / math.sqrt(D)
    k = _rope(_rms_norm(k, blk["k_norm"], hp.eps), hp.rope_theta)
    o = recurrence(q, k, v, decays(hp, layer)).reshape(T, H * D)
    gate = jax.nn.sigmoid(_mm(a, blk["wg"], cast))
    return _mm(gate * _rms_norm(o, blk["o_norm"], hp.eps), blk["wo"], cast)


def chosen_blocks(q, k, hp: Hyper, cast=identity):
    """InfLLM-v2's choice: q [T, G, R, D], k [T, G, D] (after QK-norm) ->
    bool [G, T, T / block_size], the blocks of each query's set (one set for
    the ``R`` heads of a group; ties to the lower block; fewer causal blocks
    than ``topk``: all of them)."""
    T, G, R, D = q.shape
    bs, ks, st = hp.block_size, hp.kernel_size, hp.kernel_stride
    n, nb = (T - ks) // st + 1, T // bs
    # 1. compressed keys: the mean of kernel_size keys, stride apart.
    window = st * jnp.arange(n)[:, None] + jnp.arange(ks)[None, :]
    kbar = k[window].mean(axis=1)                              # [n, G, D]
    last = st * jnp.arange(n) + ks - 1
    # The compressed positions that overlap block b.
    ratio, reach = bs // st, ks // st - 1
    over = ratio * jnp.arange(nb)[:, None] - reach \
        + jnp.arange(ratio + reach)[None, :]                   # [nb, pool]
    inside = (over >= 0) & (over < n)
    K = min(hp.topk, nb)

    def rows(args):
        t, qs = args                                   # [qb], [qb, G, R, D]
        # 2. softmax over the compressed positions that end at or before t,
        #    summed over the group's heads, max-pooled onto the blocks.
        s = jnp.einsum("qgrd,ngd->grqn", cast(qs), cast(kbar),
                       precision=HIGHEST) / math.sqrt(D)
        seen = last[None, :] <= t[:, None]
        p = jnp.where(seen, jax.nn.softmax(
            jnp.where(seen, s, -jnp.inf), axis=-1), 0.0)   # none seen: NaN
        group = p.sum(axis=1)                                  # [G, qb, n]
        score = jnp.where(inside, group[..., jnp.clip(over, 0, n - 1)],
                          0.0).max(axis=-1)                    # [G, qb, nb]
        # 3. forced blocks, causality, the topk highest.
        b = jnp.arange(nb)[None, :]
        own = (t // bs)[:, None]
        forced = (b < hp.init_blocks) | (b >= (t[:, None] - hp.window_size
                                               + 1) // bs)
        score = jnp.where(b > own, -jnp.inf,
                          jnp.where(forced, jnp.inf, score))
        _, idx = jax.lax.top_k(score, K)                       # [G, qb, K]
        picked = jnp.zeros(score.shape, bool).at[
            jnp.arange(G)[:, None, None],
            jnp.arange(t.shape[0])[None, :, None], idx].set(True)
        return picked & (b <= own)

    qb = _blocks_of(T, QUERY_BLOCK)
    sets = jax.lax.map(rows, (jnp.arange(T).reshape(T // qb, qb),
                              q.reshape(T // qb, qb, G, R, D)))
    return sets.transpose(1, 0, 2, 3).reshape(G, T, nb)


def sparse_heads(blk, a, hp: Hyper, cast):
    """q [T, G, R, D], k, v [T, G, D] of a sparse layer, after QK-norm."""
    T = a.shape[0]
    H, G = hp.n_head, hp.n_kv_head
    D = blk["wq"].shape[-1] // H
    q = _rms_norm(_mm(a, blk["wq"], cast).reshape(T, G, H // G, D),
                  blk["q_norm"], hp.eps)
    k = _rms_norm(_mm(a, blk["wk"], cast).reshape(T, G, D), blk["k_norm"],
                  hp.eps)
    return q, k, _mm(a, blk["wv"], cast).reshape(T, G, D)


def masked_attention(q, k, v, sets, hp: Hyper, cast=identity):
    """q [T, G, R, D], k, v [T, G, D], ``sets`` bool [G, T, T / block_size]
    (None: every block) -> [T, G, R, D]: softmax at ``D ** -0.5`` over the
    keys ``s <= t`` whose block is in ``t``'s set, by an explicit mask over
    explicit scores, ``QUERY_BLOCK`` queries at a time."""
    T, G, R, D = q.shape
    qb = _blocks_of(T, QUERY_BLOCK)
    keys = jnp.arange(T)
    if sets is None:
        sets = jnp.ones((G, T, T // hp.block_size), bool)

    @jax.checkpoint
    def query_block(args):
        start, qs, chosen = args             # [qb, G, R, D], [G, qb, nb]
        seen = jnp.repeat(chosen, hp.block_size, axis=-1) \
            & ((start + jnp.arange(qb))[:, None] >= keys[None, :])
        s = jnp.einsum("qgrd,kgd->grqk", cast(qs), cast(k),
                       precision=HIGHEST) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", cast(p), cast(v),
                          precision=HIGHEST)

    o = jax.lax.map(query_block, (
        jnp.arange(0, T, qb), q.reshape(T // qb, qb, G, R, D),
        sets.reshape(G, T // qb, qb, -1).transpose(1, 0, 2, 3)))
    return o.reshape(T, G, R, D)


def _sparse(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> [T, d]."""
    T = a.shape[0]
    q, k, v = sparse_heads(blk, a, hp, cast)
    sets = None if T <= hp.dense_len else jax.lax.stop_gradient(
        chosen_blocks(*jax.lax.stop_gradient((q, k)), hp, cast))
    o = masked_attention(q, k, v, sets, hp, cast)
    gate = jax.nn.sigmoid(_mm(a, blk["wg"], cast))
    return _mm(gate * o.reshape(T, -1), blk["wo"], cast)


def _mlp(blk, x, hp: Hyper, cast):
    @jax.checkpoint
    def rows(xs):
        a = _rms_norm(xs, blk["ff_ln"], hp.eps)
        return _mm(jax.nn.silu(_mm(a, blk["w_gate"], cast))
                   * _mm(a, blk["w_up"], cast), blk["w_down"], cast)

    T = x.shape[0]
    n = _blocks_of(T, ROW_BLOCK)
    return jax.lax.map(rows, x.reshape(T // n, n, -1)).reshape(x.shape)


def _block(blk, x, hp: Hyper, kind: str, layer: int, cast):
    r = hp.scale_depth / math.sqrt(hp.published_layers)
    a = _rms_norm(x, blk["input_ln"], hp.eps)
    x = x + r * (_sparse(blk, a, hp, cast) if kind == SPARSE
                 else _lightning(blk, a, hp, layer, cast))
    return x + r * _mlp(blk, x, hp, cast)


GROUPS = ("run", "vec")
VEC = ("input_ln", "ff_ln", "q_norm", "k_norm", "o_norm")


def split_groups(stack: dict, r: int) -> dict:
    """Run ``r``'s stacked leaves under their groups' names."""
    out = {}
    for k, v in stack.items():
        out.setdefault(f"{'vec' if k in VEC else 'run'}{r}", {})[k] = v
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
    """One sequence: tokens int32 [T] -> the final normalised hidden
    [T, d] over ``hidden_size / dim_model_base``."""
    x = hp.scale_emb * params["tok_emb"][tokens].astype(F32)
    layers = layers_of(params)
    if len(layers) != len(hp.mixer_types):
        raise ValueError(f"{len(layers)} layers of weights, mixer_types "
                         f"names {len(hp.mixer_types)}")
    for i, (blk, kind) in enumerate(zip(layers, hp.mixer_types)):
        if (kind == LIGHTNING) != ("o_norm" in blk):
            raise ValueError(f"layer {i}: mixer_types says {kind}, its "
                             "weights say otherwise")
        x = jax.checkpoint(
            lambda b, h, kind=kind, at=hp.first_layer + i:
            _block(b, h, hp, kind, at, cast))(blk, x)
    return _rms_norm(x, params["norm_f"], hp.eps) \
        / (x.shape[-1] / hp.dim_model_base)


def logits(params, tokens, hp: Hyper, cast=identity):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    return jnp.stack([_mm(hidden(params, t, hp, cast),
                          params["lm_head"].T, cast) for t in tokens])


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
        lg = _mm(x, params["lm_head"].T, cast)
        gold = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    def cross_entropy(x, targets):
        T = x.shape[0]
        n = _blocks_of(T, LOSS_BLOCK)
        return jnp.sum(jax.lax.map(rows, (
            x.reshape(T // n, n, -1), targets.reshape(T // n, n)))) / T

    total = 0.0
    for i in range(B):
        x = hidden(params, tokens[i, :-1], hp, cast)
        total = total + weights[i] * cross_entropy(x, tokens[i, 1:])
    return total
