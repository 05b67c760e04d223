"""Plain float32 Xing4.0 (XingChen-AGI Xing4.0-29B-A4B): forward, both
losses and gradients.

The yardstick the xing4.0-29b-a4b cell's ``correct`` is decided against.
Straight ``jax.numpy`` following the published ``config.json``
(``model_type: xing4_0``), whose keys are DeepSeek-V3's (arXiv:2412.19437:
the latent attention with a query latent, the ``noaux_tc`` sigmoid router,
``num_nextn_predict_layers``) and, for the residual path, those of
manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
Hyper-Connections, arXiv:2409.19606: ``hc_mult``, ``hc_sinkhorn_iters``,
``hc_eps``, ``mhc_h_res_clamp_min/max``). The modelling file is not on this
machine: the latent attention follows ``transformers``' ``deepseek_v3``, the
hyper-connections and the prediction module the papers, and the
configuration file lists under ``assumed`` what the config does not fix.

The residual stream is ``n = hc_mult`` lanes, **an explicit ``X`` [T, n, d]**,
``X_0`` the token embedding in every lane. Every sub-layer ``F`` with its own
``phi`` [n d, n^2 + 2n], ``b`` [n^2 + 2n], ``alpha`` [3]:

    u      = rms(vec(X_t))              over the n d joined channels, no gain
    [m_pre | m_post | m_res] = u phi                      n, n, n^2 wide
    H_pre  = sigmoid(alpha_0 m_pre + b_pre)                          [n]
    H_post = 2 sigmoid(alpha_1 m_post + b_post)                      [n]
    M_0    = exp(clip(alpha_2 mat(m_res) + b_res, clamp))            [n, n]
    M     <- M / (colsum(M) + hc_eps);  M <- M / (rowsum(M) + hc_eps)
             hc_sinkhorn_iters times, **a Python loop on [T, n, n]**
    y_t    = sum_i H_pre[i] X_t[i]
    X'_t[i] = sum_j H_res[i, j] X_t[j] + H_post[i] F(y)_t

``F(y) = Attn(rms(y; input_ln))``, then ``MLP(rms(y; post_attn_ln))`` (a
dense SwiGLU, or the shared expert plus the routed ones). Attention, per
head ``h`` of ``H``:

    cq = rms(a Wqa; q_ln);  q = cq Wqb  -> q_nope [T, Dn] beside q_rope [T, Dr]
    [c, r] = a Wkva;  [k_nope, v] = rms(c; kv_ln) Wkvb
    k_h = [k_nope_h, rope(r)],  q_h = [q_nope_h, rope(q_rope_h)]
    o_h = softmax_causal(q_h k_h^T (Dn + Dr)^-0.5 m^2) v_h;   F = concat(o) Wo

with the yarn table and ``m`` of ``reference/sarvam_mla.py``. At the end the
lanes are summed (``g``), then ``norm_f`` and the untied head. **The
prediction module** (DeepSeek-V3 section 2.2, depth 1): ``z_i = [rms(g_i;
mtp_hnorm) ; rms(tok_emb[t_{i+1}]; mtp_enorm)] mtp_eh``, ``z`` in every lane
through one more layer of the expert kind, the lanes summed, ``mtp_norm``,
the same head; ``L_mtp`` is the mean cross entropy against ``t_{i+2}`` **over
the first T - 1 positions, by a slice**, and ``L = L_main + lambda L_mtp``.

No kernel, no sort, no layout, no grouped matmul, no stack walked by a scan,
no import from the program under test: the rotary key is joined to every
head's keys and a head is plain attention by an explicit mask over explicit
scores; every held expert is applied to every token
(``reference/sarvam_mla.py:_moe``, imported: the layer is that model's).

Everything is float32 with ``precision=highest``. Departures from the
papers:

* memory, not arithmetic: weights arrive in the dtype they are trained in
  and are widened where they are used; each block and each block of queries
  is wrapped in ``jax.checkpoint``, queries are taken ``QUERY_BLOCK`` at a
  time, the dense MLP ``TOKEN_BLOCK`` tokens at a time, experts one at a
  time (sarvam's reference's own);
* the share of the experts: ``Hyper.held = (first, count)`` of the router's,
  as ``reference/sarvam_mla.py``; ``(0, E)`` is the uncut layer;
* rotate-half pairs where DeepSeek's code interleaves them;
* what the config does not fix (``assumed.mhc`` / ``assumed.mtp`` in the
  configuration file): columns before rows in a Sinkhorn round; no gain in
  the maps' RMSNorm; the embedding copied into the lanes at the start and
  the lanes summed at the end; ``lambda`` 0.1; the hidden state before the
  embedding in ``mtp_eh``'s input; the module reads the summed lanes;
* ``cast`` is applied to both operands of every matmul (``u phi`` and the
  routers' among them; the lanes' mixing is no matmul). The identity gives
  the reference; the control (``reference/gpt2.py:fp8_cast``) puts the
  reference in the program's place one precision step below bf16.

Parameter layout: ``tok_emb`` [V, d], ``norm_f`` [d], ``lm_head`` [V, d],
``mtp_eh`` [2 d, d], ``mtp_hnorm``, ``mtp_enorm``, ``mtp_norm`` [d], and the
layers as ``dense``, ``blocks`` and ``mtp`` with their maps' leaves beside
them as ``hcdense``, ``hcblocks`` and ``hcmtp``, each one dict of leaves with
a leading layer axis (unstacked here into per-layer dicts), or as ``layers``
and ``mtp_layers``, lists of per-layer dicts. Every layer has ``input_ln``,
``post_attn_ln`` [d], ``kv_ln`` [R], ``q_ln`` [Rq], ``wqa`` [d, Rq], ``wqb``
[Rq, H (Dn + Dr)], ``wkva`` [d, R + Dr], ``wkvb`` [R, H (Dn + Dv)], ``wo``
[H Dv, d], and ``phi_attn``, ``b_attn``, ``alpha_attn``, ``phi_mlp``,
``b_mlp``, ``alpha_mlp``; a dense layer ``w_gate``, ``w_up`` [d, I], ``w_down``
[I, d]; an expert layer sarvam's.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import bias_update  # noqa: F401 (the same)
from benchmark.reference.sarvam_mla import (
    F32,
    HIGHEST,
    QUERY_BLOCK,
    Yarn,
    _dense,
    _mm,
    _moe,
    _rms_norm,
    _rope,
    identity,
    softmax_scale,
)


class Hyper(NamedTuple):
    """What the arithmetic needs beyond the weights' shapes (the first ten
    as ``reference/sarvam_mla.py:Hyper``, whose functions read them)."""
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    top_k: int
    held: Tuple[int, int]                # (first, count) of the router's E
    route_scale: float = 2.0
    rope_theta: float = 10000.0
    yarn: Yarn = Yarn(factor=64.0)
    eps: float = 1e-6
    lanes: int = 4
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    clamp: Tuple[float, float] = (-30.0, 30.0)
    mtp_weight: float = 0.1


def hyper_maps(X, phi, b, alpha, hp: Hyper, cast=identity):
    """X [T, n, d] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    T, n, d = X.shape
    ones = jnp.ones((n * d,), F32)
    m = _mm(_rms_norm(X.reshape(T, n * d), ones, hp.eps), phi, cast)
    b, alpha = b.astype(F32), alpha.astype(F32)
    pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(alpha[2] * m[:, 2 * n:].reshape(T, n, n)
                         + b[2 * n:].reshape(n, n), *hp.clamp))
    for _ in range(hp.sinkhorn_iters):
        M = M / (jnp.sum(M, axis=1, keepdims=True) + hp.hc_eps)   # columns
        M = M / (jnp.sum(M, axis=2, keepdims=True) + hp.hc_eps)   # rows
    return pre, post, M


def _through(blk, X, sub: str, F, hp: Hyper, cast):
    """One sub-layer ``F`` (y -> (its output, what else it hands back)) on
    the lanes: read, apply, mix and write."""
    pre, post, H = hyper_maps(X, blk[f"phi_{sub}"], blk[f"b_{sub}"],
                              blk[f"alpha_{sub}"], hp, cast)
    out, aux = F(jnp.einsum("ti,tid->td", pre, X, precision=HIGHEST))
    return jnp.einsum("tij,tjd->tid", H, X, precision=HIGHEST) \
        + post[:, :, None] * out[:, None, :], aux


def _attention(blk, a, hp: Hyper, cast):
    """One sequence: a [T, d] (the normed input) -> the attention output
    [T, d], every head's."""
    T = a.shape[0]
    Dn, Dr, Dv, R = hp.qk_nope_head_dim, hp.qk_rope_head_dim, \
        hp.v_head_dim, hp.kv_lora_rank
    H = blk["wqb"].shape[-1] // (Dn + Dr)

    def heads(t):
        return t.reshape(T, H, -1).transpose(1, 0, 2)

    cq = _rms_norm(_mm(a, blk["wqa"], cast), blk["q_ln"], hp.eps)
    q = heads(_mm(cq, blk["wqb"], cast))                 # [H, T, Dn + Dr]
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
    o = o.transpose(0, 2, 1, 3).reshape(T, H * Dv)
    return _mm(o, blk["wo"], cast)


def _block(blk, X, hp: Hyper, cast):
    """X [T, n, d] -> (X', the router's expert ids [T, k] or None)."""
    X, _ = _through(blk, X, "attn", lambda y: (_attention(
        blk, _rms_norm(y, blk["input_ln"], hp.eps), hp, cast), None),
        hp, cast)

    def mlp(y):
        h = _rms_norm(y, blk["post_attn_ln"], hp.eps)
        return _moe(blk, h, hp, cast) if "router" in blk \
            else (_dense(blk, h, cast), None)

    return _through(blk, X, "mlp", mlp, hp, cast)


def _unstacked(params, names) -> list:
    out = []
    for name in names:
        stack = {**params.get(name, {}), **params.get("hc" + name, {})}
        if stack:
            n = next(iter(stack.values())).shape[0]
            out += [{k: v[i] for k, v in stack.items()} for i in range(n)]
    return out


def layers_of(params) -> list:
    """The model's per-layer dicts, dense layers first, whichever layout."""
    return list(params["layers"]) if "layers" in params \
        else _unstacked(params, ("dense", "blocks"))


def mtp_layers_of(params) -> list:
    """The prediction module's layers (one)."""
    return list(params["mtp_layers"]) if "mtp_layers" in params \
        else _unstacked(params, ("mtp",))


def _lanes(x, layers, hp: Hyper, cast):
    """x [T, d] into every lane, through ``layers``, the lanes summed:
    (g [T, d], the routers' expert ids)."""
    X = jnp.broadcast_to(x[:, None, :], (x.shape[0], hp.lanes, x.shape[1]))
    chosen = []
    for blk in layers:
        X, experts = jax.checkpoint(
            lambda b, h: _block(b, h, hp, cast))(blk, X)
        if experts is not None:
            chosen.append(experts)
    return jnp.sum(X, axis=1), chosen


def summed_lanes(params, tokens, hp: Hyper, cast=identity):
    """One sequence: tokens int32 [T] -> (the lanes' sum before ``norm_f``
    [T, d], the expert layers' expert ids)."""
    return _lanes(params["tok_emb"][tokens].astype(F32), layers_of(params),
                  hp, cast)


def mtp_hidden(params, g, following, hp: Hyper, cast=identity):
    """g [T, d] and the token after each position [T] -> (the prediction
    module's final normalised hidden [T, d], its router's expert ids)."""
    e = params["tok_emb"][following].astype(F32)
    z = _mm(jnp.concatenate([_rms_norm(g, params["mtp_hnorm"], hp.eps),
                             _rms_norm(e, params["mtp_enorm"], hp.eps)], -1),
            params["mtp_eh"], cast)
    x, chosen = _lanes(z, mtp_layers_of(params), hp, cast)
    return _rms_norm(x, params["mtp_norm"], hp.eps), chosen


def logits(params, tokens, hp: Hyper, cast=identity):
    """tokens int32 [B, T + 1] -> float32 (logits [B, T, V] of the next
    token, the prediction module's [B, T, V] of the one after it)."""
    main, second = [], []
    for t in tokens:
        g, _ = summed_lanes(params, t[:-1], hp, cast)
        main.append(_mm(_rms_norm(g, params["norm_f"], hp.eps),
                        params["lm_head"].T, cast))
        x, _ = mtp_hidden(params, g, t[1:], hp, cast)
        second.append(_mm(x, params["lm_head"].T, cast))
    return jnp.stack(main), jnp.stack(second)


def losses(params, tokens, hp: Hyper, cast=identity, weights=None):
    """(L_main, L_mtp) of tokens [B, T+1]: each the mean over the batch of
    a sequence's own mean, or with ``weights`` [B] the sum weighted by
    them (a batch that repeats sequences is then computed from the distinct
    ones). ``L_mtp`` is over a sequence's first ``T - 1`` positions."""
    B = tokens.shape[0]
    if weights is None:
        weights = jnp.full((B,), 1.0 / B, F32)

    @jax.checkpoint
    def cross_entropy(x, targets):
        lg = _mm(x, params["lm_head"].T, cast)
        gold = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)

    main = second = 0.0
    for i in range(B):
        t = tokens[i]
        g, _ = summed_lanes(params, t[:-1], hp, cast)
        main = main + weights[i] * cross_entropy(
            _rms_norm(g, params["norm_f"], hp.eps), t[1:])
        x, _ = mtp_hidden(params, g, t[1:], hp, cast)
        second = second + weights[i] * cross_entropy(x[:-1], t[2:])
    return main, second


def loss(params, tokens, hp: Hyper, cast=identity, weights=None):
    """The training loss ``L_main + lambda L_mtp``."""
    main, second = losses(params, tokens, hp, cast, weights)
    return main + hp.mtp_weight * second


def expert_counts(params, tokens, hp: Hyper, cast=identity):
    """tokens [B, T+1] -> float32 [routers, E]: the assignments each router
    output got over the whole batch, the prediction module's last: what the
    biases' update reads."""
    E = layers_of(params)[-1]["router"].shape[-1]
    rows = []
    for t in tokens:
        g, chosen = summed_lanes(params, t[:-1], hp, cast)
        rows.append(jnp.stack(
            chosen + mtp_hidden(params, g, t[1:], hp, cast)[1]))
    return jnp.sum(jax.nn.one_hot(jnp.stack(rows), E, dtype=F32),
                   axis=(0, 2, 3))
