"""Xing4.0 (XingChen-AGI ``Xing4.0-29B-A4B``, ``model_type: xing4_0``: 40
layers, hidden 3584, DeepSeek-V3's keys: 32 heads of multi-head latent
attention with a query latent, two leading dense SwiGLU layers of 9216, then
64 routed SwiGLU experts of 1024, 4 a token by sigmoid scores, beside one
shared expert; one multi-token-prediction module; vocabulary 131,072,
untied) on a **residual stream of ``n = hc_mult`` lanes** mixed by
manifold-constrained hyper-connections (mHC, arXiv:2512.24880).

The stream is ``X`` [T, n d], the lanes side by side, ``X_0`` the token
embedding in every lane. Every sub-layer ``F`` (a layer's attention, then its
MLP or expert part) has its own ``phi`` [n d, n^2 + 2n], ``b`` [n^2 + 2n] and
``alpha`` [3] (``models/layers.py:hyper_maps``, float32):

    H_pre, H_post, H_res = hyper_maps(X; phi, b, alpha)     a token's own
    y   = sum_i H_pre[i] X[i]                               hyper_read
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] F(y)         hyper_write

with ``F(y) = Attn(rms(y; input_ln))`` or ``MLP(rms(y; post_attn_ln))``. At
the end the lanes are summed, then ``norm_f`` and the untied head.

**Attention** is ``models/sarvam_mla.py``'s latent attention (``attend``,
``attention_inputs``, the kernels of ``ops/pallas/mla_attention.py``) with
DeepSeek-V3's query latent: ``q = rms(a Wqa; q_ln) Wqb`` where sarvam's is
one projection; the ``deepseek_yarn`` table and its ``m^2`` in the softmax
scale are the base class's. **The expert layer is Trinity's**
(``models/afmoe.py``: ``router`` / ``moe`` / ``swiglu``), told which experts
it holds (``experts_held``).

**Multi-token prediction** (DeepSeek-V3, arXiv:2412.19437, section 2.2;
``num_nextn_predict_layers`` 1): with ``g_i`` the summed lanes at position
``i`` before ``norm_f``,

    z_i = [rms(g_i; mtp_hnorm) ; rms(tok_emb[t_{i+1}]; mtp_enorm)] mtp_eh

``mtp_eh`` [2 d, d]; ``z`` in every lane goes through one more whole layer
of the expert kind (the stack ``mtp``), the lanes are summed, ``mtp_norm``,
then **the main model's head**; ``L = L_main + mtp_loss_weight x L_mtp``,
``L_mtp`` the mean cross entropy against ``t_{i+2}`` over the positions
that have one: the last position of a sequence carries weight 0
(``cross_entropy(weights=)``), so no array is one position short.

A block's token-wise parts run in chunks of the sequence as sarvam's do; the
maps are token-wise too and run in those chunks with the projections they
sit beside. The attention sub-layer's maps [T, n^2 + 2n] are made in the
chunk loop before the kernels and handed to the one after them
(``sarvam_mla.attend(read=)``). The walk's carry is the stream, one array
four times as wide as the hidden size; it is widened after the embedding
and narrowed before the head and nowhere else.

Parameters: ``l{i}`` per-layer dicts, the prediction module's layer after the
model's (``init_params``), or **three stacks** (``stacked_init_params``):
``dense`` [first_k_dense_replace, ...], ``blocks`` [the rest, ...] and ``mtp``
[1, ...], each walked with ``models/layers.py:scan_blocks``; a layer's maps'
leaves lie in the group ``hc`` (``hcdense``, ``hcblocks``, ``hcmtp``), so that
a check of a step can name them without the layer's matrices. ``loss_fn``
takes either layout.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from tepdist_tpu.models import decoder, sarvam_mla
from tepdist_tpu.models.afmoe import moe, router, swiglu
from tepdist_tpu.models.decoder import (
    fake_batch,  # noqa: F401 (the model's, as every decoder's)
    layer_dicts,
    stack_layers,
    walk_layers,
)
from tepdist_tpu.models.layers import (
    cross_entropy,
    held_routing_stats,
    hyper_maps,
    hyper_read,
    hyper_write,
    lanes_of,
    over_sequence,
    part,
    rms_norm,
)
from tepdist_tpu.telemetry import traced

traced.declare(
    "residual_lanes", "lanes of the traced step's residual stream (0: every "
    "sub-layer's result is added to one [tokens, hidden] array)")
traced.declare(
    "mhc_stream_bytes", "bytes of a micro batch's residual stream [tokens, "
    "lanes x hidden] where hyper-connections mix it")
traced.declare(
    "mhc_sinkhorn_rounds", "rounds of column and row normalisation that "
    "make a token's lane-mixing matrix doubly stochastic")
traced.declare(
    "mtp_depth", "multi-token-prediction modules of the traced step: whole "
    "layers past the model's, each with a loss through the shared head")
traced.declare(
    "mtp_loss_weight", "what the traced step's loss multiplies its "
    "multi-token-prediction loss by")


@dataclasses.dataclass(frozen=True)
class XingConfig(sarvam_mla.SarvamMLAConfig):
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_attention_heads: int = 32
    heads_held: Tuple[int, int] = (0, 32)
    q_lora_rank: int = 768
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    num_experts: int = 64
    experts_held: Tuple[int, int] = (0, 64)
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    yarn_factor: float = 64.0
    # The residual stream's lanes and their maps.
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    # Prediction modules past the model's layers (0 or 1), and what the
    # loss multiplies theirs by (no key of the published config: DeepSeek-V3's
    # value for the later part of its schedule).
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.1


CONFIGS: Dict[str, XingConfig] = {
    "29b-a4b": XingConfig(),
    # The published structure small: 4 heads of 16 + 8 and 12 behind a query
    # latent of 20, 2 of 8 experts held, four lanes, a table whose original
    # context is 8 of the tests' 32 positions, one prediction module.
    "test": XingConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4, heads_held=(0, 4),
        q_lora_rank=20, kv_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12, num_hidden_layers=3,
        first_k_dense_replace=1, num_experts=8, experts_held=(2, 2),
        num_experts_per_tok=2, rope_theta=100.0, yarn_factor=4.0,
        yarn_original_max_position=8, yarn_beta_fast=2.0,
        yarn_beta_slow=0.5, hc_sinkhorn_iters=5, dtype=jnp.float32,
        moe_tile_m=8),
}

# Small around the published head widths (128 + 64 and 128, which the kernels
# compile for on the chip) and lanes of whole lane tiles: ``chip_smoke.py``'s.
CONFIGS["smoke"] = dataclasses.replace(
    CONFIGS["test"], vocab_size=2048, hidden_size=256, intermediate_size=512,
    moe_intermediate_size=128, q_lora_rank=128, kv_lora_rank=128,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    yarn_original_max_position=256, hc_sinkhorn_iters=20,
    dtype=jnp.bfloat16, remat=True, loss_chunk=256, moe_tile_m=128)

_OUTSIDE_BLOCKS = ("tok_emb", "norm_f", "lm_head", "mtp_eh", "mtp_hnorm",
                   "mtp_enorm", "mtp_norm")
# A layer's maps' leaves lie in a group of their own beside its matrices.
GROUPS = ("", "hc")
_MAPS = tuple(f"{leaf}_{sub}" for sub in ("attn", "mlp")
              for leaf in ("phi", "b", "alpha"))
_GROUP_OF = {k: "hc" for k in _MAPS}
# A layer's leaves that the first half of ``block`` reads (``attend`` and the
# attention sub-layer's maps); the second half is handed the rest.
_ATTEND_LEAVES = ("input_ln", "kv_ln", "q_ln", "wq", "wqa", "wqb", "wkva",
                  "wkvb", "phi_attn", "b_attn", "alpha_attn")


def _depth(cfg: XingConfig) -> int:
    if cfg.num_nextn_predict_layers not in (0, 1):
        raise ValueError("one prediction module is what is written here, "
                         f"not {cfg.num_nextn_predict_layers}")
    return cfg.num_nextn_predict_layers


def init_params(cfg: XingConfig, key, std: float = 0.02):
    """normal(std) matrices, unit norm gains, zero selection bias; ``l{i}``
    per-layer dicts, the first ``first_k_dense_replace`` dense, the
    prediction module's layer last (``l{num_hidden_layers}``). **The maps
    start where they do something**: ``phi`` normal(std / sqrt(n)), the
    three ``alpha`` 1, ``b`` normal(1) with 2 more on ``H_res``'s diagonal,
    so that they differ from token to token by tenths. The papers start
    them as a plain pre-norm residual (small ``alpha``, ``b_res`` towards
    the identity), where a check cannot tell a map from none."""
    d, R, Rq, n = cfg.hidden_size, cfg.kv_lora_rank, cfg.q_lora_rank, \
        cfg.hc_mult
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    Hh = cfg.heads_held[1]
    f, fs = cfg.moe_intermediate_size, \
        cfg.moe_intermediate_size * cfg.num_shared_experts
    E, G = cfg.num_experts, cfg.experts_held[1]
    L, wide = cfg.num_hidden_layers, n * n + 2 * n
    keys = jax.random.split(key, 3 + L + _depth(cfg))

    def norm(k, shape, std=std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(
            cfg.dtype)

    def ones(n=d):           # a buffer each: a plan donates every leaf
        return jnp.ones((n,), jnp.float32)

    towards_identity = jnp.concatenate(
        [jnp.zeros((2 * n,)), 2.0 * jnp.eye(n).reshape(-1)])
    params: Dict[str, Any] = {
        "tok_emb": norm(keys[0], (cfg.vocab_size, d)),
        "norm_f": ones(),
        "lm_head": norm(keys[1], (cfg.vocab_size, d)),
    }
    if _depth(cfg):
        params.update({"mtp_eh": norm(keys[2], (2 * d, d)),
                       "mtp_hnorm": ones(), "mtp_enorm": ones(),
                       "mtp_norm": ones()})
    for i in range(L + _depth(cfg)):
        lk = jax.random.split(keys[3 + i], 17)
        layer = {
            "input_ln": ones(), "post_attn_ln": ones(), "kv_ln": ones(R),
            "q_ln": ones(Rq),
            "wqa": norm(lk[0], (d, Rq)),
            "wqb": norm(lk[1], (Rq, Hh * (Dn + Dr))),
            "wkva": norm(lk[2], (d, R + Dr)),
            "wkvb": norm(lk[3], (R, Hh * (Dn + Dv))),
            "wo": norm(lk[4], (Hh * Dv, d)),
        }
        for j, sub in enumerate(("attn", "mlp")):
            layer.update({
                f"phi_{sub}": norm(lk[5 + 2 * j], (n * d, wide),
                                   std / n ** 0.5),
                f"b_{sub}": jax.random.normal(
                    lk[6 + 2 * j], (wide,), jnp.float32) + towards_identity,
                f"alpha_{sub}": jnp.ones((3,), jnp.float32)})
        if i < cfg.first_k_dense_replace:
            layer.update({
                "w_gate": norm(lk[9], (d, cfg.intermediate_size)),
                "w_up": norm(lk[10], (d, cfg.intermediate_size)),
                "w_down": norm(lk[11], (cfg.intermediate_size, d))})
        else:
            layer.update({
                "router": norm(lk[9], (d, E)),
                "router_bias": jnp.zeros((E,), jnp.float32),
                "shared_gate": norm(lk[10], (d, fs)),
                "shared_up": norm(lk[11], (d, fs)),
                "shared_down": norm(lk[12], (fs, d)),
                "w_gate": norm(lk[13], (G, d, f)),
                "w_up": norm(lk[14], (G, d, f)),
                "w_down": norm(lk[15], (G, f, d))})
        params[f"l{i}"] = layer
    return params


def _stacks(cfg: XingConfig):
    """(name, first layer, layers) of the model's stacks of layers."""
    n, L = cfg.first_k_dense_replace, cfg.num_hidden_layers
    return [s for s in (("dense", 0, n), ("blocks", n, L - n)) if s[2]]


def _mtp_stack(cfg: XingConfig):
    """The prediction module's, after the model's."""
    return [("mtp", cfg.num_hidden_layers, _depth(cfg))] if _depth(cfg) \
        else []


def stacked_init_params(cfg: XingConfig, key, std: float = 0.02):
    """``init_params`` with the layers stacked: ``dense``, ``blocks`` and
    ``mtp``, [layers of that kind, ...] each, a layer's maps' leaves under
    ``hc`` + the stack's name."""
    return stack_layers(init_params(cfg, key, std),
                        _stacks(cfg) + _mtp_stack(cfg),
                        _OUTSIDE_BLOCKS[:3 + 4 * _depth(cfg)], GROUPS,
                        _GROUP_OF)


def rank_share(params, cfg: XingConfig, experts_held: Tuple[int, int]):
    """From the ``l{i}`` parameters of ``cfg`` (which holds every expert)
    what a rank holding ``experts_held`` has of them, and that rank's
    configuration: the held experts' weights, the prediction module's among
    them, and everything else whole (attention, the maps, the routers and
    the shared experts are data-parallel: alike on every rank)."""
    first, count = experts_held
    out = {k: v for k, v in params.items() if k in _OUTSIDE_BLOCKS}
    for i in range(cfg.num_hidden_layers + _depth(cfg)):
        blk = dict(params[f"l{i}"])
        if "router" in blk:
            for k in decoder.EXPERT_LEAVES:
                blk[k] = blk[k][first:first + count]
        out[f"l{i}"] = blk
    return out, dataclasses.replace(cfg, experts_held=tuple(experts_held))


def _widest(cfg: XingConfig) -> int:
    """What sizes the chunks of a block's token-wise parts: sarvam's, or the
    stream's ``n d`` channels, the larger."""
    return max(sarvam_mla._widest(cfg), cfg.hc_mult * cfg.hidden_size)


def _maps(blk, x, cfg: XingConfig, sub: str):
    return hyper_maps(
        x, blk[f"phi_{sub}"], blk[f"b_{sub}"], blk[f"alpha_{sub}"],
        rounds=cfg.hc_sinkhorn_iters, eps=cfg.rms_norm_eps,
        hc_eps=cfg.hc_eps, clamp=cfg.mhc_h_res_clamp)


def _read(blk, cfg: XingConfig):
    """``sarvam_mla.attend``'s ``read``: a chunk of the stream -> the
    attention sub-layer's input, and its maps to keep for the write."""
    def read(xc):
        maps = _maps(blk, xc, cfg, "attn")
        return hyper_read(xc, maps), (maps,)
    return read


def block(blk, x, cfg: XingConfig):
    """One layer on the stream ``x`` [B, T, n d]; dense or routed by what
    ``blk`` holds, its query through a latent where ``blk`` holds ``wqa``.
    As ``sarvam_mla.block``: the token-wise parts, the maps among them, in
    rematerialised chunks of the sequence, the second half handed its
    weights (``blk``'s expert leaves may be ``ExpertStack``s). With one lane
    and maps of one it is that block."""
    eps = cfg.rms_norm_eps
    traced.note("residual_lanes", cfg.hc_mult)
    traced.note("mhc_stream_bytes", x.size * jnp.dtype(x.dtype).itemsize)
    traced.note("mhc_sinkhorn_rounds", cfg.hc_sinkhorn_iters)

    def after(blk, start, xc, oc, maps):
        del start
        with part("mixer"), jax.named_scope("mla_out"):
            xc = hyper_write(xc, maps, oc @ blk["wo"])
        with part("moe" if "router" in blk else "mlp"):
            maps = _maps(blk, xc, cfg, "mlp")
            h = rms_norm(hyper_read(xc, maps), blk["post_attn_ln"], eps)
            if "router" in blk:
                return hyper_write(xc, maps, moe(blk, h, cfg))
            return hyper_write(xc, maps, swiglu(
                h, blk["w_gate"], blk["w_up"], blk["w_down"]))

    with part("mixer"):
        o, maps = sarvam_mla.attend(blk, x, cfg, _read(blk, cfg))
    with jax.named_scope("mla_out_mlp"):
        return over_sequence(after, _widest(cfg), x, o, maps, weights={
            k: w for k, w in blk.items() if k not in _ATTEND_LEAVES})


def _widened(x, cfg: XingConfig):
    """[B, T, d] in every lane of the stream [B, T, n d]."""
    return jnp.tile(x, (1, 1, cfg.hc_mult))


def _narrowed(x, cfg: XingConfig):
    """The stream's lanes summed [B, T, d], in float32."""
    return functools.reduce(jnp.add, lanes_of(x, cfg.hc_mult)).astype(
        x.dtype)


def _walk(x, params, stacks, cfg: XingConfig):
    """``x`` [B, T, d] into the lanes, through ``stacks``' layers, summed."""
    base, count = stacks[0][1], sum(s[2] for s in stacks)
    if "l0" in params:      # a walk counts its layers from 0
        params = {f"l{i}": params[f"l{base + i}"] for i in range(count)}
    x = walk_layers(lambda blk, h, _: block(blk, h, cfg), _widened(x, cfg),
                    params, [(name, first - base, layers)
                             for name, first, layers in stacks],
                    [None] * count, cfg.remat, GROUPS,
                    experts=decoder.EXPERT_LEAVES)
    return _narrowed(x, cfg)


def summed_lanes(params, tokens, cfg: XingConfig):
    """tokens int32 [B, T] -> the model's lanes summed [B, T, d], before
    ``norm_f``: what the head's norm and the prediction module read."""
    with part("embed"):
        x = params["tok_emb"][tokens].astype(cfg.dtype)
    return _walk(x, params, _stacks(cfg), cfg)


def mtp_inputs(params, g, following, cfg: XingConfig):
    """``g`` [B, T, d] (:func:`summed_lanes`) and the tokens after each
    position, int32 [B, T] -> the prediction module's input ``z`` [B, T, d],
    in chunks of the sequence."""
    eps = cfg.rms_norm_eps

    def joined(start, gc, ec):
        del start
        return jnp.concatenate(
            [rms_norm(gc, params["mtp_hnorm"], eps),
             rms_norm(ec, params["mtp_enorm"], eps)], -1) @ params["mtp_eh"]

    with part("embed"), jax.named_scope("mtp_in"):
        e = params["tok_emb"][following].astype(cfg.dtype)
        return over_sequence(joined, _widest(cfg), g, e)


def mtp_hidden_states(params, g, following, cfg: XingConfig):
    """The prediction module's final normalised hidden [B, T, d]: position
    ``i`` is to predict the token after ``following[i]``."""
    with jax.named_scope("mtp"):
        x = _walk(mtp_inputs(params, g, following, cfg), params,
                  _mtp_stack(cfg), cfg)
        with part("head_loss"):
            return rms_norm(x, params["mtp_norm"], cfg.rms_norm_eps)


def hidden_states(params, tokens, cfg: XingConfig):
    """tokens int32 [B, T] -> final normalised hidden [B, T, d]."""
    g = summed_lanes(params, tokens, cfg)
    with part("head_loss"):
        return rms_norm(g, params["norm_f"], cfg.rms_norm_eps)


def forward(params, tokens, cfg: XingConfig):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    x = hidden_states(params, tokens, cfg)
    return (x @ params["lm_head"].T).astype(jnp.float32)


def mtp_forward(params, tokens, cfg: XingConfig):
    """tokens int32 [B, T + 1] -> the prediction module's float32 logits
    [B, T, V]: position ``i``'s are for token ``i + 2``."""
    g = summed_lanes(params, tokens[:, :-1], cfg)
    x = mtp_hidden_states(params, g, tokens[:, 1:], cfg)
    return (x @ params["lm_head"].T).astype(jnp.float32)


def losses(params, tokens, cfg: XingConfig):
    """tokens [B, T+1] -> (L_main, L_mtp): the next token's cross entropy
    and, through the same head, the prediction module's of the token after
    it over the ``T - 1`` positions that have one (0.0 without a module)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    g = summed_lanes(params, inputs, cfg)
    with part("head_loss"):
        x = rms_norm(g, params["norm_f"], cfg.rms_norm_eps)
    main = cross_entropy(x, params["lm_head"], targets, cfg.loss_chunk)
    if not _depth(cfg):
        return main, 0.0
    x = mtp_hidden_states(params, g, targets, cfg)
    # Position i's target is token i + 2; the last position has none, and
    # what stands there (the row's second token) carries weight 0.
    T = targets.shape[1]
    has_one = jnp.broadcast_to(
        (jnp.arange(T) < T - 1).astype(jnp.float32), targets.shape)
    with jax.named_scope("mtp"):
        return main, cross_entropy(
            x, params["lm_head"], jnp.roll(targets, -1, axis=1),
            cfg.loss_chunk, weights=has_one)


def loss_fn(params, tokens, cfg: XingConfig):
    """``L_main + mtp_loss_weight x L_mtp`` of tokens [B, T+1]
    (:func:`losses`). The routers' biases receive their step's counts where
    their gradients would be (``afmoe.count_choices``)."""
    traced.note("mtp_depth", _depth(cfg))
    traced.note("mtp_loss_weight", cfg.mtp_loss_weight * _depth(cfg))
    main, second = losses(params, tokens, cfg)
    return main + cfg.mtp_loss_weight * second if _depth(cfg) else main


def expert_choices(params, tokens, cfg: XingConfig):
    """tokens int32 [B, T + 1] -> the expert ids every router chose, the
    prediction module's last, int32 [routers, B * T, k]; the forward pass
    alone."""
    inputs, following = tokens[:, :-1], tokens[:, 1:]
    S = inputs.shape[0] * inputs.shape[1]
    ids = []

    def walked(x, stacks):
        x = _widened(x, cfg)
        for blk in layer_dicts(params, stacks, GROUPS):
            if "router" in blk:
                o, maps = sarvam_mla.attend(blk, x, cfg, _read(blk, cfg))
                mid = hyper_write(x, maps, o @ blk["wo"])
                h = rms_norm(hyper_read(mid, _maps(blk, mid, cfg, "mlp")),
                             blk["post_attn_ln"], cfg.rms_norm_eps)
                ids.append(router(blk, h.reshape(S, -1), cfg)[2])
            x = block(blk, x, cfg)
        return _narrowed(x, cfg)

    g = walked(params["tok_emb"][inputs].astype(cfg.dtype), _stacks(cfg))
    if _depth(cfg):
        walked(mtp_inputs(params, g, following, cfg), _mtp_stack(cfg))
    return jnp.stack(ids)


def routing_stats(params, tokens, cfg: XingConfig) -> dict:
    """What the routers did with ``tokens`` [B, T+1], outside any step
    (``models/layers.py:held_routing_stats`` over this model's choices, the
    prediction module's among them)."""
    return held_routing_stats(expert_choices(params, tokens, cfg),
                              cfg.num_experts, cfg.moe_tile_m,
                              cfg.experts_held)
