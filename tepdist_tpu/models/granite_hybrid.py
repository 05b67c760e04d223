"""Granite 4.0-H (ibm-granite ``granite-4.0-h-small``, ``model_type:
granitemoehybrid``: 40 layers, hidden 4096, every layer a mixer AND an expert
part; 36 Mamba-2 mixers of 128 heads of 64 over one group of 128 states and 4
attention mixers (layers 5, 15, 25, 35) of 32 query heads over 8 of 128 with
no positional embedding; every layer 72 routed SwiGLU experts of 768, 10 a
token, beside a shared SwiGLU MLP of 1536; vocabulary 100,352, the head the
embedding). Four multipliers rescale the embedding (12), the attention scores
(1/128, NOT 128^-0.5), both residual adds (0.22) and the logits (1/16):

    x0 = 12 * tok_emb[tokens]
    x  = x + 0.22 * Mixer(rms(x; input_ln))
    h  = rms(x; post_attn_ln)
    x  = x + 0.22 * (Experts(h) + SharedMLP(h))
    logits = rms(x; norm_f) tok_emb^T / 16

``Mixer``, **Mamba-2**: ``models/nemotron_h.py:mamba2``, the one function
that mixer's whole form runs too (``[z | xBC | dt] = a W_in``, a 4-tap conv
with its bias and a silu over the joined ``xBC``, ``Delta = softplus(dt +
dt_bias)`` float32 and unclamped, the state-space rule with ``D u``, the gate
before ONE RMSNorm over the gated channels, ``W_out``). **Attention**:
``models/layers.py:gqa_heads`` with the scores' multiplier its own, through
``wo``. ``Experts``: ``e = top_10(h Wr)`` in float32, ``w = softmax(logits[e])``
(the softmax over the chosen alone, which is ``models/mellum.py:router``: the
top k of the softmax over all, normalised over the k);
``sum_j w_j Wd[e_j] (silu(Wg[e_j] h) * Wu[e_j] h)`` over the experts held here
(``ops/grouped_matmul.py:routed_experts``); ``SharedMLP`` the same form, every
token, unweighted. The cross entropy alone: no auxiliary loss.

**A rank of the ranks that share each layer.** The configuration counts what
is HERE: ``mamba_n_heads`` Mamba-2 heads (their columns of ``w_z``, ``w_dt``
and of ``w_xbc``'s ``u``; ``B`` and ``C``, one group, whole on every rank;
their rows of ``w_out``), ``num_attention_heads`` over ``num_key_value_heads``,
``experts_held`` of the router's ``num_experts``, ``vocab_size`` rows of the
embedding; the norms, the router and the shared MLP are whole on every rank
(what every rank computes alike counts once in the ranks' sum).
:func:`rank_share` cuts a whole model's leaves and configuration to rank
``rank`` of ``of``. Every sub-layer then gives its partial sum, which times
0.22 goes on to the next layer; nothing stands in for the other ranks or for
the sums over them, with one exception that is no stand-in: **the gated norm's
mean square is over a token's gated channels of ALL ranks** in the model;
``nemotron_h.mamba2(axis_name=)`` takes the mapped axis (``jax.vmap(...,
axis_name=)``, ``shard_map``) over which its sum of squares is
``lax.psum``-ed. Here, one rank alone, the mean is over the rank's own
channels.

bf16 weights, activations and residual stream; norms, ``Delta``, the state,
the router's logits and softmax and the loss in float32. Parameters: ``l{i}``
per-layer dicts (``init_params``) or each run of one mixer stacked
(``stacked_init_params``: ``run{r}`` beside ``vec{r}`` and ``out{r}``, walked with
``models/layers.py:scan_blocks`` in the model's order). ``loss_fn`` takes
either.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from tepdist_tpu.models import decoder, mellum
from tepdist_tpu.models.afmoe import swiglu
from tepdist_tpu.models.decoder import (
    EXPERT_LEAVES,
    fake_batch,  # noqa: F401 (the model's, as every decoder's)
    held_heads,
    held_weights,
    layer_dicts,
    run_stacks,
    stack_layers,
    walk_layers,
)
from tepdist_tpu.models.layers import cross_entropy, gqa_heads, part, rms_norm
from tepdist_tpu.models.nemotron_h import mamba2
from tepdist_tpu.ops.grouped_matmul import routed_experts
from tepdist_tpu.ops.pallas.ssd_attention import CHUNK
from tepdist_tpu.telemetry import traced

traced.declare(
    "ssd_heads_held", "Mamba-2 heads a mixer that is divided over the ranks "
    "of a layer holds here")
traced.declare(
    "moe_choices", "expert choices a micro batch and expert part: its "
    "tokens times the experts a token")
traced.declare(
    "moe_experts_held", "routed experts an expert part holds here of its "
    "router's")

MAMBA, ATTN = "mamba", "attention"
# The start of a head's step: the published module's ``time_step_min`` and
# ``time_step_max`` (constants of its ``__init__``, no keys of the config) and
# the Mamba-2 reference's floor.
STEP_MIN, STEP_MAX, STEP_FLOOR = 0.001, 0.1, 1e-4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    layer_types: Tuple[str, ...] = ((MAMBA,) * 5 + (ATTN,) + (MAMBA,) * 4) * 4
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 768         # one routed expert's width
    shared_intermediate_size: int = 1536
    num_experts: int = 72                # the router's width
    experts_held: Tuple[int, int] = (0, 72)    # (first, count) held here
    num_experts_per_tok: int = 10
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Flash attention tile sizes (0 = kernel default), the state-space
    # kernels' chunk, every block rematerialised in the backward pass
    # (layers.scan_blocks) and the loss chunk: gpt2.GPT2Config's vocabulary.
    flash_block_q: int = 0
    flash_block_k: int = 0
    ssd_chunk: int = CHUNK
    remat: bool = False
    loss_chunk: int = 0
    # Rows of a grouped-matmul tile; every expert's rows are padded to it.
    moe_tile_m: int = 128

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)


CONFIGS: Dict[str, GraniteHybridConfig] = {
    "4.0-h-small": GraniteHybridConfig(),
    # A whole small model that two ranks divide: two Mamba-2 layers before
    # one attention layer (runs of 2 and 1), four query heads a key/value
    # head, 10 of 16 experts a token. A rank's Mamba-2 heads fill a block of
    # 128 lanes and ``B`` and ``C`` another (the conv's channels come in such
    # blocks), whole or divided.
    "test": GraniteHybridConfig(
        vocab_size=512, hidden_size=64,
        layer_types=(MAMBA, MAMBA, ATTN), mamba_n_heads=8,
        mamba_d_head=32, mamba_d_state=64, num_attention_heads=8,
        num_key_value_heads=2, head_dim=8, intermediate_size=24,
        shared_intermediate_size=32, num_experts=16, experts_held=(0, 16),
        dtype=jnp.float32, ssd_chunk=16, moe_tile_m=8),
}
_OUTSIDE_BLOCKS = ("tok_emb", "norm_f")
GROUPS = ("run", "vec", "out")
# A Mamba-2 mixer's float32 vectors (with the conv's bias) and its output
# projection lie in groups of their own (``vec{r}``, ``out{r}``), so that a
# check of a step can name them without the run's experts
# (``models/decoder.py``).
_GROUP_OF = {"A_log": "vec", "D": "vec", "dt_bias": "vec", "conv_b": "vec",
             "w_out": "out"}


def _mixer_params(cfg: GraniteHybridConfig, mixer: str, keys, norm):
    d = cfg.hidden_size
    f32 = jnp.float32
    if mixer == ATTN:
        H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        return {"wq": norm(keys[0], (d, H * hd)),
                "wk": norm(keys[1], (d, Hkv * hd)),
                "wv": norm(keys[2], (d, Hkv * hd)),
                "wo": norm(keys[3], (H * hd, d))}
    H, P = cfg.mamba_n_heads, cfg.mamba_d_head
    wide = H * P + 2 * cfg.mamba_n_groups * cfg.mamba_d_state
    # As ``models/nemotron_h.py`` starts them (the Mamba-2 reference's): A =
    # 1 .. H, D = 1, and the step's bias the inverse softplus of exp(U(log
    # min, log max)), floored. The published module leaves ``dt_bias`` at 1
    # for a checkpoint to overwrite.
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        keys[5], (H,), f32, jnp.log(STEP_MIN), jnp.log(STEP_MAX))),
        STEP_FLOOR)
    return {"w_z": norm(keys[0], (d, H * P)),
            "w_xbc": norm(keys[1], (d, wide)),
            "w_dt": norm(keys[2], (d, H)),
            "conv": jax.random.uniform(
                keys[3], (cfg.mamba_d_conv, wide), f32, -0.5, 0.5).astype(
                    cfg.dtype),
            "conv_b": norm(keys[6], (wide,)),
            "A_log": jnp.log(jnp.arange(1, H + 1, dtype=f32)),
            "D": jnp.ones((H,), f32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "ssm_norm": jnp.ones((H * P,), f32),
            "w_out": norm(keys[4], (H * P, d))}


def init_params(cfg: GraniteHybridConfig, key, std: float = 0.02):
    """normal(std) matrices and conv bias, conv taps U(-1/2, 1/2), unit norm
    gains, ``A_log``, ``D`` and ``dt_bias`` as above; ``l{i}`` per-layer
    dicts. The head is ``tok_emb``."""
    d = cfg.hidden_size
    f, fs = cfg.intermediate_size, cfg.shared_intermediate_size
    E, G = cfg.num_experts, cfg.experts_held[1]
    keys = jax.random.split(key, 1 + cfg.num_hidden_layers)

    def norm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(
            cfg.dtype)

    def ones():              # a buffer each: a plan donates every leaf
        return jnp.ones((d,), jnp.float32)

    params: Dict[str, Any] = {"tok_emb": norm(keys[0], (cfg.vocab_size, d)),
                              "norm_f": ones()}
    for i, mixer in enumerate(cfg.layer_types):
        lk = jax.random.split(keys[1 + i], 14)
        params[f"l{i}"] = {
            "input_ln": ones(), "post_attn_ln": ones(),
            **_mixer_params(cfg, mixer, lk[:7], norm),
            "router": norm(lk[7], (d, E)),
            "shared_gate": norm(lk[8], (d, fs)),
            "shared_up": norm(lk[9], (d, fs)),
            "shared_down": norm(lk[10], (fs, d)),
            "w_gate": norm(lk[11], (G, d, f)),
            "w_up": norm(lk[12], (G, d, f)),
            "w_down": norm(lk[13], (G, f, d))}
    return params


def stacked(params, cfg: GraniteHybridConfig):
    """``l{i}`` dicts -> each run of one mixer stacked, [layers of the run,
    ...] a leaf, under ``run{r}`` and (``_GROUP_OF``) ``vec{r}``, ``out{r}``."""
    return stack_layers(params, run_stacks(cfg.layer_types), _OUTSIDE_BLOCKS,
                        GROUPS, _GROUP_OF)


def stacked_init_params(cfg: GraniteHybridConfig, key, std: float = 0.02):
    """``init_params`` in the stacked layout (:func:`stacked`)."""
    return stacked(init_params(cfg, key, std), cfg)


def rank_share(params, cfg: GraniteHybridConfig, rank: int, of: int):
    """From the ``l{i}`` parameters of ``cfg`` (a whole model) what rank
    ``rank`` of the ``of`` that share each layer holds, and that rank's
    configuration: its ``1 / of`` of the Mamba-2 heads (columns of ``w_z``,
    ``w_dt`` and ``w_xbc``'s ``u`` with their conv channels, ``dt_bias``,
    ``A_log``, ``D``, the gated norm's gain, rows of ``w_out``; ``B`` and
    ``C`` whole), of the query and key/value heads, of the experts and of
    the vocabulary's rows; the norms, the router and the shared MLP whole."""
    counts = (cfg.mamba_n_heads, cfg.num_attention_heads,
              cfg.num_key_value_heads, cfg.num_experts, cfg.vocab_size)
    if cfg.mamba_n_groups != 1 or any(n % of for n in counts) \
            or cfg.experts_held != (0, cfg.num_experts):
        raise ValueError(f"{of} ranks do not divide a whole model of one "
                         f"group evenly: {counts}")
    H, Hq, Hkv, E, V = (n // of for n in counts)
    P, hd = cfg.mamba_d_head, cfg.head_dim
    inner = cfg.mamba_n_heads * P

    def mine(w, count, width=1, axis=-1):
        return held_heads(w, (rank * count, count), width, axis)

    def u_b_c(w):            # the held heads' ``u`` beside ``B`` and ``C``
        return jnp.concatenate(
            [mine(w[..., :inner], H, P), w[..., inner:]], axis=-1)

    out = {"tok_emb": mine(params["tok_emb"], V, axis=0),
           "norm_f": params["norm_f"]}
    for i, mixer in enumerate(cfg.layer_types):
        blk = dict(params[f"l{i}"])
        if mixer == ATTN:
            blk.update(wq=mine(blk["wq"], Hq, hd), wk=mine(blk["wk"], Hkv, hd),
                       wv=mine(blk["wv"], Hkv, hd),
                       wo=mine(blk["wo"], Hq, hd, axis=0))
        else:
            blk.update(
                w_z=mine(blk["w_z"], H, P), w_dt=mine(blk["w_dt"], H),
                w_xbc=u_b_c(blk["w_xbc"]), conv=u_b_c(blk["conv"]),
                conv_b=u_b_c(blk["conv_b"]), A_log=mine(blk["A_log"], H),
                D=mine(blk["D"], H), dt_bias=mine(blk["dt_bias"], H),
                ssm_norm=mine(blk["ssm_norm"], H, P),
                w_out=mine(blk["w_out"], H, P, axis=0))
        for k in EXPERT_LEAVES:
            blk[k] = blk[k][rank * E:(rank + 1) * E]
        out[f"l{i}"] = blk
    return out, dataclasses.replace(
        cfg, mamba_n_heads=H, num_attention_heads=Hq,
        num_key_value_heads=Hkv, experts_held=(rank * E, E), vocab_size=V)


def mamba(blk, a, cfg: GraniteHybridConfig):
    """a [B, T, d] (the normed input) -> the held heads' part of the
    Mamba-2 mixer's output through ``w_out``."""
    traced.note("ssd_heads_held", cfg.mamba_n_heads)
    return mamba2(blk, a, heads=cfg.mamba_n_heads, head_dim=cfg.mamba_d_head,
                  groups=cfg.mamba_n_groups, states=cfg.mamba_d_state,
                  chunk=cfg.ssd_chunk, eps=cfg.rms_norm_eps)


def attention(blk, a, cfg: GraniteHybridConfig):
    """a [B, T, d] (the normed input) -> the held heads through ``wo``:
    causal, no positional embedding, the scores times
    ``attention_multiplier``."""
    o = gqa_heads(
        blk, a, n_head=cfg.num_attention_heads,
        n_kv_head=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        eps=cfg.rms_norm_eps, window=0, windowed=False,
        block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
        scale=cfg.attention_multiplier)
    with jax.named_scope("attn_out"):
        return o @ blk["wo"]


def moe(blk, x, cfg: GraniteHybridConfig):
    """x [B, T, d] -> the held routed experts' part of the layer's output
    plus the shared MLP's."""
    B, T, d = x.shape
    h = x.reshape(B * T, d)
    traced.note("moe_choices", B * T * cfg.num_experts_per_tok)
    traced.note("moe_experts_held", cfg.experts_held[1])
    with jax.named_scope("moe_router"):
        weights, experts = mellum.router(blk, h, cfg)
        weights = held_weights(weights, experts, cfg.experts_held,
                               cfg.num_experts)
    y = routed_experts(h, weights, experts, blk["w_gate"], blk["w_up"],
                       blk["w_down"], cfg.num_experts, cfg.moe_tile_m,
                       held=cfg.experts_held)
    with jax.named_scope("moe_shared"):
        y = y + swiglu(h, blk["shared_gate"], blk["shared_up"],
                       blk["shared_down"])
    return y.reshape(B, T, d)


_MIXERS = {MAMBA: mamba, ATTN: attention}


def _add(x, y, cfg: GraniteHybridConfig):
    """``x + residual_multiplier * y``, float32 inside."""
    return (x.astype(jnp.float32) + cfg.residual_multiplier
            * y.astype(jnp.float32)).astype(x.dtype)


def block(blk, x, cfg: GraniteHybridConfig, mixer: str):
    """One layer of either mixer."""
    eps = cfg.rms_norm_eps
    with part("mixer"):
        x = _add(x, _MIXERS[mixer](
            blk, rms_norm(x, blk["input_ln"], eps), cfg), cfg)
    with part("moe"):
        return _add(x, moe(blk, rms_norm(x, blk["post_attn_ln"], eps), cfg),
                    cfg)


def _embed(params, tokens, cfg: GraniteHybridConfig):
    return (params["tok_emb"][tokens].astype(jnp.float32)
            * cfg.embedding_multiplier).astype(cfg.dtype)


def hidden_states(params, tokens, cfg: GraniteHybridConfig):
    """tokens int32 [B, T] -> the final normalised hidden over
    ``logits_scaling`` [B, T, d] (the division folded into the norm's gain:
    ``logits / 16 = (h / 16) E^T``)."""
    with part("embed"):
        x = _embed(params, tokens, cfg)
    x = walk_layers(lambda blk, h, kind: block(blk, h, cfg, kind), x,
                    params, run_stacks(cfg.layer_types), cfg.layer_types,
                    cfg.remat, GROUPS, experts=EXPERT_LEAVES)
    with part("head_loss"):
        return rms_norm(x, params["norm_f"] / cfg.logits_scaling,
                        cfg.rms_norm_eps)


def forward(params, tokens, cfg: GraniteHybridConfig):
    """tokens int32 [B, T] -> float32 logits [B, T, V] over the held rows
    of the vocabulary."""
    x = hidden_states(params, tokens, cfg)
    return (x @ params["tok_emb"].T).astype(jnp.float32)


def loss_fn(params, tokens, cfg: GraniteHybridConfig):
    """Cross entropy of tokens [B, T+1] through the tied head."""
    x = hidden_states(params, tokens[:, :-1], cfg)
    return cross_entropy(x, params["tok_emb"], tokens[:, 1:], cfg.loss_chunk)


def expert_choices(params, tokens, cfg: GraniteHybridConfig):
    """tokens int32 [B, T] -> the expert ids every layer's router chose,
    int32 [L, B * T, k]; the forward pass alone, no host value in it (it
    can be jitted)."""
    eps = cfg.rms_norm_eps
    x = _embed(params, tokens, cfg)
    S = x.shape[0] * x.shape[1]
    ids = []
    for blk, mixer in zip(
            layer_dicts(params, run_stacks(cfg.layer_types), GROUPS),
            cfg.layer_types):
        mid = _add(x, _MIXERS[mixer](
            blk, rms_norm(x, blk["input_ln"], eps), cfg), cfg)
        h = rms_norm(mid, blk["post_attn_ln"], eps)
        ids.append(mellum.router(blk, h.reshape(S, -1), cfg)[1])
        x = _add(mid, moe(blk, h, cfg), cfg)
    return jnp.stack(ids)


# What the routers did with ``tokens`` [B, T+1], outside any step
# (``models/decoder.py:routing_stats`` over this model's choices): the rows
# each held expert got and the live share of the tiles laid out.
routing_stats = functools.partial(decoder.routing_stats, expert_choices)
