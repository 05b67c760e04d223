"""Qwen3-Next (Qwen ``Qwen3-Next-80B-A3B``, ``model_type: qwen3_next``: 48
layers, hidden 2048, three Gated-DeltaNet layers to each gated softmax
attention layer, every layer 512 routed SwiGLU experts of 512, 10 a token,
beside one shared expert scaled by a sigmoid of the token; vocabulary
151,936, untied).

Every norm but the gated one is zero-centred, ``rms0(x; w) = x / rms(x) *
(1 + w)`` (``models/layers.py:rms_norm0``). Layer ``i`` (from 0) is full
attention where ``(i + 1) % full_attention_interval == 0``. With ``a =
rms0(x; input_ln)``:

A **Gated-DeltaNet layer** (``Hk`` = 16 key heads under ``Hv`` = 32 value
heads of ``K = V`` = 128 channels; value head ``h`` reads key head ``h //
2``):

    [q~|k~|v~] = silu(conv4(a Wqkv))   one depth-wise causal conv over the
                               joined 8192 channels, 4 taps, no bias
    z, [b | al] = a Wz, a Wba
    q_j    = q~_j / |q~_j|_2 * K^-0.5       k_j = k~_j / |k~_j|_2
    g_h    = -exp(A_h) * softplus(al_h + dt_h)     float32: the log of ONE
                               decay in (0, 1) a value head and token
    beta_h = sigmoid(b_h)                          float32
    S_t    = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t    = S_t^T q_t                      (``ops/pallas/gdn_attention.py``)
    y_h    = o_h / rms(o_h) * o_norm * silu(z_h)   (not zero-centred)
    x      = x + concat_h(y_h) Wo

A **gated attention layer**: ``models/layers.py:gqa_heads`` (16 query heads
of 256 over 2 key/value heads, ``rms0`` over each head of q and k, rotary on
a head's first 64 channels) times ``sigmoid(a Wa)`` (``layers.attn_gate``),
through ``Wo``. Then in every layer ``h = rms0(x; post_attn_ln)``, Mellum2's
softmax router (``models/mellum.py:router``: the top 10 of 512, normalised
over the chosen) over the experts held here (``experts_held``) and ``x = x +
sum_j w_j expert_{e_j}(h) + sigmoid(h w_sg) * shared(h)``. ``x0 =
tok_emb[tokens]``, a final ``rms0``, an untied head, the cross entropy alone
(no auxiliary loss; the multi-token-prediction block is left out).

The columns of ``Wqkv``, ``Wz`` and ``Wba`` lie a kind after a kind where
the published ``in_proj_qkvz`` / ``in_proj_ba`` interleave them a key head,
and ``Wq`` / ``Wa`` are the query and the gate halves of the published
``q_proj``: permutations of columns.

bf16 weights and activations; norms, gates, decays, the state, the router's
softmax and the loss in float32. Parameters: ``l{i}`` per-layer dicts
(``init_params``) or **a stack a run of consecutive layers of one kind**
(``stacked_init_params``: ``run{r}``), each walked with
``models/layers.py:scan_blocks`` in the published order. ``loss_fn`` takes
either layout.
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
    held_weights,
    layer_dicts,
    run_stacks,
    stack_layers,
    walk_layers,
)
from tepdist_tpu.models.kimi_linear import gated_norm, l2_norm
from tepdist_tpu.models.layers import (
    attn_gate,
    cross_entropy,
    gqa_heads,
    part,
    rms_norm0,
)
from tepdist_tpu.ops.grouped_matmul import routed_experts
from tepdist_tpu.ops.pallas.causal_conv import causal_conv
from tepdist_tpu.ops.pallas.gdn_attention import CHUNK, gdn_attention
from tepdist_tpu.telemetry import traced

traced.declare(
    "gdn_state_bytes", "bytes of the float32 state [value heads, K, V] one "
    "Gated-DeltaNet layer leaves a sequence: what a stage hands on or a "
    "decode keeps")
traced.declare(
    "attn_rotary_dim", "channels of an attention head that are rotated (a "
    "partial rotary embedding: the rest carry no position)")

GDN, ATTN = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128       # = linear_value_head_dim
    linear_conv_kernel_dim: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    moe_intermediate_size: int = 512     # one routed expert's width
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512               # the router's width
    experts_held: Tuple[int, int] = (0, 512)   # (first, count) held here
    num_experts_per_tok: int = 10
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # Flash attention tile sizes (0 = kernel default), the delta-rule
    # kernels' chunk, every block rematerialised in the backward pass
    # (layers.scan_blocks) and the loss chunk: gpt2.GPT2Config's vocabulary.
    flash_block_q: int = 0
    flash_block_k: int = 0
    gdn_chunk: int = CHUNK
    remat: bool = False
    loss_chunk: int = 0
    # Rows of a grouped-matmul tile; every expert's rows are padded to it.
    moe_tile_m: int = 128

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Every layer's mixer, in order."""
        return tuple(
            ATTN if (i + 1) % self.full_attention_interval == 0 else GDN
            for i in range(self.num_hidden_layers))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)


CONFIGS: Dict[str, Qwen3NextConfig] = {
    "80b-a3b": Qwen3NextConfig(),
    # The published ratios small: one period, three Gated-DeltaNet layers
    # to one attention layer (runs of 3 and 1), two value heads a key head,
    # eight query heads a key/value head, a quarter of the head rotated, 10
    # experts a token, a rank's 16 of 32 experts.
    "test": Qwen3NextConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=4,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=32, num_attention_heads=8,
        num_key_value_heads=1, head_dim=16, rope_theta=100.0,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_experts=32, experts_held=(8, 16), num_experts_per_tok=10,
        dtype=jnp.float32, gdn_chunk=16, moe_tile_m=8),
}
CONFIGS["test_bf16"] = dataclasses.replace(CONFIGS["test"],
                                           dtype=jnp.bfloat16)
# Small around the published head widths (lane blocks of 128 for the delta
# rule, heads of 256 with 64 rotated for the flash kernels, which compile for
# those on the chip): ``chip_smoke.py``'s.
CONFIGS["smoke"] = dataclasses.replace(
    CONFIGS["test"], vocab_size=2048, hidden_size=256,
    linear_num_key_heads=1, linear_num_value_heads=2,
    linear_key_head_dim=128, num_attention_heads=2, num_key_value_heads=1,
    head_dim=256, moe_intermediate_size=128,
    shared_expert_intermediate_size=128, dtype=jnp.bfloat16,
    gdn_chunk=CHUNK, remat=True, loss_chunk=256, moe_tile_m=64)

_OUTSIDE_BLOCKS = ("tok_emb", "norm_f", "lm_head")
GROUPS = ("run",)


def _mixer_params(cfg: Qwen3NextConfig, mixer: str, keys, norm):
    d = cfg.hidden_size
    f32 = jnp.float32
    if mixer == ATTN:
        H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        return {"wq": norm(keys[0], (d, H * hd)),
                "wa": norm(keys[1], (d, H * hd)),
                "wk": norm(keys[2], (d, Hkv * hd)),
                "wv": norm(keys[3], (d, Hkv * hd)),
                "q_norm": jnp.zeros((hd,), f32),
                "k_norm": jnp.zeros((hd,), f32),
                "wo": norm(keys[4], (H * hd, d))}
    Hk, Hv, D = cfg.linear_num_key_heads, cfg.linear_num_value_heads, \
        cfg.linear_key_head_dim
    wide = 2 * Hk * D + Hv * D
    # As ``models/kimi_linear.py`` starts its decays (the ``fla`` layer's):
    # A = log U(1, 16) and dt the inverse softplus of exp(U(log 1e-3, log
    # 1e-1)), here one of each a value head.
    dt = jnp.exp(jax.random.uniform(keys[5], (Hv,), f32, jnp.log(1e-3),
                                    jnp.log(1e-1)))
    return {"wqkv": norm(keys[0], (d, wide)),
            "wz": norm(keys[1], (d, Hv * D)),
            "wba": norm(keys[2], (d, 2 * Hv)),
            "conv": norm(keys[3], (cfg.linear_conv_kernel_dim, wide)),
            "A_log": jnp.log(jax.random.uniform(keys[6], (Hv,), f32, 1.0,
                                                16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "o_norm": jnp.ones((D,), f32),
            "wo": norm(keys[4], (Hv * D, d))}


def init_params(cfg: Qwen3NextConfig, key, std: float = 0.02):
    """normal(std) matrices and conv taps, zero-centred norm leaves at 0, the
    gated norm's gain at 1, the decays' ``A_log`` and ``dt_bias`` as above;
    ``l{i}`` per-layer dicts."""
    d = cfg.hidden_size
    f, fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    E, G = cfg.num_experts, cfg.experts_held[1]
    keys = jax.random.split(key, 2 + cfg.num_hidden_layers)

    def norm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(
            cfg.dtype)

    def zeros():             # a buffer each: a plan donates every leaf
        return jnp.zeros((d,), jnp.float32)

    params: Dict[str, Any] = {
        "tok_emb": norm(keys[0], (cfg.vocab_size, d)),
        "norm_f": zeros(),
        "lm_head": norm(keys[1], (cfg.vocab_size, d)),
    }
    for i, mixer in enumerate(cfg.kinds):
        lk = jax.random.split(keys[2 + i], 15)
        params[f"l{i}"] = {
            "input_ln": zeros(), "post_attn_ln": zeros(),
            **_mixer_params(cfg, mixer, lk[:7], norm),
            "router": norm(lk[7], (d, E)),
            "shared_gate": norm(lk[8], (d, fs)),
            "shared_up": norm(lk[9], (d, fs)),
            "shared_down": norm(lk[10], (fs, d)),
            "shared_expert_gate": norm(lk[11], (d, 1)),
            "w_gate": norm(lk[12], (G, d, f)),
            "w_up": norm(lk[13], (G, d, f)),
            "w_down": norm(lk[14], (G, f, d))}
    return params


def stacked_init_params(cfg: Qwen3NextConfig, key, std: float = 0.02):
    """``init_params`` with each run of one kind stacked, [layers of the
    run, ...] a leaf, under ``run{r}``."""
    return stack_layers(init_params(cfg, key, std), run_stacks(cfg.kinds),
                        _OUTSIDE_BLOCKS, GROUPS)


def rank_share(params, cfg: Qwen3NextConfig, experts_held: Tuple[int, int]):
    """From the ``l{i}`` parameters of ``cfg`` (which holds every expert)
    what a rank holding ``experts_held`` has of them, and that rank's
    configuration: the held experts' weights and everything else (mixers,
    router, the shared expert and its gate) whole."""
    first, count = experts_held
    out = {k: params[k] for k in _OUTSIDE_BLOCKS}
    for i in range(cfg.num_hidden_layers):
        blk = dict(params[f"l{i}"])
        for k in EXPERT_LEAVES:
            blk[k] = blk[k][first:first + count]
        out[f"l{i}"] = blk
    return out, dataclasses.replace(cfg, experts_held=tuple(experts_held))


def gdn_gates(blk, ba):
    """``ba`` float32 [B, T, 2 Hv] (``a Wba``) -> (the log decays, ``beta``)
    float32 [B, T, Hv] each: ``-exp(A_h) * softplus(al_h + dt_h)``, at most
    0, and ``sigmoid(b_h)``."""
    Hv = ba.shape[-1] // 2
    g = -jnp.exp(blk["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., Hv:] + blk["dt_bias"])
    return g, jax.nn.sigmoid(ba[..., :Hv])


def gdn(blk, a, cfg: Qwen3NextConfig):
    """a [B, T, d] (the normed input) -> the Gated-DeltaNet mixer's output
    through ``wo``."""
    B, T, _ = a.shape
    Hk, Hv, D = cfg.linear_num_key_heads, cfg.linear_num_value_heads, \
        cfg.linear_key_head_dim
    traced.note("gdn_state_bytes", B * Hv * D * D * 4)
    with jax.named_scope("gdn_in"):
        qkv = a @ blk["wqkv"]
        ba = jnp.dot(a, blk["wba"], preferred_element_type=jnp.float32)
    with jax.named_scope("gdn_conv"):
        qkv = causal_conv(qkv, blk["conv"])
    with jax.named_scope("gdn_gates"):
        q = l2_norm(qkv[..., :Hk * D], Hk, D ** -0.5)
        k = l2_norm(qkv[..., Hk * D:2 * Hk * D], Hk)
        g, beta = gdn_gates(blk, ba)
    with jax.named_scope("gdn_core"):
        o = gdn_attention(q, k, qkv[..., 2 * Hk * D:], g, beta,
                          chunk=cfg.gdn_chunk)
    with jax.named_scope("gdn_out"):
        return gated_norm(o, a @ blk["wz"], blk["o_norm"], Hv,
                          cfg.rms_norm_eps, act=jax.nn.silu) @ blk["wo"]


def attention(blk, a, cfg: Qwen3NextConfig):
    """a [B, T, d] (the normed input) -> the gated heads through ``wo``."""
    traced.note("attn_rotary_dim", cfg.rotary_dim)
    o = gqa_heads(
        blk, a, n_head=cfg.num_attention_heads,
        n_kv_head=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        eps=cfg.rms_norm_eps, window=0, windowed=False,
        rope_global=cfg.rope_theta, block_q=cfg.flash_block_q,
        block_k=cfg.flash_block_k, rotary_dim=cfg.rotary_dim,
        norm=rms_norm0)
    o = attn_gate(o, a, blk["wa"])
    with jax.named_scope("attn_out"):
        return o @ blk["wo"]


def moe(blk, x, cfg: Qwen3NextConfig):
    """x [B, T, d] -> the held routed experts' part of the layer's output
    plus the shared expert's times ``sigmoid(x w_sg)``."""
    B, T, d = x.shape
    h = x.reshape(B * T, d)
    with jax.named_scope("moe_router"):
        weights, experts = mellum.router(blk, h, cfg)
        weights = held_weights(weights, experts, cfg.experts_held,
                               cfg.num_experts)
    y = routed_experts(h, weights, experts, blk["w_gate"], blk["w_up"],
                       blk["w_down"], cfg.num_experts, cfg.moe_tile_m,
                       held=cfg.experts_held)
    with jax.named_scope("moe_shared"):
        gate = jax.nn.sigmoid(jnp.dot(h, blk["shared_expert_gate"],
                                      preferred_element_type=jnp.float32))
        shared = swiglu(h, blk["shared_gate"], blk["shared_up"],
                        blk["shared_down"])
        y = y + (shared.astype(jnp.float32) * gate).astype(y.dtype)
    return y.reshape(B, T, d)


_MIXERS = {GDN: gdn, ATTN: attention}


def block(blk, x, cfg: Qwen3NextConfig, mixer: str):
    """One layer of either mixer."""
    eps = cfg.rms_norm_eps
    with part("mixer"):
        a = rms_norm0(x, blk["input_ln"], eps)
        x = x + _MIXERS[mixer](blk, a, cfg)
    with part("moe"):
        return x + moe(blk, rms_norm0(x, blk["post_attn_ln"], eps), cfg)


def hidden_states(params, tokens, cfg: Qwen3NextConfig):
    """tokens int32 [B, T] -> final normalised hidden [B, T, d]."""
    with part("embed"):
        x = params["tok_emb"][tokens].astype(cfg.dtype)
    x = walk_layers(lambda blk, h, kind: block(blk, h, cfg, kind), x,
                    params, run_stacks(cfg.kinds), cfg.kinds, cfg.remat,
                    GROUPS, experts=EXPERT_LEAVES)
    with part("head_loss"):
        return rms_norm0(x, params["norm_f"], cfg.rms_norm_eps)


def forward(params, tokens, cfg: Qwen3NextConfig):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    x = hidden_states(params, tokens, cfg)
    return (x @ params["lm_head"].T).astype(jnp.float32)


def loss_fn(params, tokens, cfg: Qwen3NextConfig):
    """Cross entropy of tokens [B, T+1]."""
    x = hidden_states(params, tokens[:, :-1], cfg)
    return cross_entropy(x, params["lm_head"], tokens[:, 1:], cfg.loss_chunk)


def expert_choices(params, tokens, cfg: Qwen3NextConfig):
    """tokens int32 [B, T] -> the expert ids every layer's router chose,
    int32 [L, B * T, k]; the forward pass alone, no host value in it (it
    can be jitted)."""
    eps = cfg.rms_norm_eps
    x = params["tok_emb"][tokens].astype(cfg.dtype)
    S = x.shape[0] * x.shape[1]
    ids = []
    for blk, mixer in zip(
            layer_dicts(params, run_stacks(cfg.kinds), GROUPS), cfg.kinds):
        a = rms_norm0(x, blk["input_ln"], eps)
        mid = x + _MIXERS[mixer](blk, a, cfg)
        h = rms_norm0(mid, blk["post_attn_ln"], eps)
        ids.append(mellum.router(blk, h.reshape(S, -1), cfg)[1])
        x = mid + moe(blk, h, cfg)
    return jnp.stack(ids)


# What the routers did with ``tokens`` [B, T+1], outside any step
# (``models/decoder.py:routing_stats`` over this model's choices): the rows
# each held expert got and the live share of the tiles laid out.
routing_stats = functools.partial(decoder.routing_stats, expert_choices)
