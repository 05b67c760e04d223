"""ZAYA (Zyphra ZAYA1-8B, ``model_type: zaya``: 40 layers, hidden 2048, each
layer Compressed Convolutional Attention, 8 query heads over 2 key/value
heads of 128 inside a latent narrower than the hidden size, then a top-1
mixture of 16 SwiGLU experts of 2048 whose router is a small MLP that carries
its state from layer to layer; no dense MLP anywhere, no shared expert;
vocabulary 262,272, tied). From the published ``config.json`` and the two
publications its keys are the keys of: Compressed Convolutional Attention
(arXiv:2510.04476) and the ZAYA1 technical report (arXiv:2511.17127).

Per layer, ``x`` [T, d]; ``a_{-1} = 0``, ``r_{-1} = 0``, every convolution
sees zeros before the sequence:

    a    = rms(x; attn_ln)
    q0   = a Wq            [T, H, D]     the query latent, H D = d / 2
    k0   = a Wk            [T, Hkv, D]   the key latent, Hkv D = d / 8
    v_t  = [a_t Wva ; a_{t-1} Wvb]       the first half of the key/value heads
                                         from this token, the second from the
                                         token before (the value shift)
    q, k = mix([q0 ; k0])  two causal convs of 2 taps and the q-k mean
                           (``ops/pallas/cca_mix.py``, its docstring)
    q    = q / |q|_2 sqrt(D);  k = k / |k|_2 sqrt(D) tau_g     per head
    q, k = rope(q), rope(k)  on the first ``partial_rotary_factor`` of a head
    o_h  = softmax_causal(q_h k_g(h)^T D^-0.5) v_g(h)     the flash kernels
    x    = x + concat_h(o_h) Wo                           Wo [H D, d]

    h    = rms(x; moe_ln)
    r    = rms(h Wrd; router_ln) + gamma * r_{l-1}   [T, R]; to layer l + 1
    z    = gelu(gelu(r W1) W2) W3                    float32, exact (erf) GeLU
    p    = softmax(z);   e = argmax(p + b)           b: no gradient reaches it
    x    = x + p[e] * SwiGLU_e(h)                    one expert a token

with ``x0 = tok_emb[tokens]``, a final RMSNorm and the tied head; the loss is
the cross entropy alone. What the config does not fix (the norm's ``sqrt(D)``
and the temperature on keys alone, which half is shifted, the router's norm
and ``gamma``, ...) is listed with its reason in the benchmark's
``configs/zaya1-8b.json`` under ``assumed``.

**The walk carries two arrays**, ``(x, r)``: layer ``l``'s router mixes its
own down-projection with layer ``l - 1``'s state, so the state rides beside
the residual stream through ``models/decoder.py:walk_layers`` (a pytree
carry), a walked block's kept input is the pair, and the written-out backward
carries ``(dx, dr)``. ``r`` is float32, as the router's whole chain is.

**The mixing** runs on the kernel pair of ``ops/pallas/cca_mix.py`` where a
head is a multiple of 128 wide (the published width), else on its
``jax.numpy`` form (the tests' small presets). Both give ``q`` and ``k``
head-major, as the norm, the rotary embedding and the flash kernels take them.

**The selection bias** is Trinity's (``models/afmoe.py:count_choices``,
``optim.adamw_bf16_router_bias``): the loss hands each layer's counts to the
optimizer where the leaf's gradient would be. The gate is the chosen expert's
own softmax probability, unnormalised (normalised over one choice it would
be 1 and the router would have no gradient).

bf16 weights and activations; norms, the L2 norm, rotary, the router's whole
chain (its MLP's weights are float32 leaves and its matmuls run at
``precision=highest``: three ``[R, R]``-sized products a token), softmax
statistics and the loss in float32. Parameters: ``l{i}`` per-layer dicts
(``init_params``) or the layers stacked as ``blocks`` [layers, ...]
(``stacked_init_params``), walked with ``models/layers.py:scan_blocks``.
``loss_fn`` takes either.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from tepdist_tpu.models import decoder
from tepdist_tpu.models.afmoe import count_choices
from tepdist_tpu.models.decoder import (
    fake_batch,  # noqa: F401 (the model's, as every decoder's)
    layer_dicts,
    stack_layers,
    walk_layers,
)
from tepdist_tpu.models.layers import cross_entropy, part, rms_norm, rope
from tepdist_tpu.ops.grouped_matmul import layout_rows, routed_experts
from tepdist_tpu.ops.pallas import cca_mix
from tepdist_tpu.ops.pallas.flash_attention import flash_attention
from tepdist_tpu.telemetry import traced

traced.declare(
    "cca_latent_bytes", "bytes of the query, key and value latents [tokens, "
    "(H + 2 Hkv) D] one compressed-attention layer attends in, from a micro "
    "batch (a full-width layer's q, k, v are [tokens, 3 hidden])")
traced.declare(
    "router_carry_bytes", "bytes of the router's state [tokens, R] float32 "
    "a layer hands to the next: what a walk keeps of it a layer, beside x")
traced.declare(
    "moe_top1_rows", "rows of a top-1 expert layer's dropless layout: the "
    "micro batch's tokens and a tile's pads an expert")

_HIGHEST = jax.lax.Precision.HIGHEST
_L2_EPS = 1e-12          # against a zero vector only


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2                   # taps of the depth-wise conv
    cca_time1: int = 2                   # ... of the conv that mixes a head
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6
    router_hidden_size: int = 256
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Flash attention tile sizes (0 = kernel default), every block
    # rematerialised in the backward pass but for its attention kernels'
    # output and log-sum-exp (layers.scan_blocks), and the loss chunk:
    # gpt2.GPT2Config's vocabulary.
    flash_block_q: int = 0
    flash_block_k: int = 0
    remat: bool = False
    loss_chunk: int = 0
    # Rows of a grouped-matmul tile; every expert's rows are padded to it.
    moe_tile_m: int = 128

    def __post_init__(self):
        if (self.cca_time0, self.cca_time1) != (2, 2) \
                or self.num_experts_per_tok != 1 \
                or self.num_key_value_heads % 2 \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"ZayaConfig: convs of 2 and 2 taps, one expert "
                             f"a token and an even number of key/value heads "
                             f"that divides the query heads: {self}")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def experts_held(self) -> Tuple[int, int]:
        """Every expert is resident (``models/decoder.py:routing_stats``)."""
        return (0, self.num_experts)


CONFIGS: Dict[str, ZayaConfig] = {
    "8b": ZayaConfig(),
    # The published ratios small: 8 heads over 2, latents hidden / 2 and
    # hidden / 8, rotary on half a head, 16 experts, one a token.
    "test": ZayaConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=3, head_dim=8,
        rope_theta=100.0, router_hidden_size=16, moe_intermediate_size=64,
        dtype=jnp.float32, moe_tile_m=8),
}
CONFIGS["test-bf16"] = dataclasses.replace(CONFIGS["test"],
                                           dtype=jnp.bfloat16)
# Small around the published head width (128, which the mixing and the flash
# kernels compile for on the chip): ``chip_smoke.py``'s.
CONFIGS["smoke"] = dataclasses.replace(
    CONFIGS["test"], vocab_size=2048, hidden_size=256, head_dim=128,
    router_hidden_size=64, moe_intermediate_size=256, dtype=jnp.bfloat16,
    remat=True, loss_chunk=256, moe_tile_m=128)

_OUTSIDE_BLOCKS = ("tok_emb", "norm_f")
# The depth-wise taps' standard deviation: a tap is a gain on a channel, not
# a row of a matrix; at a matrix's 0.02 the convs' path would be a hundredth
# of the mean it is added to and no check would see it.
TAP_STD = 0.5


def init_params(cfg: ZayaConfig, key, std: float = 0.02) -> Dict[str, Any]:
    """normal(std) matrices (the router's MLP in float32), depth-wise taps
    normal(``TAP_STD``), unit norm gains and temperature, ``gamma`` 0.5, zero
    conv biases and selection bias; ``l{i}`` per-layer dicts."""
    d, D, R = cfg.hidden_size, cfg.head_dim, cfg.router_hidden_size
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    N, E, f = H + Hkv, cfg.num_experts, cfg.moe_intermediate_size
    keys = jax.random.split(key, 1 + cfg.num_hidden_layers)
    f32 = jnp.float32

    def norm(k, shape, dtype=cfg.dtype, s=std):
        return (jax.random.normal(k, shape, f32) * s).astype(dtype)

    def ones(n=d):           # a buffer each: a plan donates every leaf
        return jnp.ones((n,), f32)

    params: Dict[str, Any] = {"tok_emb": norm(keys[0], (cfg.vocab_size, d)),
                              "norm_f": ones()}
    for i in range(cfg.num_hidden_layers):
        lk = jax.random.split(keys[1 + i], 14)
        params[f"l{i}"] = {
            "attn_ln": ones(), "moe_ln": ones(),
            "wq": norm(lk[0], (d, H * D)), "wk": norm(lk[1], (d, Hkv * D)),
            "wva": norm(lk[2], (d, Hkv // 2 * D)),
            "wvb": norm(lk[3], (d, Hkv // 2 * D)),
            "wo": norm(lk[4], (H * D, d)),
            "conv_w1": norm(lk[5], (2, N * D), f32, TAP_STD),
            "conv_b1": jnp.zeros((N * D,), f32),
            "conv_w2": norm(lk[6], (2, N, D, D)),
            "conv_b2": jnp.zeros((N * D,), f32),
            "tau": ones(Hkv),
            "router_down": norm(lk[7], (d, R)),
            "router_ln": ones(R), "router_gamma": jnp.full((R,), 0.5, f32),
            "router_w1": norm(lk[8], (R, R), f32),
            "router_w2": norm(lk[9], (R, R), f32),
            "router_w3": norm(lk[10], (R, E), f32),
            "router_bias": jnp.zeros((E,), f32),
            "w_gate": norm(lk[11], (E, d, f)),
            "w_up": norm(lk[12], (E, d, f)),
            "w_down": norm(lk[13], (E, f, d)),
        }
    return params


def _stacks(cfg: ZayaConfig):
    """One stack, every layer: (name, first layer, layers)."""
    return (("blocks", 0, cfg.num_hidden_layers),)


def stacked_init_params(cfg: ZayaConfig, key, std: float = 0.02):
    """``init_params`` with the layers stacked: ``blocks`` [L, ...]."""
    return stack_layers(init_params(cfg, key, std), _stacks(cfg),
                        _OUTSIDE_BLOCKS)


def shifted(x):
    """[B, T, ...] -> row ``t - 1`` at row ``t``, zeros at row 0: inside
    each sequence of the batch."""
    return jnp.pad(x, ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))[:, :-1]


def l2_heads(t, scale):
    """t [..., D] -> float32 ``t / |t|_2 * scale`` over the last dim."""
    t32 = t.astype(jnp.float32)
    return t32 * (jax.lax.rsqrt(
        jnp.sum(t32 * t32, axis=-1, keepdims=True) + _L2_EPS) * scale)


def mix(blk, q0, k0, cfg: ZayaConfig):
    """The latents [B, T, H D], [B, T, Hkv D] -> q [B, H, T, D], k [B, Hkv,
    T, D] before their norm: the kernels, or their ``jax.numpy`` form."""
    operands = (q0, k0, blk["conv_w1"], blk["conv_b1"], blk["conv_w2"],
                blk["conv_b2"])
    if cfg.head_dim % cca_mix.LANES == 0:
        return cca_mix.cca_mix(*operands)
    return cca_mix.reference(*operands)


def attention(blk, x, cfg: ZayaConfig):
    """x [B, T, d] -> the compressed attention sublayer's output [B, T, d]
    (before the residual)."""
    B, T, _ = x.shape
    D, H, Hkv = cfg.head_dim, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    traced.note("cca_latent_bytes", B * T * (H + 2 * Hkv) * D
                * jnp.dtype(x.dtype).itemsize)
    a = rms_norm(x, blk["attn_ln"], cfg.rms_norm_eps)
    with jax.named_scope("cca_down"):
        q0, k0 = a @ blk["wq"], a @ blk["wk"]
        v = jnp.concatenate(
            [(a @ blk["wva"]).reshape(B, T, Hkv // 2, D),
             shifted(a @ blk["wvb"]).reshape(B, T, Hkv // 2, D)],
            axis=2).transpose(0, 2, 1, 3)
    with jax.named_scope("cca_mix"):
        q, k = mix(blk, q0, k0, cfg)
    with jax.named_scope("cca_norm_rope"):
        root = math.sqrt(D)
        q = l2_heads(q, root)
        k = l2_heads(k, root * blk["tau"].astype(jnp.float32)[:, None, None])
        q = rope(q, cfg.rope_theta, rotary_dim=cfg.rotary_dim).astype(x.dtype)
        k = rope(k, cfg.rope_theta, rotary_dim=cfg.rotary_dim).astype(x.dtype)
    o = flash_attention(q, k, v, causal=True, scale=D ** -0.5,
                        block_q=cfg.flash_block_q or None,
                        block_k=cfg.flash_block_k or None)
    with jax.named_scope("cca_out"):
        return o.transpose(0, 2, 1, 3).reshape(B, T, H * D) @ blk["wo"]


def router(blk, h, r_before, cfg: ZayaConfig):
    """h [S, d], the layer before's state ``r_before`` [S, R] -> (this
    layer's state r [S, R], float32 probabilities [S, E], the gate [S, 1],
    the expert id [S, 1]): the state mixed, the MLP over it, ``argmax(p +
    b)``, the gate the chosen expert's own probability."""
    f32 = jnp.float32
    down = jnp.dot(h, blk["router_down"], preferred_element_type=f32)
    r = rms_norm(down, blk["router_ln"], cfg.rms_norm_eps) \
        + blk["router_gamma"] * r_before

    def dense(t, w):
        return jnp.dot(t, w.astype(f32), precision=_HIGHEST)

    z = dense(jax.nn.gelu(dense(jax.nn.gelu(
        dense(r, blk["router_w1"]), approximate=False), blk["router_w2"]),
        approximate=False), blk["router_w3"])
    probs = jax.nn.softmax(z, axis=-1)
    experts = jnp.argmax(
        probs + jax.lax.stop_gradient(blk["router_bias"]), axis=-1,
        keepdims=True).astype(jnp.int32)
    # The choice's probability by compare and sum (``afmoe.router``).
    gate = jnp.sum(jnp.where(
        experts == jnp.arange(probs.shape[-1], dtype=experts.dtype),
        probs, 0.0), axis=-1, keepdims=True)
    return r, probs, count_choices(gate, blk["router_bias"], experts), experts


def expert_layer(blk, x, r_before, cfg: ZayaConfig):
    """x [B, T, d], r_before [B, T, R] -> (the chosen experts' output [B, T,
    d] before the residual, this layer's router state [B, T, R], the expert
    ids [B T, 1])."""
    B, T, d = x.shape
    S, R = B * T, cfg.router_hidden_size
    traced.note("router_carry_bytes", S * R * 4)
    traced.note("moe_top1_rows", layout_rows(
        S, 1, cfg.num_experts, cfg.num_experts, cfg.moe_tile_m)[0])
    h = rms_norm(x, blk["moe_ln"], cfg.rms_norm_eps).reshape(S, d)
    with jax.named_scope("zaya_router"):
        r, _, gate, experts = router(blk, h, r_before.reshape(S, R), cfg)
    y = routed_experts(h, gate, experts, blk["w_gate"], blk["w_up"],
                       blk["w_down"], cfg.num_experts, cfg.moe_tile_m)
    return y.reshape(B, T, d), r.reshape(B, T, R), experts


def block(blk, carry, cfg: ZayaConfig):
    """One layer: ``(x, r)`` in, ``(x, r)`` out."""
    x, r = carry
    with part("mixer"):
        x = x + attention(blk, x, cfg)
    with part("moe"):
        y, r, _ = expert_layer(blk, x, r, cfg)
        return x + y, r


def _start(params, tokens, cfg: ZayaConfig):
    """The walk's first carry: the embeddings and ``r_{-1} = 0``."""
    with part("embed"):
        x = params["tok_emb"][tokens].astype(cfg.dtype)
        return x, jnp.zeros(x.shape[:2] + (cfg.router_hidden_size,),
                            jnp.float32)


def hidden_states(params, tokens, cfg: ZayaConfig):
    """tokens int32 [B, T] -> final normalised hidden [B, T, d]."""
    x, _ = walk_layers(lambda blk, carry, _: block(blk, carry, cfg),
                       _start(params, tokens, cfg), params, _stacks(cfg),
                       [None] * cfg.num_hidden_layers, cfg.remat,
                       experts=decoder.EXPERT_LEAVES)
    with part("head_loss"):
        return rms_norm(x, params["norm_f"], cfg.rms_norm_eps)


def forward(params, tokens, cfg: ZayaConfig):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    x = hidden_states(params, tokens, cfg)
    return (x @ params["tok_emb"].T).astype(jnp.float32)


def loss_fn(params, tokens, cfg: ZayaConfig):
    """Cross entropy of tokens [B, T+1] over the tied embedding; the
    router's bias receives its step's counts where its gradient would be
    (``afmoe.count_choices``)."""
    x = hidden_states(params, tokens[:, :-1], cfg)
    return cross_entropy(x, params["tok_emb"], tokens[:, 1:], cfg.loss_chunk)


def expert_choices(params, tokens, cfg: ZayaConfig):
    """tokens int32 [B, T] -> the expert every layer's router chose, int32
    [layers, B * T, 1]; the forward pass alone."""
    x, r = _start(params, tokens, cfg)
    ids = []
    for blk in layer_dicts(params, _stacks(cfg)):
        x = x + attention(blk, x, cfg)
        y, r, experts = expert_layer(blk, x, r, cfg)
        x = x + y
        ids.append(experts)
    return jnp.stack(ids)


# What the routers did with ``tokens`` [B, T+1], outside any step
# (``models/decoder.py:routing_stats`` over this model's choices).
routing_stats = functools.partial(decoder.routing_stats, expert_choices)
