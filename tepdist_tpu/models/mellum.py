"""Mellum2 (JetBrains ``Mellum2-12B-A2.5B``, ``model_type: mellum``: 28
layers, hidden 2304, 32 query and 4 key/value heads of 128, every layer 64
routed SwiGLU experts of 896, 8 a token, no dense layer and no shared
expert; three window-1024 layers to one global layer, whose rotary table is
YaRN's).

Per layer, pre-norm, RMSNorm without biases:

    a = rms(x; input_ln)
    q, k, v = rms_head(a Wq), rms_head(a Wk), a Wv     (QK-norm over each head)
    window layer: q, k = rope(q, k; the plain table); key j visible to query
                  i iff 0 <= i - j < sliding_window
    global layer: q, k = rope(q, k; the YaRN table); causal
    x = x + flash(q, k, v) Wo
    h = rms(x; post_attention_ln)
    p = softmax_f32(h Wr);  e = top_k(p);  w = p[e] / sum(p[e])
    x = x + sum_j w_j . Wd[e_j] (silu(Wg[e_j] h) * Wu[e_j] h)

with ``x0 = tok_emb[tokens]``, a final RMSNorm and an untied head. The loss
is the cross entropy alone: there is no auxiliary loss. The two tables
(``models/layers.py:rope``, ``yarn_table``) differ in what they do only past
the YaRN table's original context: a sequence inside it turns every pair
that matters to it at the plain rate.

**The expert layer is told which experts it holds** (``experts_held =
(first, count)`` of the router's ``num_experts``), as ``models/afmoe.py``'s:
it routes over all of them, the normalising sum runs over all k choices, and
it computes the part of the result its own experts give
(``ops/grouped_matmul.py:routed_experts``: dropless, no capacity). Nothing
stands in for the other ranks or for the exchange with them.

bf16 weights and activations; norms, the router's softmax and the loss in
float32. Parameters: ``l{i}`` per-layer dicts (``init_params``) or one
``blocks`` dict of ``[L, ...]`` leaves walked with ``lax.scan``
(``stacked_init_params``); window and global layers differ in no shape and
the body chooses by ``lax.cond`` on its layer's kind
(``models/layers.py:scan_blocks``). ``loss_fn`` takes either.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from tepdist_tpu.models import decoder
from tepdist_tpu.models.decoder import (
    EXPERT_LEAVES,
    fake_batch,  # noqa: F401 (the model's, as every decoder's)
    held_weights,
    layer_dicts,
    stack_layers,
    walk_layers,
)
from tepdist_tpu.models.layers import (
    RopeTable,
    cross_entropy,
    gqa_heads,
    part,
    rms_norm,
    yarn_table,
)
from tepdist_tpu.ops.grouped_matmul import routed_experts
from tepdist_tpu.ops.pallas.router_choice import choose

WINDOW, GLOBAL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    moe_intermediate_size: int = 896     # one expert's width
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, GLOBAL) * 7
    num_experts: int = 64                # the router's width
    experts_held: Tuple[int, int] = (0, 64)    # (first, count) held here
    num_experts_per_tok: int = 8
    sliding_window: int = 1024
    rope_theta: float = 500000.0         # both kinds' base
    # The global layers' YaRN table (``rope_parameters.full_attention``);
    # factor 1 is the plain table.
    yarn_factor: float = 16.0
    yarn_original_max_position: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: Optional[float] = 1.2772588722239782
    rms_norm_eps: float = 1e-6
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
    moe_tile_m: int = 256

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def global_rope(self) -> RopeTable:
        return yarn_table(
            self.head_dim, self.rope_theta, self.yarn_factor,
            self.yarn_original_max_position, self.yarn_beta_fast,
            self.yarn_beta_slow, self.yarn_attention_factor)


CONFIGS: Dict[str, MellumConfig] = {
    "2-12b-a2.5b": MellumConfig(),
    # The YaRN table's original context is 8 of the tests' 32 positions.
    "test": MellumConfig(
        vocab_size=512, hidden_size=64, moe_intermediate_size=32,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        layer_types=(WINDOW, GLOBAL, WINDOW), num_experts=16,
        experts_held=(4, 4), num_experts_per_tok=2, sliding_window=8,
        rope_theta=100.0, yarn_factor=4.0, yarn_original_max_position=8,
        yarn_beta_fast=2.0, yarn_beta_slow=0.5, yarn_attention_factor=None,
        dtype=jnp.float32, moe_tile_m=8),
}

_OUTSIDE_BLOCKS = ("tok_emb", "norm_f", "lm_head")


def init_params(cfg: MellumConfig, key, std: float = 0.02) -> Dict[str, Any]:
    """normal(std) weights, unit norm gains; ``l{i}`` per-layer dicts."""
    d, hd, f = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    E, G = cfg.num_experts, cfg.experts_held[1]
    keys = jax.random.split(key, 2 + cfg.num_hidden_layers)

    def norm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(
            cfg.dtype)

    def ones(n=d):           # a buffer each: a plan donates every leaf
        return jnp.ones((n,), jnp.float32)

    params: Dict[str, Any] = {
        "tok_emb": norm(keys[0], (cfg.vocab_size, d)),
        "norm_f": ones(),
        "lm_head": norm(keys[1], (cfg.vocab_size, d)),
    }
    for i in range(cfg.num_hidden_layers):
        lk = jax.random.split(keys[2 + i], 8)
        params[f"l{i}"] = {
            "input_ln": ones(), "post_attn_ln": ones(),
            "q_norm": ones(hd), "k_norm": ones(hd),
            "wq": norm(lk[0], (d, H * hd)), "wk": norm(lk[1], (d, Hkv * hd)),
            "wv": norm(lk[2], (d, Hkv * hd)), "wo": norm(lk[3], (H * hd, d)),
            "router": norm(lk[4], (d, E)),
            "w_gate": norm(lk[5], (G, d, f)),
            "w_up": norm(lk[6], (G, d, f)),
            "w_down": norm(lk[7], (G, f, d)),
        }
    return params


def stacked_init_params(cfg: MellumConfig, key, std: float = 0.02):
    """``init_params`` with the layers stacked: ``blocks`` [L, ...]."""
    return stack_layers(init_params(cfg, key, std), _stacks(cfg),
                        _OUTSIDE_BLOCKS)


def _stacks(cfg: MellumConfig):
    """One stack, every layer: (name, first layer, layers)."""
    return (("blocks", 0, cfg.num_hidden_layers),)


def attention(blk, a, cfg: MellumConfig, window):
    """a [B, T, d] (the normed input) -> the heads through ``wo``.
    ``window``: this layer's kind, a bool or a traced scalar."""
    o = gqa_heads(
        blk, a, n_head=cfg.num_attention_heads,
        n_kv_head=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        eps=cfg.rms_norm_eps, window=cfg.sliding_window, windowed=window,
        rope_window=cfg.rope_theta, rope_global=cfg.global_rope,
        block_q=cfg.flash_block_q, block_k=cfg.flash_block_k)
    return o @ blk["wo"]


def router(blk, h, cfg: MellumConfig):
    """h [S, d] -> (weights [S, k], expert ids [S, k]): the top k of the
    float32 softmax over all experts, normalised over the k chosen."""
    logits = jnp.dot(h, blk["router"], preferred_element_type=jnp.float32)
    chosen, experts = choose(jax.nn.softmax(logits, axis=-1),
                             cfg.num_experts_per_tok)
    return chosen / chosen.sum(-1, keepdims=True), experts


def moe(blk, x, cfg: MellumConfig):
    """x [B, T, d] -> the held routed experts' part of the layer's output."""
    B, T, d = x.shape
    h = x.reshape(B * T, d)
    with jax.named_scope("moe_router"):
        weights, experts = router(blk, h, cfg)
        weights = held_weights(weights, experts, cfg.experts_held,
                               cfg.num_experts)
    y = routed_experts(h, weights, experts, blk["w_gate"], blk["w_up"],
                       blk["w_down"], cfg.num_experts, cfg.moe_tile_m,
                       held=cfg.experts_held)
    return y.reshape(B, T, d)


def block(blk, x, cfg: MellumConfig, window):
    eps = cfg.rms_norm_eps
    with part("mixer"):
        x = x + attention(blk, rms_norm(x, blk["input_ln"], eps), cfg,
                          window)
    with part("moe"):
        return x + moe(blk, rms_norm(x, blk["post_attn_ln"], eps), cfg)


def hidden_states(params, tokens, cfg: MellumConfig):
    """tokens int32 [B, T] -> final normalised hidden [B, T, d]."""
    with part("embed"):
        x = params["tok_emb"][tokens].astype(cfg.dtype)
    x = walk_layers(lambda blk, h, window: block(blk, h, cfg, window), x,
                    params, _stacks(cfg),
                    [t == WINDOW for t in cfg.layer_types], cfg.remat,
                    experts=EXPERT_LEAVES)
    with part("head_loss"):
        return rms_norm(x, params["norm_f"], cfg.rms_norm_eps)


def forward(params, tokens, cfg: MellumConfig):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    x = hidden_states(params, tokens, cfg)
    return (x @ params["lm_head"].T).astype(jnp.float32)


def loss_fn(params, tokens, cfg: MellumConfig):
    """Cross entropy of tokens [B, T+1]."""
    x = hidden_states(params, tokens[:, :-1], cfg)
    return cross_entropy(x, params["lm_head"], tokens[:, 1:], cfg.loss_chunk)


def expert_choices(params, tokens, cfg: MellumConfig):
    """tokens int32 [B, T] -> the expert ids every layer's router chose,
    int32 [L, B * T, k]; the forward pass alone, no host value in it (it
    can be jitted)."""
    eps = cfg.rms_norm_eps
    x = params["tok_emb"][tokens].astype(cfg.dtype)
    S = x.shape[0] * x.shape[1]
    ids = []
    for blk, kind in zip(layer_dicts(params, _stacks(cfg)),
                         cfg.layer_types):
        mid = x + attention(blk, rms_norm(x, blk["input_ln"], eps), cfg,
                            kind == WINDOW)
        h = rms_norm(mid, blk["post_attn_ln"], eps)
        ids.append(router(blk, h.reshape(S, -1), cfg)[1])
        x = mid + moe(blk, h, cfg)
    return jnp.stack(ids)


# What the routers did with ``tokens`` [B, T+1], outside any step
# (``models/decoder.py:routing_stats`` over this model's choices).
routing_stats = functools.partial(decoder.routing_stats, expert_choices)
