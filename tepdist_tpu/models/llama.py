"""Llama-style decoder family (RMSNorm, SwiGLU, rotary embeddings, GQA).

Beyond the reference's zoo (GPT-2/WRN/MoE): a modern-architecture flagship
exercising planner paths the GPT-2 graph does not — RMSNorm's rsqrt chain,
gated SwiGLU MLPs (three weight matmuls), rotary position application
(sin/cos + rotate-half concatenation), and grouped-query attention
(K/V head broadcasting). bf16 activations; einsum attention exposes clean
dims to the cone planner like gpt2.py."""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from tepdist_tpu.models.layers import rms_norm as _rms_norm, rope as _rope


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_ctx: int = 2048
    dim: int = 2048
    n_layer: int = 16
    n_head: int = 16
    n_kv_head: int = 4            # grouped-query attention
    ffn_mult: float = 2.6875      # hidden = mult * dim, rounded to 128
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    # "einsum" (planner-visible dots) or "flash" (pallas fused kernel,
    # applied after RoPE + GQA head broadcast; O(T) activation memory).
    attn: str = "einsum"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_head

    @property
    def ffn_dim(self) -> int:
        return int((self.ffn_mult * self.dim + 127) // 128 * 128)


CONFIGS: Dict[str, LlamaConfig] = {
    "1B": LlamaConfig(dim=2048, n_layer=16, n_head=16, n_kv_head=4),
    "7B": LlamaConfig(dim=4096, n_layer=32, n_head=32, n_kv_head=32,
                      ffn_mult=2.6875),
    "test": LlamaConfig(vocab_size=512, n_ctx=64, dim=64, n_layer=2,
                        n_head=4, n_kv_head=2, dtype=jnp.float32),
}


def init_params(cfg: LlamaConfig, key) -> Dict[str, Any]:
    d, hd = cfg.dim, cfg.head_dim
    kvd = cfg.n_kv_head * hd
    f = cfg.ffn_dim
    std = 1.0 / math.sqrt(d)
    keys = jax.random.split(key, 2 + cfg.n_layer)

    def norm(k, shape, s=std):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(
            cfg.dtype)

    params: Dict[str, Any] = {
        "tok_emb": norm(keys[0], (cfg.vocab_size, d), 0.02),
        "norm_f": jnp.ones((d,), jnp.float32),
        "lm_head": norm(keys[1], (d, cfg.vocab_size), std),
    }
    for i in range(cfg.n_layer):
        lk = jax.random.split(keys[2 + i], 7)
        params[f"l{i}"] = {
            "attn_norm": jnp.ones((d,), jnp.float32),
            "wq": norm(lk[0], (d, d)),
            "wk": norm(lk[1], (d, kvd)),
            "wv": norm(lk[2], (d, kvd)),
            "wo": norm(lk[3], (d, d), std / math.sqrt(2 * cfg.n_layer)),
            "ffn_norm": jnp.ones((d,), jnp.float32),
            "w_gate": norm(lk[4], (d, f)),
            "w_up": norm(lk[5], (d, f)),
            "w_down": norm(lk[6], (f, d), std / math.sqrt(2 * cfg.n_layer)),
        }
    return params


def _attention(blk, x, cfg: LlamaConfig):
    B, T, D = x.shape
    H, KV, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = (x @ blk["wq"]).reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    k = (x @ blk["wk"]).reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
    v = (x @ blk["wv"]).reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
    q = _rope(q, cfg.rope_theta)
    k = _rope(k, cfg.rope_theta)
    # GQA: broadcast each KV head over its query group.
    group = H // KV
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    if cfg.attn == "flash":
        from tepdist_tpu.ops.pallas.flash_attention import flash_attention
        o = flash_attention(q, k, v, causal=True)
    else:
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(
            jnp.float32) / math.sqrt(hd)
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask, s, -1e9)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, D)
    return o @ blk["wo"]


def _swiglu(blk, x):
    return (jax.nn.silu(x @ blk["w_gate"]) * (x @ blk["w_up"])) @ blk[
        "w_down"]


def forward(params, tokens, cfg: LlamaConfig):
    B, T = tokens.shape
    x = params["tok_emb"][tokens].astype(cfg.dtype)
    for i in range(cfg.n_layer):
        blk = params[f"l{i}"]
        x = x + _attention(blk, _rms_norm(x, blk["attn_norm"]), cfg)
        x = x + _swiglu(blk, _rms_norm(x, blk["ffn_norm"]))
    x = _rms_norm(x, params["norm_f"])
    return (x @ params["lm_head"]).astype(jnp.float32)


def loss_fn(params, tokens, cfg: LlamaConfig):
    logits = forward(params, tokens[:, :-1], cfg)
    targets = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def fake_batch(cfg: LlamaConfig, batch_size: int, seq_len: Optional[int] = None,
               seed: int = 0):
    T = seq_len or cfg.n_ctx
    key = jax.random.PRNGKey(seed)
    return jax.random.randint(key, (batch_size, T + 1), 0, cfg.vocab_size,
                              dtype=jnp.int32)
