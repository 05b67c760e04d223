"""OLMoE: a decoder whose feed-forward layers are dropless top-k routed
SwiGLU experts (Muennighoff et al. 2024, arXiv:2409.02060;
allenai/OLMoE-1B-7B: 16 layers, hidden 2048, 16 heads of 128, 64 experts of
width 1024, 8 a token).

Per layer, pre-norm, no biases:

    h = rms(x);  q, k = rms(h Wq), rms(h Wk)      (QK-norm over all 2048)
    x += Wo . flash(rope(q), rope(k), h Wv)
    h = rms(x);  p = softmax_f32(h Wr);  (w, e) = top_k(p)   (unrenormalised)
    x += sum_j w_j . Wd[e_j] (silu(Wg[e_j] h) * Wu[e_j] h)

then a final RMSNorm and an untied head. The expert layer is token choice
with **no dropped token** (``ops/grouped_matmul.py``): the ``S x k``
assignments are sorted by expert into a tile-aligned layout, three grouped
matmuls (``ops/pallas/grouped_matmul.py``) run over the 64 uneven groups,
and each token's k rows are weighted and summed. Nothing here has a
capacity.

Training loss = cross entropy + ``lb_coef`` x load-balancing loss +
``z_coef`` x router z-loss, the two averaged over layers:
``LB = E . sum_e f_e . pbar_e`` (``f_e`` the share of assignments that went
to expert e, no gradient; ``pbar_e`` the mean of ``p[:, e]``) and
``ZL = mean_t logsumexp(h_t Wr)^2``. Both are taken **per sequence** and
averaged over the batch (OLMoE's trainer takes them over a rank's micro
batch): the loss of a batch is then the mean of its sequences' losses, so
gradient accumulation gives the same step whatever the split.

bf16 weights and activations; norms, the router's softmax and the loss in
float32. Parameters: ``l{i}`` per-layer dicts (``init_params``) or one
``blocks`` dict of ``[L, ...]`` leaves walked with ``lax.scan``
(``stacked_init_params``); ``loss_fn`` takes either.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from tepdist_tpu.models.decoder import (
    fake_batch,  # noqa: F401 (the model's, as every decoder's)
    layer_dicts,
    stack_layers,
)
from tepdist_tpu.models.layers import (
    cross_entropy,
    part,
    rms_norm,
    rope,
    scan_blocks,
)
from tepdist_tpu.ops.grouped_matmul import (  # noqa: F401 — gated: tests
    gated,
    route,
    routed_experts,
)
from tepdist_tpu.ops.pallas.router_choice import choose


@dataclasses.dataclass(frozen=True)
class OlmoeConfig:
    vocab_size: int = 50304
    max_position_embeddings: int = 4096
    hidden_size: int = 2048
    intermediate_size: int = 1024        # one expert's width
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 8
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    lb_coef: float = 0.01
    z_coef: float = 0.001
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
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


CONFIGS: Dict[str, OlmoeConfig] = {
    "1B-7B": OlmoeConfig(),
    "test": OlmoeConfig(vocab_size=512, max_position_embeddings=64,
                        hidden_size=64, intermediate_size=32,
                        num_hidden_layers=2, num_attention_heads=4,
                        num_experts=8, num_experts_per_tok=2,
                        dtype=jnp.float32, moe_tile_m=8),
}

_OUTSIDE_BLOCKS = ("tok_emb", "norm_f", "lm_head")


def init_params(cfg: OlmoeConfig, key, std: float = 0.02) -> Dict[str, Any]:
    """normal(std) weights, unit norm gains; ``l{i}`` per-layer dicts."""
    d, f, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    keys = jax.random.split(key, 2 + cfg.num_hidden_layers)

    def norm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(
            cfg.dtype)

    def ones():              # a buffer each: a plan donates every leaf
        return jnp.ones((d,), jnp.float32)

    params: Dict[str, Any] = {
        "tok_emb": norm(keys[0], (cfg.vocab_size, d)),
        "norm_f": ones(),
        "lm_head": norm(keys[1], (cfg.vocab_size, d)),
    }
    for i in range(cfg.num_hidden_layers):
        lk = jax.random.split(keys[2 + i], 8)
        params[f"l{i}"] = {
            "attn_norm": ones(), "q_norm": ones(), "k_norm": ones(),
            "wq": norm(lk[0], (d, d)), "wk": norm(lk[1], (d, d)),
            "wv": norm(lk[2], (d, d)), "wo": norm(lk[3], (d, d)),
            "ffn_norm": ones(),
            "router": norm(lk[4], (d, E)),
            "w_gate": norm(lk[5], (E, d, f)),
            "w_up": norm(lk[6], (E, d, f)),
            "w_down": norm(lk[7], (E, f, d)),
        }
    return params


def stacked_init_params(cfg: OlmoeConfig, key, std: float = 0.02):
    """``init_params`` with the layers stacked: ``blocks`` [L, ...]."""
    return stack_layers(init_params(cfg, key, std), _stacks(cfg),
                        _OUTSIDE_BLOCKS)


def _stacks(cfg: OlmoeConfig):
    """One stack, every layer: (name, first layer, layers)."""
    return (("blocks", 0, cfg.num_hidden_layers),)


def attention(blk, x, cfg: OlmoeConfig):
    from tepdist_tpu.ops.pallas.flash_attention import flash_attention
    B, T, D = x.shape
    H, hd = cfg.num_attention_heads, cfg.head_dim
    eps = cfg.rms_norm_eps

    def heads(t):
        return t.reshape(B, T, H, hd).transpose(0, 2, 1, 3)

    q = rope(heads(rms_norm(x @ blk["wq"], blk["q_norm"], eps)),
             cfg.rope_theta)
    k = rope(heads(rms_norm(x @ blk["wk"], blk["k_norm"], eps)),
             cfg.rope_theta)
    o = flash_attention(q, k, heads(x @ blk["wv"]), causal=True,
                        block_q=cfg.flash_block_q or None,
                        block_k=cfg.flash_block_k or None)
    return o.transpose(0, 2, 1, 3).reshape(B, T, D) @ blk["wo"]


def router(blk, h, cfg: OlmoeConfig):
    """h [S, d] -> (float32 logits [S, E], probabilities [S, E], top-k
    weights [S, k] as they leave the softmax, expert ids [S, k])."""
    logits = jnp.dot(h, blk["router"], preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = choose(probs, cfg.num_experts_per_tok)
    return logits, probs, weights, experts


def moe(blk, x, cfg: OlmoeConfig):
    """x [B, T, d] -> (expert layer's output [B, T, d], load-balancing
    loss, router z-loss), the two losses per sequence, averaged."""
    B, T, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    h = x.reshape(B * T, d)
    with jax.named_scope("moe_router"):
        logits, probs, weights, experts = router(blk, h, cfg)
        share = jnp.mean(
            experts.reshape(B, T * k, 1) == jnp.arange(E), axis=1,
            dtype=jnp.float32)                              # f_e [B, E]
        lb = E * jnp.mean(jnp.sum(
            jax.lax.stop_gradient(share)
            * probs.reshape(B, T, E).mean(axis=1), axis=-1))
        zl = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    y = routed_experts(h, weights, experts, blk["w_gate"], blk["w_up"],
                       blk["w_down"], E, cfg.moe_tile_m)
    return y.reshape(B, T, d), lb, zl


def block(blk, x, cfg: OlmoeConfig):
    eps = cfg.rms_norm_eps
    with part("mixer"):
        x = x + attention(blk, rms_norm(x, blk["attn_norm"], eps), cfg)
    with part("moe"):
        y, lb, zl = moe(blk, rms_norm(x, blk["ffn_norm"], eps), cfg)
        return x + y, lb, zl


def hidden_states(params, tokens, cfg: OlmoeConfig):
    """tokens int32 [B, T] -> (final normalised hidden [B, T, d],
    load-balancing loss, router z-loss), the losses averaged over layers."""
    with part("embed"):
        x = params["tok_emb"][tokens].astype(cfg.dtype)

    def body(h, blk):
        h, lb, zl = block(blk, h, cfg)
        return h, (lb, zl)

    if "blocks" in params:
        # The experts' leaves are scanned a slice a layer. The kernels can
        # read them in place (``scan_blocks(in_place=)``, as the decoders of
        # ``models/decoder.py:walk_layers`` do); this walk takes that form
        # once the benchmark's reader of the grouped matmuls' roofline
        # follows a rank-4 weight operand (PERF.md section 7 (0)).
        x, aux = scan_blocks(body, x, params["blocks"], remat=cfg.remat)
        lb, zl = (a.mean() for a in aux)
    else:
        if cfg.remat:
            body = jax.checkpoint(body)
        aux = []
        for i in range(cfg.num_hidden_layers):
            x, layer_aux = body(x, params[f"l{i}"])
            aux.append(layer_aux)
        lb, zl = (sum(a) / len(aux) for a in zip(*aux))
    with part("head_loss"):
        return rms_norm(x, params["norm_f"], cfg.rms_norm_eps), lb, zl


def forward(params, tokens, cfg: OlmoeConfig):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    x, _, _ = hidden_states(params, tokens, cfg)
    return (x @ params["lm_head"].T).astype(jnp.float32)


def loss_terms(params, tokens, cfg: OlmoeConfig):
    """(cross entropy, load-balancing loss, router z-loss) of tokens
    [B, T+1]."""
    x, lb, zl = hidden_states(params, tokens[:, :-1], cfg)
    ce = cross_entropy(x, params["lm_head"], tokens[:, 1:], cfg.loss_chunk)
    return ce, lb, zl


def loss_fn(params, tokens, cfg: OlmoeConfig):
    ce, lb, zl = loss_terms(params, tokens, cfg)
    return ce + cfg.lb_coef * lb + cfg.z_coef * zl


def routing_stats(params, tokens, cfg: OlmoeConfig) -> dict:
    """What the router did with ``tokens`` [B, T+1], outside any step:
    the expert ids of every layer (``experts`` [L, S, k]), and the
    telemetry counters ``moe_assignments``, ``moe_expert_rows_max``,
    ``moe_expert_rows_mean`` (rows an expert got in one layer) and
    ``moe_tokens_dropped`` (assignments that reached no row of the layout:
    0 by construction, counted from the layout itself)."""
    from tepdist_tpu.telemetry import metrics

    E = cfg.num_experts
    x = params["tok_emb"][tokens[:, :-1]].astype(cfg.dtype)
    S = x.shape[0] * x.shape[1]
    ids, sizes, placed = [], [], 0
    for blk in layer_dicts(params, _stacks(cfg)):
        x = x + attention(
            blk, rms_norm(x, blk["attn_norm"], cfg.rms_norm_eps), cfg)
        h = rms_norm(x, blk["ffn_norm"], cfg.rms_norm_eps)
        _, _, weights, experts = router(blk, h.reshape(S, -1), cfg)
        r = route(experts, E, cfg.moe_tile_m)
        placed += int(jnp.sum(r.row_token < S))
        ids.append(experts)
        sizes.append(r.group_sizes)
        x = x + moe(blk, h, cfg)[0]
    sizes = jnp.stack(sizes)
    assignments = int(sizes.sum())
    out = {"moe_assignments": assignments,
           "moe_expert_rows_max": int(sizes.max()),
           "moe_expert_rows_mean": float(sizes.mean()),
           "moe_tokens_dropped": assignments - placed}
    for name in ("moe_assignments", "moe_tokens_dropped"):
        metrics().counter(name).inc(out[name])
    metrics().gauge("moe_expert_rows_max").set(out["moe_expert_rows_max"])
    metrics().gauge("moe_expert_rows_mean").set(out["moe_expert_rows_mean"])
    return {**out, "experts": jnp.stack(ids)}
