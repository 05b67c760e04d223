"""AFMoE (arcee-ai Trinity: ``model_type: afmoe``; Trinity-Mini is 32
layers, hidden 2048, 32 query and 4 key/value heads of 128, 2 leading dense
layers of width 6144, then 128 routed SwiGLU experts of 1024, 8 a token,
beside one shared expert; three window-2048 layers to one global layer).

Per layer, RMSNorm without biases, four norms (sandwich):

    a  = rms(x; input_ln)
    q, k, v = rms_head(a Wq), rms_head(a Wk), a Wv     (QK-norm over each head)
    window layer: q, k = rope(q), rope(k); key j visible to query i iff
                  0 <= i - j < sliding_window.  Global layer: causal, and no
                  position encoding.
    o  = flash(q, k, v) * sigmoid(a Wa)                (gated attention)
    x  = x + rms(o Wo; post_attn_ln)
    h  = rms(x; pre_mlp_ln)
    dense layer:  y = Wd (silu(Wg h) * Wu h)
    expert layer: s = sigmoid_f32(h Wr);  e = top_k(s + b)
                  w = s[e] / (sum s[e] + 1e-20) * route_scale
                  y = shared(h) + sum_j w_j . Wd[e_j] (silu(Wg[e_j] h) * Wu[e_j] h)
    x  = x + rms(y; post_mlp_ln)

with ``x0 = tok_emb[tokens] * sqrt(hidden)``, a final RMSNorm and an untied
head. The loss is the cross entropy alone: there is no auxiliary loss.

**The expert layer is told which experts it holds** (``experts_held =
(first, count)`` of the router's ``num_experts``): it routes over all of
them, the normalising sum runs over all k choices, and it computes the part
of the result its own experts give (``ops/grouped_matmul.py:routed_experts``,
the layer OLMoE runs whole: dropless, no capacity). What the experts
elsewhere would add is left out; the shared expert is whole on every rank.
Nothing stands in for the other ranks or for the exchange with them.

**The selection bias ``b``** (``router_bias``, float32, one a router output)
moves the choice and nothing else: no gradient reaches it. Load is balanced
by its update once an optimizer step, ``b += delta - mean(delta)`` with
``delta = rate * sign(mean(n) - n)`` and ``n`` the step's assignments to
each expert. The loss hands ``n`` to the optimizer as the leaf's
"gradient" (``count_choices``: a custom VJP whose cotangent for ``b`` is the
count); counts add over micro batches as gradients do, so the step does not
depend on the accumulation split, and ``optim.adamw_bf16_router_bias``
routes the leaf to the sign update.

bf16 weights and activations; norms, the router's sigmoid and the loss in
float32. Parameters: ``l{i}`` per-layer dicts (``init_params``) or the
layers stacked by what they hold (``stacked_init_params``): ``dense``
[num_dense_layers, ...] and ``blocks`` [the rest, ...], each walked with
``lax.scan``; window and global layers of one stack differ in no shape and
the body chooses by ``lax.cond`` on its layer's kind
(``models/layers.py:scan_blocks``). ``loss_fn`` takes either.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

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
    attn_gate,
    cross_entropy,
    gqa_heads,
    part,
    rms_norm,
)
from tepdist_tpu.ops.grouped_matmul import routed_experts
from tepdist_tpu.ops.pallas.router_choice import choose

WINDOW, GLOBAL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144        # a dense layer's width
    moe_intermediate_size: int = 1024    # one expert's, routed or shared
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, GLOBAL) * 8
    num_dense_layers: int = 2
    num_experts: int = 128               # the router's width
    experts_held: Tuple[int, int] = (0, 128)   # (first, count) held here
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_scale: float = 2.826
    sliding_window: int = 2048
    rope_theta: float = 10000.0
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
    moe_tile_m: int = 256

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)


CONFIGS: Dict[str, AfmoeConfig] = {
    "trinity-mini": AfmoeConfig(),
    "test": AfmoeConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16,
        layer_types=(WINDOW, GLOBAL, WINDOW), num_dense_layers=1,
        num_experts=16, experts_held=(4, 4),
        num_experts_per_tok=2, sliding_window=8, dtype=jnp.float32,
        moe_tile_m=8),
}

_OUTSIDE_BLOCKS = ("tok_emb", "norm_f", "lm_head")


def init_params(cfg: AfmoeConfig, key, std: float = 0.02) -> Dict[str, Any]:
    """normal(std) weights, unit norm gains, zero selection bias; ``l{i}``
    per-layer dicts, the first ``num_dense_layers`` of them dense."""
    d, hd = cfg.hidden_size, cfg.head_dim
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    f, fs = cfg.moe_intermediate_size, \
        cfg.moe_intermediate_size * cfg.num_shared_experts
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
        lk = jax.random.split(keys[2 + i], 12)
        layer = {
            "input_ln": ones(), "post_attn_ln": ones(),
            "pre_mlp_ln": ones(), "post_mlp_ln": ones(),
            "q_norm": ones(hd), "k_norm": ones(hd),
            "wq": norm(lk[0], (d, H * hd)), "wk": norm(lk[1], (d, Hkv * hd)),
            "wv": norm(lk[2], (d, Hkv * hd)), "wa": norm(lk[3], (d, H * hd)),
            "wo": norm(lk[4], (H * hd, d)),
        }
        if i < cfg.num_dense_layers:
            layer.update({
                "w_gate": norm(lk[5], (d, cfg.intermediate_size)),
                "w_up": norm(lk[6], (d, cfg.intermediate_size)),
                "w_down": norm(lk[7], (cfg.intermediate_size, d))})
        else:
            layer.update({
                "router": norm(lk[5], (d, E)),
                "router_bias": jnp.zeros((E,), jnp.float32),
                "shared_gate": norm(lk[6], (d, fs)),
                "shared_up": norm(lk[7], (d, fs)),
                "shared_down": norm(lk[8], (fs, d)),
                "w_gate": norm(lk[9], (G, d, f)),
                "w_up": norm(lk[10], (G, d, f)),
                "w_down": norm(lk[11], (G, f, d))})
        params[f"l{i}"] = layer
    return params


def _stacks(cfg: AfmoeConfig):
    """(name, first layer, layers) of each stack of layers."""
    n, L = cfg.num_dense_layers, cfg.num_hidden_layers
    return [s for s in (("dense", 0, n), ("blocks", n, L - n)) if s[2]]


def stacked_init_params(cfg: AfmoeConfig, key, std: float = 0.02):
    """``init_params`` with the layers stacked: ``dense`` and ``blocks``,
    [layers of that kind, ...] each."""
    return stack_layers(init_params(cfg, key, std), _stacks(cfg),
                        _OUTSIDE_BLOCKS)


def attention(blk, a, cfg: AfmoeConfig, window):
    """a [B, T, d] (the normed input) -> the gated heads through ``wo``.
    ``window``: this layer's kind, a bool or a traced scalar (a stack of
    both kinds: the branch is a ``lax.cond``). Positions are rotary on the
    window layers alone."""
    o = gqa_heads(
        blk, a, n_head=cfg.num_attention_heads,
        n_kv_head=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        eps=cfg.rms_norm_eps, window=cfg.sliding_window, windowed=window,
        rope_window=cfg.rope_theta, rope_global=None,
        block_q=cfg.flash_block_q, block_k=cfg.flash_block_k)
    return attn_gate(o, a, blk["wa"]) @ blk["wo"]


@jax.custom_vjp
def count_choices(weights, bias, experts):
    """``weights`` as they are. Backward, the selection bias gets as its
    cotangent **how often each expert was chosen** (``experts`` [S, k] over
    ``bias``'s [E]): not a gradient (none reaches the bias) but what its
    once-a-step sign update reads, handed over where a gradient would be so
    that it adds over micro batches as gradients do."""
    del bias, experts
    return weights


def _count_fwd(weights, bias, experts):
    return weights, (bias, experts)


def _count_bwd(res, g):
    bias, experts = res
    counts = jnp.sum(
        experts[..., None] == jnp.arange(bias.shape[-1], dtype=experts.dtype),
        axis=tuple(range(experts.ndim)), dtype=jnp.float32)
    return g, counts.astype(bias.dtype), None


count_choices.defvjp(_count_fwd, _count_bwd)


def router(blk, h, cfg):
    """h [S, d] -> (float32 scores [S, E], weights [S, k], expert ids
    [S, k]): top-k of ``sigmoid(h Wr) + b``, weights from the unbiased
    scores, normalised over the k chosen and scaled."""
    logits = jnp.dot(h, blk["router"], preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    chosen, experts = choose(
        scores, cfg.num_experts_per_tok,
        select=scores + jax.lax.stop_gradient(blk["router_bias"]))
    weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) \
        * cfg.route_scale
    return scores, count_choices(weights, blk["router_bias"], experts), \
        experts


def swiglu(h, w_gate, w_up, w_down):
    g = (h @ w_gate).astype(jnp.float32)
    return (jax.nn.silu(g) * (h @ w_up).astype(jnp.float32)).astype(
        h.dtype) @ w_down


def moe(blk, x, cfg):
    """x [B, T, d] -> the shared expert's output plus the held routed
    experts' part of the layer's. ``cfg``: whatever has ``num_experts``,
    ``experts_held``, ``num_experts_per_tok``, ``route_scale`` and
    ``moe_tile_m`` (``models/sarvam_mla.py``'s runs this layer too)."""
    B, T, d = x.shape
    h = x.reshape(B * T, d)
    with jax.named_scope("moe_router"):
        _, weights, experts = router(blk, h, cfg)
        weights = held_weights(weights, experts, cfg.experts_held,
                               cfg.num_experts)
    y = routed_experts(h, weights, experts, blk["w_gate"], blk["w_up"],
                       blk["w_down"], cfg.num_experts, cfg.moe_tile_m,
                       held=cfg.experts_held)
    with jax.named_scope("moe_shared"):
        y = swiglu(h, blk["shared_gate"], blk["shared_up"],
                   blk["shared_down"]) + y
    return y.reshape(B, T, d)


def block(blk, x, cfg: AfmoeConfig, window):
    """One layer; dense or routed by what ``blk`` holds."""
    eps = cfg.rms_norm_eps
    with part("mixer"):
        a = rms_norm(x, blk["input_ln"], eps)
        x = x + rms_norm(attention(blk, a, cfg, window),
                         blk["post_attn_ln"], eps)
    with part("moe" if "router" in blk else "mlp"):
        h = rms_norm(x, blk["pre_mlp_ln"], eps)
        y = moe(blk, h, cfg) if "router" in blk else swiglu(
            h, blk["w_gate"], blk["w_up"], blk["w_down"])
        return x + rms_norm(y, blk["post_mlp_ln"], eps)


def hidden_states(params, tokens, cfg: AfmoeConfig):
    """tokens int32 [B, T] -> final normalised hidden [B, T, d]."""
    with part("embed"):
        x = (params["tok_emb"][tokens]
             * math.sqrt(cfg.hidden_size)).astype(cfg.dtype)
    x = walk_layers(lambda blk, h, window: block(blk, h, cfg, window), x,
                    params, _stacks(cfg),
                    [t == WINDOW for t in cfg.layer_types], cfg.remat,
                    experts=EXPERT_LEAVES)
    with part("head_loss"):
        return rms_norm(x, params["norm_f"], cfg.rms_norm_eps)


def forward(params, tokens, cfg: AfmoeConfig):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    x = hidden_states(params, tokens, cfg)
    return (x @ params["lm_head"].T).astype(jnp.float32)


def loss_fn(params, tokens, cfg: AfmoeConfig):
    """Cross entropy of tokens [B, T+1]; the router's bias receives its
    step's counts where its gradient would be (``count_choices``)."""
    x = hidden_states(params, tokens[:, :-1], cfg)
    return cross_entropy(x, params["lm_head"], tokens[:, 1:], cfg.loss_chunk)


def expert_choices(params, tokens, cfg: AfmoeConfig):
    """tokens int32 [B, T] -> the expert ids every expert layer's router
    chose, int32 [expert layers, B * T, k]; the forward pass alone, no
    host value in it (it can be jitted)."""
    eps = cfg.rms_norm_eps
    x = (params["tok_emb"][tokens]
         * math.sqrt(cfg.hidden_size)).astype(cfg.dtype)
    S = x.shape[0] * x.shape[1]
    ids = []
    for blk, kind in zip(layer_dicts(params, _stacks(cfg)),
                         cfg.layer_types):
        if "router" in blk:
            a = rms_norm(x, blk["input_ln"], eps)
            mid = x + rms_norm(attention(blk, a, cfg, kind == WINDOW),
                               blk["post_attn_ln"], eps)
            h = rms_norm(mid, blk["pre_mlp_ln"], eps)
            ids.append(router(blk, h.reshape(S, -1), cfg)[2])
        x = block(blk, x, cfg, kind == WINDOW)
    return jnp.stack(ids)


# What the routers did with ``tokens`` [B, T+1], outside any step
# (``models/decoder.py:routing_stats`` over this model's choices).
routing_stats = functools.partial(decoder.routing_stats, expert_choices)
