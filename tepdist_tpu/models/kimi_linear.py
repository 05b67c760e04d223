"""Kimi Linear (moonshotai ``Kimi-Linear-48B-A3B``, ``model_type:
kimi_linear``; arXiv:2510.26692: 27 layers, hidden 2304, three Kimi Delta
Attention layers to each latent-attention layer without positions, one
leading dense SwiGLU layer of 9216, then 256 routed SwiGLU experts of 1024, 8
a token, beside one shared expert; vocabulary 163,840, untied).

A **KDA layer** (``H`` = 32 heads of ``K = V`` = 128 channels; ``a`` the
normed input [T, hidden]):

    q~, k~, v = silu(conv4(a Wq)), silu(conv4(a Wk)), silu(conv4(a Wv))
                               depth-wise causal conv, 4 taps, no bias
    q_h    = q~_h / |q~_h|_2 * K^-0.5       k_h = k~_h / |k~_h|_2
    g_h    = -exp(A_h) * softplus(((a Wfa) Wfb)_h + dt_h)      float32: the
                               log of a decay in (0, 1) for every key channel
    beta_h = sigmoid(a Wb)_h                                   float32
    S_t    = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t    = S_t^T q_t                      (``ops/pallas/kda_attention.py``)
    y_h    = rms(o_h; o_norm [V]) * sigmoid(((a Wga) Wgb)_h)
    x      = x + concat_h(y_h) Wo

A **latent-attention layer** is ``models/sarvam_mla.py``'s block, called as
it is, at these widths and **with no rotary table** (``mla_use_nope``): the
64 ``qk_rope_head_dim`` channels of a query and the one shared key of them
are plain score channels, and the linear layers carry the position. Then in
every layer ``h = rms(x; post_attn_ln)`` and ``x = x + SwiGLU(h)`` (a leading
dense layer) or ``x = x + shared(h) + sum_j w_j expert_{e_j}(h)``: Trinity's
expert layer (``models/afmoe.py``: sigmoid scores, a selection bias no
gradient reaches, the 8 chosen normalised and scaled by
``routed_scaling_factor``), told which experts it holds (``experts_held``).
``x0 = tok_emb[tokens]``, a final RMSNorm, an untied head, the cross entropy
alone.

**A block's token-wise parts run in chunks of the sequence**
(``models/layers.py:over_sequence``), as sarvam-105b's: a KDA layer's first
half hands on five arrays (the three projections, the log decays and
``beta``), the convs, the L2 norms and the kernel see the whole sequence,
and the half that holds the expert layer is handed its weights
(``ExpertStack``s inside a walk that accumulates gradients). bf16 weights
and activations; norms, gates, decays, the state, the router's sigmoid,
softmax statistics and the loss in float32.

Parameters: ``l{i}`` per-layer dicts (``init_params``) or **a stack a run of
consecutive layers of one kind** (``stacked_init_params``: ``run{r}``), a
kind being the mixer and what follows it (``kda`` or ``mla``, ``dense`` or
``moe``), each walked with ``models/layers.py:scan_blocks`` in the published
order. ``loss_fn`` takes either layout.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from tepdist_tpu.models import decoder, sarvam_mla
from tepdist_tpu.models.afmoe import moe, swiglu
from tepdist_tpu.models.decoder import (
    fake_batch,  # noqa: F401 (the model's, as every decoder's)
    run_stacks,
    stack_layers,
    walk_layers,
)
from tepdist_tpu.models.layers import (
    cross_entropy,
    over_sequence,
    part,
    rms_norm,
    scaled_by_head,
)
from tepdist_tpu.ops.pallas.causal_conv import causal_conv
from tepdist_tpu.ops.pallas.kda_attention import CHUNK, kda_attention
from tepdist_tpu.telemetry import traced

traced.declare(
    "kda_state_bytes", "bytes of the float32 state [heads, K, V] one delta-"
    "rule layer leaves a sequence: what a stage hands on or a decode keeps")
traced.declare(
    "kda_decay_bytes", "bytes of the float32 log decays [tokens, heads * K] "
    "one delta-rule layer makes of a micro batch")

KDA, MLA = "kda", "mla"
DENSE, MOE = "dense", "moe"
# The published layers, numbered from 1: every fourth and the last are
# latent attention.
_PUBLISHED = tuple(MLA if i in (4, 8, 12, 16, 20, 24, 27) else KDA
                   for i in range(1, 28))


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216        # a dense layer's width
    moe_intermediate_size: int = 1024    # one expert's, routed or shared
    # The mixers of the layers held, in order (``linear_attn_config``'s
    # ``kda_layers`` / ``full_attn_layers``), the first
    # ``first_k_dense_replace`` of them over a dense MLP.
    mixers: Tuple[str, ...] = _PUBLISHED
    first_k_dense_replace: int = 1
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    num_attention_heads: int = 32        # the latent layers'
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_experts: int = 256               # the router's width
    experts_held: Tuple[int, int] = (0, 256)   # (first, count) held here
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # The latent layers' kernel tiles (0 = kernel default), the delta-rule
    # kernels' chunk, every block rematerialised in the backward pass
    # (layers.scan_blocks) and the loss chunk: gpt2.GPT2Config's vocabulary.
    flash_block_q: int = 0
    flash_block_k: int = 0
    kda_chunk: int = CHUNK
    remat: bool = False
    loss_chunk: int = 0
    # Rows of a grouped-matmul tile; every expert's rows are padded to it.
    moe_tile_m: int = 256

    @property
    def num_hidden_layers(self) -> int:
        return len(self.mixers)

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, what follows it) of every layer held."""
        return tuple((m, DENSE if i < self.first_k_dense_replace else MOE)
                     for i, m in enumerate(self.mixers))

    # What ``models/sarvam_mla.py``'s latent layer and ``models/afmoe.py``'s
    # expert layer read of a configuration.
    @property
    def heads_held(self) -> Tuple[int, int]:
        return (0, self.num_attention_heads)

    @property
    def rope_table(self):
        return None                      # mla_use_nope

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def route_scale(self) -> float:
        return self.routed_scaling_factor


CONFIGS: Dict[str, KimiLinearConfig] = {
    "48b-a3b": KimiLinearConfig(),
    # The published structure small: the leading dense layer, three KDA
    # layers to one latent, 8 experts a token beside a shared one, a rank's
    # 16 of 32 experts; runs of 1, 2, 1 and 1 layers.
    "test": KimiLinearConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, mixers=(KDA, KDA, KDA, MLA, KDA),
        kda_num_heads=4, kda_head_dim=32, num_attention_heads=2,
        kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12, num_experts=32, experts_held=(8, 16),
        num_experts_per_tok=8, dtype=jnp.float32, kda_chunk=16,
        moe_tile_m=8),
}
CONFIGS["test_bf16"] = dataclasses.replace(CONFIGS["test"],
                                           dtype=jnp.bfloat16)
# Small around the published head widths (32-lane blocks of 128 for the
# delta rule, 128 + 64 and 128 for the latent layer, which the kernels
# compile for on the chip): ``chip_smoke.py``'s.
CONFIGS["smoke"] = dataclasses.replace(
    CONFIGS["test"], vocab_size=2048, hidden_size=256, intermediate_size=512,
    moe_intermediate_size=128, kda_num_heads=2, kda_head_dim=128,
    kv_lora_rank=128,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    dtype=jnp.bfloat16, kda_chunk=CHUNK, remat=True, loss_chunk=256,
    moe_tile_m=128)

_OUTSIDE_BLOCKS = ("tok_emb", "norm_f", "lm_head")
GROUPS = ("run",)
# A KDA layer's leaves that the first half of its block alone reads; the
# second half is handed the rest.
_KDA_FIRST = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "wfa", "wfb",
              "A_log", "dt_bias", "wb")


def _mixer_params(cfg: KimiLinearConfig, mixer: str, keys, norm):
    d = cfg.hidden_size
    f32 = jnp.float32
    if mixer == MLA:
        R = cfg.kv_lora_rank
        Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        Hh = cfg.num_attention_heads
        return {"kv_ln": jnp.ones((R,), f32),
                "wq": norm(keys[0], (d, Hh * (Dn + Dr))),
                "wkva": norm(keys[1], (d, R + Dr)),
                "wkvb": norm(keys[2], (R, Hh * (Dn + Dv))),
                "wo": norm(keys[3], (Hh * Dv, d))}
    H, D, taps = cfg.kda_num_heads, cfg.kda_head_dim, \
        cfg.short_conv_kernel_size
    # The published initialisation: A = log U(1, 16) a head; dt the inverse
    # softplus of exp(U(log 1e-3, log 1e-1)) a channel (Mamba's).
    dt = jnp.exp(jax.random.uniform(keys[11], (H * D,), f32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {"wq": norm(keys[0], (d, H * D)), "wk": norm(keys[1], (d, H * D)),
            "wv": norm(keys[2], (d, H * D)),
            "conv_q": norm(keys[3], (taps, H * D)),
            "conv_k": norm(keys[4], (taps, H * D)),
            "conv_v": norm(keys[5], (taps, H * D)),
            "wfa": norm(keys[6], (d, D)), "wfb": norm(keys[7], (D, H * D)),
            "A_log": jnp.log(jax.random.uniform(keys[12], (H,), f32, 1.0,
                                                16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "wb": norm(keys[8], (d, H)),
            "wga": norm(keys[9], (d, D)), "wgb": norm(keys[10], (D, H * D)),
            "o_norm": jnp.ones((D,), f32),
            "wo": norm(keys[13], (H * D, d))}


def init_params(cfg: KimiLinearConfig, key, std: float = 0.02):
    """normal(std) matrices and conv taps, unit norm gains, a zero selection
    bias, the decays' ``A_log`` and ``dt_bias`` as published; ``l{i}``
    per-layer dicts, the first ``first_k_dense_replace`` of them dense."""
    d = cfg.hidden_size
    f, fs = cfg.moe_intermediate_size, \
        cfg.moe_intermediate_size * cfg.num_shared_experts
    E, G = cfg.num_experts, cfg.experts_held[1]
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
    for i, (mixer, follows) in enumerate(cfg.kinds):
        lk = jax.random.split(keys[2 + i], 21)
        layer = {"input_ln": ones(), "post_attn_ln": ones(),
                 **_mixer_params(cfg, mixer, lk[:14], norm)}
        if follows == DENSE:
            layer.update({
                "w_gate": norm(lk[14], (d, cfg.intermediate_size)),
                "w_up": norm(lk[15], (d, cfg.intermediate_size)),
                "w_down": norm(lk[16], (cfg.intermediate_size, d))})
        else:
            layer.update({
                "router": norm(lk[14], (d, E)),
                "router_bias": jnp.zeros((E,), jnp.float32),
                "shared_gate": norm(lk[15], (d, fs)),
                "shared_up": norm(lk[16], (d, fs)),
                "shared_down": norm(lk[17], (fs, d)),
                "w_gate": norm(lk[18], (G, d, f)),
                "w_up": norm(lk[19], (G, d, f)),
                "w_down": norm(lk[20], (G, f, d))})
        params[f"l{i}"] = layer
    return params


def stacked_init_params(cfg: KimiLinearConfig, key, std: float = 0.02):
    """``init_params`` with each run of one kind stacked, [layers of the
    run, ...] a leaf, under ``run{r}``."""
    return stack_layers(init_params(cfg, key, std), run_stacks(cfg.kinds),
                        _OUTSIDE_BLOCKS, GROUPS)


def rank_share(params, cfg: KimiLinearConfig, experts_held: Tuple[int, int]):
    """From the ``l{i}`` parameters of ``cfg`` (which holds every expert)
    what a rank holding ``experts_held`` has of them, and that rank's
    configuration: the held experts' weights and everything else (mixers,
    router, shared expert, a dense layer's MLP) whole."""
    first, count = experts_held
    out = {k: params[k] for k in _OUTSIDE_BLOCKS}
    for i in range(cfg.num_hidden_layers):
        blk = dict(params[f"l{i}"])
        if "router" in blk:
            for k in decoder.EXPERT_LEAVES:
                blk[k] = blk[k][first:first + count]
        out[f"l{i}"] = blk
    return out, dataclasses.replace(cfg, experts_held=tuple(experts_held))


def log_decays(blk, a, cfg: KimiLinearConfig):
    """a [B, T, d] -> float32 [B, T, H * K], at most 0: ``-exp(A_h) *
    softplus(((a Wfa) Wfb)_h + dt_h)``."""
    H, D = cfg.kda_num_heads, cfg.kda_head_dim
    raw = jnp.dot(a @ blk["wfa"], blk["wfb"],
                  preferred_element_type=jnp.float32) + blk["dt_bias"]
    rate = jnp.repeat(jnp.exp(blk["A_log"].astype(jnp.float32)), D)
    return -rate * jax.nn.softplus(raw)


def l2_norm(x, heads: int, scale: float = 1.0, eps: float = 1e-6):
    """x [B, T, heads * D] -> each head's D channels over their L2 norm,
    times ``scale``; float32 inside, back in x's dtype."""
    x32 = scaled_by_head(x.astype(jnp.float32), heads, eps, mean=False)
    return (x32 * scale if scale != 1.0 else x32).astype(x.dtype)


def gated_norm(o, gate, g, heads: int, eps: float, act=jax.nn.sigmoid):
    """``rms(o_h; g) * act(gate_h)`` over each head's channels: o, gate
    [B, T, heads * D], g [D] (one gain, every head's), ``act`` the gate's
    function (the sigmoid here, ``silu`` in ``models/qwen3_next.py``);
    float32 inside, back in o's dtype."""
    normed = scaled_by_head(o.astype(jnp.float32), heads, eps, mean=True) \
        * jnp.tile(g.astype(jnp.float32), heads)
    return (normed * act(gate.astype(jnp.float32))).astype(o.dtype)


def kda_inputs(blk, a, cfg: KimiLinearConfig):
    """The normed input's chunk -> the three projections before their convs,
    the log decays and ``beta``: the five arrays the mixer is made of."""
    with jax.named_scope("kda_in"):
        q, k, v = a @ blk["wq"], a @ blk["wk"], a @ blk["wv"]
    with jax.named_scope("kda_gates"):
        g = log_decays(blk, a, cfg)
        beta = jax.nn.sigmoid(jnp.dot(a, blk["wb"],
                                      preferred_element_type=jnp.float32))
    return q, k, v, g, beta


def kda_mix(blk, x, cfg: KimiLinearConfig):
    """x [B, T, d] -> the heads' outputs side by side [B, T, H * V], before
    the gated norm and ``wo``: the projections in chunks of the sequence,
    the convs, the L2 norms and the kernel over the whole of it."""
    B, T, _ = x.shape
    H, D = cfg.kda_num_heads, cfg.kda_head_dim
    traced.note("kda_state_bytes", B * H * D * D * 4)
    traced.note("kda_decay_bytes", B * T * H * D * 4)
    q, k, v, g, beta = over_sequence(
        lambda start, xc: kda_inputs(
            blk, rms_norm(xc, blk["input_ln"], cfg.rms_norm_eps), cfg),
        sarvam_mla._widest(cfg), x)
    with jax.named_scope("kda_conv"):
        q, k, v = (causal_conv(t, blk[w]) for t, w in (
            (q, "conv_q"), (k, "conv_k"), (v, "conv_v")))
    with jax.named_scope("kda_gates"):
        q = l2_norm(q, H, D ** -0.5)
        k = l2_norm(k, H)
    with jax.named_scope("kda_core"):
        return kda_attention(q, k, v, g, beta, chunk=cfg.kda_chunk)


def kda_block(blk, x, cfg: KimiLinearConfig):
    """A KDA layer; dense or routed by what ``blk`` holds. As
    ``sarvam_mla.block``: the token-wise parts in chunks of the sequence,
    the second half handed its weights (``blk``'s expert leaves may be
    ``ExpertStack``s)."""
    eps = cfg.rms_norm_eps

    def after(blk, start, xc, oc):
        del start
        with part("mixer"), jax.named_scope("kda_out"):
            a = rms_norm(xc, blk["input_ln"], eps)
            gate = jnp.dot(a @ blk["wga"], blk["wgb"],
                           preferred_element_type=jnp.float32)
            xc = xc + gated_norm(oc, gate, blk["o_norm"], cfg.kda_num_heads,
                                 eps) @ blk["wo"]
        with part("moe" if "router" in blk else "mlp"):
            h = rms_norm(xc, blk["post_attn_ln"], eps)
            if "router" in blk:
                return xc + moe(blk, h, cfg)
            return xc + swiglu(h, blk["w_gate"], blk["w_up"], blk["w_down"])

    with part("mixer"):
        o = kda_mix(blk, x, cfg)
    with jax.named_scope("kda_out_mlp"):
        return over_sequence(after, sarvam_mla._widest(cfg), x, o, weights={
            k: w for k, w in blk.items() if k not in _KDA_FIRST})


def block(blk, x, cfg: KimiLinearConfig, mixer: str):
    """One layer of either mixer."""
    if mixer == MLA:
        return sarvam_mla.block(blk, x, cfg)
    return kda_block(blk, x, cfg)


def hidden_states(params, tokens, cfg: KimiLinearConfig):
    """tokens int32 [B, T] -> final normalised hidden [B, T, d]."""
    with part("embed"):
        x = params["tok_emb"][tokens].astype(cfg.dtype)
    x = walk_layers(lambda blk, h, kind: block(blk, h, cfg, kind[0]), x,
                    params, run_stacks(cfg.kinds), cfg.kinds, cfg.remat,
                    GROUPS, experts=decoder.EXPERT_LEAVES)
    with part("head_loss"):
        return rms_norm(x, params["norm_f"], cfg.rms_norm_eps)


def forward(params, tokens, cfg: KimiLinearConfig):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    x = hidden_states(params, tokens, cfg)
    return (x @ params["lm_head"].T).astype(jnp.float32)


def loss_fn(params, tokens, cfg: KimiLinearConfig):
    """Cross entropy of tokens [B, T+1]; the router's bias receives its
    step's counts where its gradient would be (``afmoe.count_choices``)."""
    x = hidden_states(params, tokens[:, :-1], cfg)
    return cross_entropy(x, params["lm_head"], tokens[:, 1:], cfg.loss_chunk)
