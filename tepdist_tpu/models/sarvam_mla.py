"""Sarvam MLA (sarvamai ``sarvam-105b``, ``model_type: sarvam_mla``: 32
layers, hidden 4096, 64 heads of multi-head latent attention, one leading
dense SwiGLU layer of 16,384, then 128 routed SwiGLU experts of 2048, 8 a
token, beside one shared expert; vocabulary 262,144, untied).

Per layer, pre-norm, RMSNorm without biases (``a`` [T, hidden]):

    a      = rms(x; input_ln)
    q      = a Wq                    Wq [d, Hh * (Dn + Dr)]: a head's q_nope
                                     [T, Dn] beside its q_rope [T, Dr]
    [c, r] = a Wkva                  Wkva [d, R + Dr]: the latent c [T, R]
                                     and the rotary key r [T, Dr]
    [k_nope, v] = rms(c; kv_ln) Wkvb   Wkvb [R, Hh * (Dn + Dv)]
    q_rope, k_rope = rope(q_rope), rope(r)     one k_rope for all heads; the
                                     ``deepseek_yarn`` table
    s_h    = (q_nope_h k_nope_h^T + q_rope_h k_rope^T) (Dn + Dr)^-0.5 m^2
    o_h    = softmax_causal(s_h) v_h                           [T, Dv]
    x      = x + concat_h(o_h) Wo    Wo [Hh * Dv, d]
    h      = rms(x; post_attn_ln)
    x      = x + Wd (silu(Wg h) * Wu h)                  a leading dense layer
    x      = x + shared(h) + sum_j w_j expert_{e_j}(h)   an expert layer

with ``x0 = tok_emb[tokens]``, a final RMSNorm and an untied head; the loss
is the cross entropy alone. ``R`` 512, ``Dn`` 128, ``Dr`` 64, ``Dv`` 128;
there is no query latent (``q_lora_rank`` is not a key of the config: one
projection, as DeepSeek-V2-Lite). The published ``use_qk_norm`` is read as
the one norm the latent form carries, the RMSNorm on ``c``.

**The rotary table** is ``deepseek_yarn``'s (``models/layers.py:yarn_table``
with the config's ``rope_scaling``; cos and sin times ``mscale /
mscale_all_dim``' ratio, 1 here) and the softmax scale carries ``m^2``, ``m
= 0.1 mscale_all_dim ln(factor) + 1`` (:attr:`SarvamMLAConfig.softmax_scale`).
Rotate-half pairs where DeepSeek interleaves them: a permutation of ``Wq``'s
and ``Wkva``'s rotary columns.

**The expert layer is Trinity's** (``models/afmoe.py``: ``router`` with
sigmoid scores, a selection bias no gradient reaches, the k chosen normalised
and scaled by ``routed_scaling_factor``; ``moe`` with its shared expert;
``count_choices`` for the bias's sign update), called as it is, and is told
which experts it holds (``experts_held``).

**The attention layer is told which heads it holds** (``heads_held = (first,
count)`` of ``num_attention_heads``): ``Wq``, ``Wkvb`` and ``Wo`` hold the
held heads' columns and rows, the layer computes those heads and their part
of the sum through ``Wo``; the down-projection ``Wkva``, its norm and the
rotary key are whole on every rank and computed alike there (the gauge
``mla_latent_bytes``). What the heads elsewhere would add is left out, as an
expert's elsewhere is; nothing stands in for the other ranks or for the
all-reduce with them. :func:`rank_share` cuts a rank's parameters out of the
whole model's.

The attention core is ``ops/pallas/mla_attention.py``: no key or value wider
than published, one ``k_rope`` read by all heads. **A block's token-wise
parts run in chunks of the sequence** (:func:`block`,
``models/layers.py:over_sequence``), as MiniCPM-SALA's; the half that holds
the expert layer is handed its weights, so that a walk which accumulates
gradients gives it the experts' stacks where they lie (``ExpertStack``) and
the chunk loop's backward adds each chunk's expert gradients into the
walk's accumulator, carried from chunk to chunk. bf16 weights
and activations; norms, rotary, the router's sigmoid, softmax statistics and
the loss in float32. Parameters: ``l{i}`` per-layer dicts (``init_params``)
or the layers stacked by what they hold (``stacked_init_params``): ``dense``
[first_k_dense_replace, ...] and ``blocks`` [the rest, ...], each walked with
``models/layers.py:scan_blocks``. ``loss_fn`` takes either.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from tepdist_tpu.models import decoder
from tepdist_tpu.models.afmoe import moe, router, swiglu
from tepdist_tpu.models.decoder import (
    fake_batch,  # noqa: F401 (the model's, as every decoder's)
    held_heads,
    layer_dicts,
    stack_layers,
    walk_layers,
)
from tepdist_tpu.models.layers import (
    RopeTable,
    cross_entropy,
    over_sequence,
    part,
    rms_norm,
    rope,
    yarn_table,
)
from tepdist_tpu.ops.pallas.mla_attention import mla_attention
from tepdist_tpu.telemetry import traced

traced.declare(
    "mla_heads_held", "attention heads a latent-attention layer of the "
    "traced step computes (its share of the model's)")
traced.declare(
    "mla_latent_bytes", "bytes of the latent and the rotary key [tokens, "
    "kv_lora_rank + qk_rope_head_dim] one latent-attention layer computes "
    "from a micro batch, alike on every rank that shares the layer's heads")


@dataclasses.dataclass(frozen=True)
class SarvamMLAConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 16384       # a dense layer's width
    moe_intermediate_size: int = 2048    # one expert's, routed or shared
    num_attention_heads: int = 64        # the model's
    heads_held: Tuple[int, int] = (0, 64)      # (first, count) held here
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_hidden_layers: int = 32
    first_k_dense_replace: int = 1       # leading dense layers
    num_experts: int = 128               # the router's width
    experts_held: Tuple[int, int] = (0, 128)   # (first, count) held here
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    rope_theta: float = 10000.0
    # ``rope_scaling`` (type deepseek_yarn).
    yarn_factor: float = 40.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # The attention kernels' tile sizes (0 = kernel default), every block
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
    def route_scale(self) -> float:
        """What ``afmoe.router`` scales the normalised weights by."""
        return self.routed_scaling_factor

    @property
    def rope_table(self) -> RopeTable:
        return yarn_table(
            self.qk_rope_head_dim, self.rope_theta, self.yarn_factor,
            self.yarn_original_max_position, self.yarn_beta_fast,
            self.yarn_beta_slow,
            _mscale(self.yarn_factor, self.yarn_mscale)
            / _mscale(self.yarn_factor, self.yarn_mscale_all_dim))

    @property
    def softmax_scale(self) -> float:
        m = _mscale(self.yarn_factor, self.yarn_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


def _mscale(factor: float, mscale: float) -> float:
    """DeepSeek's ``yarn_get_mscale``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


CONFIGS: Dict[str, SarvamMLAConfig] = {
    "105b": SarvamMLAConfig(),
    # The published structure small: a rank's 2 of 4 heads and 4 of 16
    # experts, head widths that differ (16 + 8 and 12), and a table whose
    # original context is 8 of the tests' 32 positions.
    "test": SarvamMLAConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4, heads_held=(2, 2),
        kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12, num_hidden_layers=3, num_experts=16,
        experts_held=(4, 4), num_experts_per_tok=2, rope_theta=100.0,
        yarn_factor=4.0, yarn_original_max_position=8, yarn_beta_fast=2.0,
        yarn_beta_slow=0.5, dtype=jnp.float32, moe_tile_m=8),
}

# Small around the published head widths (128 + 64 and 128, which the kernels
# compile for on the chip): ``chip_smoke.py``'s.
CONFIGS["smoke"] = dataclasses.replace(
    CONFIGS["test"], vocab_size=2048, hidden_size=256, intermediate_size=512,
    moe_intermediate_size=128, kv_lora_rank=128, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, yarn_original_max_position=256,
    dtype=jnp.bfloat16, remat=True, loss_chunk=256, moe_tile_m=128)

_OUTSIDE_BLOCKS = ("tok_emb", "norm_f", "lm_head")
# A layer's leaves that the first half of ``block`` reads (``attend``); the
# second half is handed the rest.
_ATTEND_LEAVES = ("input_ln", "kv_ln", "wq", "wkva", "wkvb")


def init_params(cfg: SarvamMLAConfig, key, std: float = 0.02):
    """normal(std) weights, unit norm gains, zero selection bias; ``l{i}``
    per-layer dicts, the first ``first_k_dense_replace`` of them dense."""
    d, R = cfg.hidden_size, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    Hh = cfg.heads_held[1]
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
        lk = jax.random.split(keys[2 + i], 11)
        layer = {
            "input_ln": ones(), "post_attn_ln": ones(), "kv_ln": ones(R),
            "wq": norm(lk[0], (d, Hh * (Dn + Dr))),
            "wkva": norm(lk[1], (d, R + Dr)),
            "wkvb": norm(lk[2], (R, Hh * (Dn + Dv))),
            "wo": norm(lk[3], (Hh * Dv, d)),
        }
        if i < cfg.first_k_dense_replace:
            layer.update({
                "w_gate": norm(lk[4], (d, cfg.intermediate_size)),
                "w_up": norm(lk[5], (d, cfg.intermediate_size)),
                "w_down": norm(lk[6], (cfg.intermediate_size, d))})
        else:
            layer.update({
                "router": norm(lk[4], (d, E)),
                "router_bias": jnp.zeros((E,), jnp.float32),
                "shared_gate": norm(lk[5], (d, fs)),
                "shared_up": norm(lk[6], (d, fs)),
                "shared_down": norm(lk[7], (fs, d)),
                "w_gate": norm(lk[8], (G, d, f)),
                "w_up": norm(lk[9], (G, d, f)),
                "w_down": norm(lk[10], (G, f, d))})
        params[f"l{i}"] = layer
    return params


def _stacks(cfg: SarvamMLAConfig):
    """(name, first layer, layers) of each stack of layers."""
    n, L = cfg.first_k_dense_replace, cfg.num_hidden_layers
    return [s for s in (("dense", 0, n), ("blocks", n, L - n)) if s[2]]


def stacked_init_params(cfg: SarvamMLAConfig, key, std: float = 0.02):
    """``init_params`` with the layers stacked: ``dense`` and ``blocks``,
    [layers of that kind, ...] each."""
    return stack_layers(init_params(cfg, key, std), _stacks(cfg),
                        _OUTSIDE_BLOCKS)


def rank_share(params, cfg: SarvamMLAConfig, heads_held: Tuple[int, int],
               experts_held: Tuple[int, int]):
    """From the ``l{i}`` parameters of ``cfg`` (which holds every head and
    every expert) what a rank holding ``heads_held`` and ``experts_held``
    has of them, and that rank's configuration: the held heads' columns of
    ``wq`` and ``wkvb`` and rows of ``wo``, the held experts' weights, and
    everything else whole."""
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    first, count = experts_held
    out = {k: params[k] for k in _OUTSIDE_BLOCKS}
    for i in range(cfg.num_hidden_layers):
        blk = dict(params[f"l{i}"])
        blk["wq"] = held_heads(blk["wq"], heads_held, Dn + Dr)
        blk["wkvb"] = held_heads(blk["wkvb"], heads_held, Dn + Dv)
        blk["wo"] = held_heads(blk["wo"], heads_held, Dv, axis=0)
        if "router" in blk:
            for k in ("w_gate", "w_up", "w_down"):
                blk[k] = blk[k][first:first + count]
        out[f"l{i}"] = blk
    return out, dataclasses.replace(cfg, heads_held=tuple(heads_held),
                                    experts_held=tuple(experts_held))


def _widest(cfg) -> int:
    """What sizes the chunks of a block's token-wise parts
    (``over_sequence``), one size for every layer: a dense layer's MLP
    width, or half of a token's ``k`` rows of an expert layer's dropless
    layout in the worst case (``ops/grouped_matmul.py:layout_rows``: ``[tokens
    k, d]`` and three ``[tokens k, f]`` arrays beside the size the routing
    takes, 2.8e9 bytes at 16,384 tokens whether or not a step ever takes it;
    a chunk's is its own tokens'), the larger. Both are 16,384 at the
    published sizes: chunks of 2,048 tokens."""
    return max(cfg.intermediate_size,
               cfg.num_experts_per_tok * cfg.hidden_size // 2)


def attention_inputs(blk, a, cfg, table: Optional[RopeTable], start=0):
    """a [B, T, d] (the normed input of positions ``start ..``) -> q_nope,
    q_rope, k_nope [B, T, Hh, .], k_rope [B, T, 1, Dr], v [B, T, Hh, Dv] of
    the held heads, the rotary parts rotated under ``table``; as they are
    where it is None (a model whose latent layers carry no position:
    ``models/kimi_linear.py``, whose ``cfg`` this also takes). The query
    is one projection ``wq``, or where ``blk`` holds ``wqa`` the two through
    a latent, ``q = rms(a Wqa; q_ln) Wqb`` (``models/xing.py``)."""
    B, T, _ = a.shape
    Hh, R = cfg.heads_held[1], cfg.kv_lora_rank
    Dn = cfg.qk_nope_head_dim

    def rotated(t):          # [B, T, H, Dr], by the position
        if table is None:
            return t
        return rope(t.transpose(0, 2, 1, 3), table,
                    start).transpose(0, 2, 1, 3)

    if "wqa" in blk:        # a query latent with its norm, DeepSeek-V3's
        with jax.named_scope("mla_q_down"):
            cq = rms_norm(a @ blk["wqa"], blk["q_ln"], cfg.rms_norm_eps)
        with jax.named_scope("mla_q_up"):
            q = (cq @ blk["wqb"]).reshape(B, T, Hh, -1)
    else:
        with jax.named_scope("mla_q"):
            q = (a @ blk["wq"]).reshape(B, T, Hh, -1)
    with jax.named_scope("mla_kv_down"):
        latent = a @ blk["wkva"]
        c = rms_norm(latent[..., :R], blk["kv_ln"], cfg.rms_norm_eps)
    with jax.named_scope("mla_kv_up"):
        kv = (c @ blk["wkvb"]).reshape(B, T, Hh, -1)
    with jax.named_scope("mla_rope"):
        q_rope = rotated(q[..., Dn:])
        k_rope = rotated(latent[..., None, R:])
    return q[..., :Dn], q_rope, kv[..., :Dn], k_rope, kv[..., Dn:]


def attend(blk, x, cfg, read=None):
    """x [B, T, d] -> the held heads' outputs side by side [B, T, Hh * Dv],
    before ``wo``: the projections in chunks of the sequence, the kernels
    over the whole of it. ``cfg``: a :class:`SarvamMLAConfig` or whatever
    has its head widths, ``heads_held``, ``rope_table`` (which may be None),
    ``softmax_scale`` and the widths :func:`_widest` reads.

    ``read``: for a walk whose carry is not the sub-layer's input (a
    residual stream of several lanes: ``models/xing.py``), ``read(xc) ->
    (the chunk's input [B, chunk, d], arrays [B, chunk, ...] to keep)``,
    run inside the chunk loop before the norm; the call then returns the
    outputs with what was kept, each [B, T, ...], for the chunk loop after
    the kernels to take and not make again."""
    B, T, _ = x.shape
    traced.note("mla_heads_held", cfg.heads_held[1])
    traced.note("mla_latent_bytes",
                B * T * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                * jnp.dtype(x.dtype).itemsize)

    def inputs(start, xc):
        xc, kept = (xc, ()) if read is None else read(xc)
        return attention_inputs(
            blk, rms_norm(xc, blk["input_ln"], cfg.rms_norm_eps), cfg,
            cfg.rope_table, start) + tuple(kept)

    with jax.named_scope("mla_in"):
        operands = over_sequence(inputs, _widest(cfg), x)
    o = mla_attention(*(t.transpose(0, 2, 1, 3) for t in operands[:5]),
                      causal=True, scale=cfg.softmax_scale,
                      block_q=cfg.flash_block_q or None,
                      block_k=cfg.flash_block_k or None)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
    return o if read is None else (o, *operands[5:])


def block(blk, x, cfg: SarvamMLAConfig):
    """One layer; dense or routed by what ``blk`` holds. The token-wise
    parts (norms, projections, rotary, the MLP or the expert layer) run in
    chunks of the sequence, each rematerialised in the block's own backward
    (``over_sequence``): a block's working set holds ``[T, heads, D]``
    arrays for the kernels and never a ``[T, intermediate_size]`` one nor
    the whole sequence's worst-case expert layout. Routing is a token's own,
    so the chunks change no value. ``blk``'s expert leaves may be
    ``ExpertStack``s (``walk_layers(experts=)``)."""
    eps = cfg.rms_norm_eps

    def after(blk, start, xc, oc):
        del start
        with part("mixer"), jax.named_scope("mla_out"):
            xc = xc + oc @ blk["wo"]
        with part("moe" if "router" in blk else "mlp"):
            h = rms_norm(xc, blk["post_attn_ln"], eps)
            if "router" in blk:
                return xc + moe(blk, h, cfg)
            return xc + swiglu(h, blk["w_gate"], blk["w_up"], blk["w_down"])

    with part("mixer"):
        o = attend(blk, x, cfg)
    # The second half is handed its weights: a walk that accumulates
    # gradients gives an expert layer ``ExpertStack``s, whose accumulators
    # the chunk loop's backward has to carry and not sum.
    with jax.named_scope("mla_out_mlp"):
        return over_sequence(after, _widest(cfg), x, o, weights={
            k: w for k, w in blk.items() if k not in _ATTEND_LEAVES})


def hidden_states(params, tokens, cfg: SarvamMLAConfig):
    """tokens int32 [B, T] -> final normalised hidden [B, T, d]."""
    with part("embed"):
        x = params["tok_emb"][tokens].astype(cfg.dtype)
    x = walk_layers(lambda blk, h, _: block(blk, h, cfg), x, params,
                    _stacks(cfg), [None] * cfg.num_hidden_layers, cfg.remat,
                    experts=decoder.EXPERT_LEAVES)
    with part("head_loss"):
        return rms_norm(x, params["norm_f"], cfg.rms_norm_eps)


def forward(params, tokens, cfg: SarvamMLAConfig):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    x = hidden_states(params, tokens, cfg)
    return (x @ params["lm_head"].T).astype(jnp.float32)


def loss_fn(params, tokens, cfg: SarvamMLAConfig):
    """Cross entropy of tokens [B, T+1]; the router's bias receives its
    step's counts where its gradient would be (``afmoe.count_choices``)."""
    x = hidden_states(params, tokens[:, :-1], cfg)
    return cross_entropy(x, params["lm_head"], tokens[:, 1:], cfg.loss_chunk)


def expert_choices(params, tokens, cfg: SarvamMLAConfig):
    """tokens int32 [B, T] -> the expert ids every expert layer's router
    chose, int32 [expert layers, B * T, k]; the forward pass alone."""
    x = params["tok_emb"][tokens].astype(cfg.dtype)
    S = x.shape[0] * x.shape[1]
    ids = []
    for blk in layer_dicts(params, _stacks(cfg)):
        if "router" in blk:
            mid = x + attend(blk, x, cfg) @ blk["wo"]
            h = rms_norm(mid, blk["post_attn_ln"], cfg.rms_norm_eps)
            ids.append(router(blk, h.reshape(S, -1), cfg)[2])
        x = block(blk, x, cfg)
    return jnp.stack(ids)


# What the routers did with ``tokens`` [B, T+1], outside any step
# (``models/decoder.py:routing_stats`` over this model's choices).
routing_stats = functools.partial(decoder.routing_stats, expert_choices)
