"""GPT-2 model family in pure JAX (pytree params, planner-friendly einsums).

Reference parity: ``examples/GPT2`` (reference: examples/GPT2/models/gpt2/
gpt2.py, configs 117M/345M/1.5B/175B in examples/GPT2/*.json). The reference
feeds a TF-1.x GPT-2 graph to the planner; here the model is written
jax-first: bfloat16 activations for the MXU, einsum attention whose
dot_generals expose clean batch/head/sequence/model dims to the cone planner,
static causal masking (no dynamic shapes), and a fused next-token
cross-entropy loss.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from tepdist_tpu.models.layers import (
    cross_entropy,
    part,
    rematerialised_whole,
    scan_blocks,
)


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_ctx: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dtype: Any = jnp.bfloat16
    # "einsum" (planner-visible dots) or "flash" (pallas fused kernel with
    # custom VJP — O(T) activation memory, the training default on TPU for
    # larger configs). Reference config names mirror
    # examples/GPT2/{117M,345M,1.5B,175B}.json.
    attn: str = "einsum"
    # Rematerialise each transformer block in backward (jax.checkpoint):
    # trades recompute FLOPs for activation HBM — how the big configs fit.
    remat: bool = False
    # Remat policy when remat=True (vocabulary matches train.py's
    # REMAT_POLICY knob): "full" recomputes the whole block in backward
    # (minimum memory); "dots" saves matmul outputs (checkpoint_dots);
    # "dots_no_batch" saves only no-batch-dim matmuls — the backward skips
    # recomputing MXU-heavy ops at the cost of the saved activations' HBM;
    # "save_attn" keeps the attention's output: in the stacked form, where a
    # gradient-accumulation step walks the blocks (layers.scan_blocks), the
    # flash kernel's output and log-sum-exp, so its forward runs once.
    remat_policy: str = "full"
    # Flash attention tile sizes (0 = kernel default). Bigger q tiles mean
    # fewer grid steps/LSE traffic; sweepable per chip generation.
    flash_block_q: int = 0
    flash_block_k: int = 0
    # Chunked cross-entropy: compute logits/logsumexp over `loss_chunk`
    # tokens at a time under jax.checkpoint, so the [B*T, vocab] fp32
    # logits tensor never materialises (peak loss memory drops from
    # B*T*V*4 to chunk*V*4 bytes — the big configs' other memory wall).
    # 0 = dense. Non-dividing token counts use a zero-padded masked tail
    # chunk (the LM loss shifts tokens, so counts are B*(T-1)).
    loss_chunk: int = 0

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


CONFIGS: Dict[str, GPT2Config] = {
    "117M": GPT2Config(n_embd=768, n_layer=12, n_head=12),
    "345M": GPT2Config(n_embd=1024, n_layer=24, n_head=16),
    "762M": GPT2Config(n_embd=1280, n_layer=36, n_head=20),
    "1.5B": GPT2Config(n_embd=1600, n_layer=48, n_head=25),
    "175B": GPT2Config(n_embd=12288, n_layer=96, n_head=96, n_ctx=2048),
    # tiny config for tests
    "test": GPT2Config(vocab_size=512, n_ctx=64, n_embd=64, n_layer=2,
                       n_head=4, dtype=jnp.float32),
}


def num_params(cfg: GPT2Config) -> int:
    d, L, v = cfg.n_embd, cfg.n_layer, cfg.vocab_size
    per_layer = 12 * d * d + 13 * d
    return v * d + cfg.n_ctx * d + L * per_layer + 2 * d


def init_params(cfg: GPT2Config, key) -> Dict[str, Any]:
    """Initializer specs follow GPT-2: normal(0.02), residual projections
    scaled by 1/sqrt(2*n_layer)."""
    std = 0.02
    resid_std = std / math.sqrt(2 * cfg.n_layer)
    d = cfg.n_embd
    keys = jax.random.split(key, 4 + cfg.n_layer)
    f32 = jnp.float32

    def norm(k, shape, s):
        return (jax.random.normal(k, shape, f32) * s).astype(cfg.dtype)

    params: Dict[str, Any] = {
        "wte": norm(keys[0], (cfg.vocab_size, d), std),
        "wpe": norm(keys[1], (cfg.n_ctx, d), std),
        "ln_f_g": jnp.ones((d,), f32),
        "ln_f_b": jnp.zeros((d,), f32),
    }
    for i in range(cfg.n_layer):
        lk = jax.random.split(keys[4 + i], 4)
        params[f"h{i}"] = {
            "ln1_g": jnp.ones((d,), f32),
            "ln1_b": jnp.zeros((d,), f32),
            "attn_qkv_w": norm(lk[0], (d, 3 * d), std),
            "attn_qkv_b": jnp.zeros((3 * d,), cfg.dtype),
            "attn_proj_w": norm(lk[1], (d, d), resid_std),
            "attn_proj_b": jnp.zeros((d,), cfg.dtype),
            "ln2_g": jnp.ones((d,), f32),
            "ln2_b": jnp.zeros((d,), f32),
            "mlp_fc_w": norm(lk[2], (d, 4 * d), std),
            "mlp_fc_b": jnp.zeros((4 * d,), cfg.dtype),
            "mlp_proj_w": norm(lk[3], (4 * d, d), resid_std),
            "mlp_proj_b": jnp.zeros((d,), cfg.dtype),
        }
    return params


def _layer_norm(x, g, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * g + b).astype(x.dtype)


def attention(block, x, cfg: GPT2Config, attn_impl=None):
    B, T, D = x.shape
    H, hd = cfg.n_head, cfg.head_dim
    qkv = x @ block["attn_qkv_w"] + block["attn_qkv_b"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    if attn_impl is None and cfg.attn == "flash":
        from tepdist_tpu.ops.pallas.flash_attention import flash_attention
        kw = {}
        if cfg.flash_block_q:
            kw["block_q"] = cfg.flash_block_q
        if cfg.flash_block_k:
            kw["block_k"] = cfg.flash_block_k
        attn_impl = functools.partial(flash_attention, **kw) if kw \
            else flash_attention
    if attn_impl is not None:
        from jax.ad_checkpoint import checkpoint_name
        o = checkpoint_name(attn_impl(q, k, v), "attn_out")
    else:
        scale = 1.0 / math.sqrt(hd)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        mask = jnp.tril(jnp.ones((T, T), bool))
        logits = jnp.where(mask, logits.astype(jnp.float32), -1e9)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        from jax.ad_checkpoint import checkpoint_name
        o = checkpoint_name(
            jnp.einsum("bhqk,bhkd->bhqd", probs, v), "attn_out")
    o = o.transpose(0, 2, 1, 3).reshape(B, T, D)
    return o @ block["attn_proj_w"] + block["attn_proj_b"]


def mlp(block, x):
    h = x @ block["mlp_fc_w"] + block["mlp_fc_b"]
    h = jax.nn.gelu(h)
    return h @ block["mlp_proj_w"] + block["mlp_proj_b"]


def _remat_kwargs(cfg: GPT2Config) -> dict:
    if cfg.remat_policy == "dots":
        return {"policy": jax.checkpoint_policies.checkpoint_dots}
    if cfg.remat_policy == "dots_no_batch":
        return {"policy":
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable}
    if cfg.remat_policy == "save_attn":
        # Save ONLY the attention outputs (tagged checkpoint_name above):
        # the backward skips re-running the flash kernel — the one block op
        # XLA cannot fuse into the recompute anyway — for mb*T*D*2 bytes
        # per layer, a fraction of what "dots" keeps.
        return {"policy":
                jax.checkpoint_policies.save_only_these_names("attn_out")}
    if cfg.remat_policy != "full":
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r}; expected 'full', "
            "'dots', 'dots_no_batch', or 'save_attn' (superset of "
            "train.py's REMAT_POLICY vocabulary)")
    return {}


def transformer_block(block, x, cfg: GPT2Config, attn_impl=None):
    with part("mixer"):
        x = x + attention(
            block, _layer_norm(x, block["ln1_g"], block["ln1_b"]), cfg,
            attn_impl)
    with part("mlp"):
        x = x + mlp(block, _layer_norm(x, block["ln2_g"], block["ln2_b"]))
    return x


def _embedded(params, tokens, cfg: GPT2Config):
    """tokens int32 [B, T] -> token plus position embeddings [B, T, D]."""
    with part("embed"):
        x = params["wte"][tokens] + params["wpe"][:tokens.shape[1]]
        return x.astype(cfg.dtype)


def _final_norm(params, x):
    with part("head_loss"):
        return _layer_norm(x, params["ln_f_g"], params["ln_f_b"])


def hidden_states(params, tokens, cfg: GPT2Config, attn_impl=None):
    """tokens: int32 [B, T] -> final (ln_f-normalised) hidden [B, T, D]."""
    x = _embedded(params, tokens, cfg)
    block_fn = transformer_block
    if cfg.remat:
        block_fn = jax.checkpoint(
            lambda blk, h: transformer_block(blk, h, cfg, attn_impl),
            **_remat_kwargs(cfg))
        for i in range(cfg.n_layer):
            x = block_fn(params[f"h{i}"], x)
    else:
        for i in range(cfg.n_layer):
            x = block_fn(params[f"h{i}"], x, cfg, attn_impl)
    return _final_norm(params, x)


def forward(params, tokens, cfg: GPT2Config, attn_impl=None):
    """tokens: int32 [B, T] -> logits [B, T, vocab] (fp32)."""
    x = hidden_states(params, tokens, cfg, attn_impl)
    return (x @ params["wte"].T).astype(jnp.float32)


def loss_fn(params, tokens, cfg: GPT2Config, attn_impl=None):
    """Next-token cross entropy over shifted tokens (reference GPT2 LM loss)."""
    x = hidden_states(params, tokens[:, :-1], cfg, attn_impl)
    return cross_entropy(x, params["wte"], tokens[:, 1:], cfg.loss_chunk)


# --------------------------------------------------------------------------
# Scan-over-layers form: per-layer params stacked on a leading [L, ...] dim
# and the block applied with lax.scan — one layer's HLO traced once instead
# of n_layer times (compile time and program size drop ~n_layer-fold; the
# math is identical). This is the TPU-idiomatic big-model form.
# --------------------------------------------------------------------------

def stacked_init_params(cfg: GPT2Config, key):
    """init_params in stacked form: {embed leaves, "blocks": {k: [L, ...]}}."""
    params = init_params(cfg, key)
    out = {k: params[k] for k in ("wte", "wpe", "ln_f_g", "ln_f_b")}
    out["blocks"] = stack_block_params(params, cfg)
    return out


def hidden_states_stacked(params, tokens, cfg: GPT2Config, attn_impl=None):
    """tokens: int32 [B, T] -> final hidden [B, T, D], scanning the
    stacked block params (one layer's HLO traced once)."""
    x = _embedded(params, tokens, cfg)

    def body(h, layer_params):
        return transformer_block(layer_params, h, cfg, attn_impl), None

    if cfg.remat and cfg.remat_policy in ("full", "save_attn"):
        # The forms a gradient-accumulation step can reach into
        # (layers.scan_blocks): every block rematerialised, all of it
        # ("full") or but for what its flash kernel's forward pass gave
        # ("save_attn"; what the walk keeps of any block). A policy that
        # saves more stays below.
        if cfg.remat_policy == "full":
            body = rematerialised_whole(body)
        x, _ = scan_blocks(body, x, params["blocks"])
    else:
        if cfg.remat:
            body = jax.checkpoint(body, **_remat_kwargs(cfg))
        x, _ = jax.lax.scan(body, x, params["blocks"])
    return _final_norm(params, x)


def forward_stacked(params, tokens, cfg: GPT2Config, attn_impl=None):
    """tokens: int32 [B, T] -> logits [B, T, vocab] (fp32), scanning the
    stacked block params."""
    x = hidden_states_stacked(params, tokens, cfg, attn_impl)
    return (x @ params["wte"].T).astype(jnp.float32)


def loss_fn_stacked(params, tokens, cfg: GPT2Config, attn_impl=None):
    x = hidden_states_stacked(params, tokens[:, :-1], cfg, attn_impl)
    return cross_entropy(x, params["wte"], tokens[:, 1:], cfg.loss_chunk)


# --------------------------------------------------------------------------
# Stacked-parameter form for the collective (single-program) pipeline:
# per-layer block params stacked on a leading layer dim, shardable over a
# 'stage' mesh axis (ops/collective_pipeline.py).
# --------------------------------------------------------------------------

def stack_block_params(params, cfg: GPT2Config):
    """h0..hN per-layer dicts -> one dict of [L, ...] stacked leaves."""
    keys = params["h0"].keys()
    return {k: jnp.stack([params[f"h{i}"][k] for i in range(cfg.n_layer)])
            for k in keys}


# Megatron-style TP placement of the stacked block leaves over a model
# axis: column-split the up-projections (their biases follow), row-split
# the down-projections (GSPMD inserts the psum), replicate norms and
# residual biases. Dims are relative to the [..., d_in, d_out] tail of
# the [S, L/S, ...] stacked leaves. The FUSED qkv weight is special: its
# column thirds are the Q/K/V slabs, so a column shard only aligns with
# the later jnp.split when tp % 3 == 0 — otherwise it is row-split
# (valid TP; one psum before the bias) to avoid boundary-crossing
# reshards (r4 review finding).
_TP_DIM_FROM_END = {
    "mlp_fc_w": 1, "mlp_fc_b": 1,
    "attn_proj_w": 2, "mlp_proj_w": 2,
}


def _tp_dim_from_end(name: str, tp: int) -> Optional[int]:
    if name == "attn_qkv_w":
        return 1 if tp % 3 == 0 else 2
    if name == "attn_qkv_b":
        return 1 if tp % 3 == 0 else None
    return _TP_DIM_FROM_END.get(name)


def shard_stacked_for_stages(params, cfg: GPT2Config, mesh,
                             axis: str = "stage",
                             model_axis: Optional[str] = None):
    """Split full params into (embed_leaves, stage-sharded stacked blocks)
    for the collective pipeline. Validates device count and divisibility.

    ``model_axis``: additionally shard each stage's weights over a model
    axis of the SAME mesh (Megatron column/row pattern) — the PP x TP
    placement `collective_pipeline(..., model_axis=...)` consumes."""
    from jax.sharding import NamedSharding, PartitionSpec

    S = mesh.shape[axis]
    tp = mesh.shape[model_axis] if model_axis else 1
    if len(mesh.devices.flat) != S * tp:
        raise ValueError(f"mesh has {len(mesh.devices.flat)} devices; "
                         f"{axis}x{model_axis or '-'} covers {S * tp}")
    if cfg.n_layer % S:
        raise ValueError(f"n_layer={cfg.n_layer} not divisible by "
                         f"{S} stages")
    stacked = stack_block_params(params, cfg)
    stacked = jax.tree_util.tree_map(
        lambda a: a.reshape((S, cfg.n_layer // S) + a.shape[1:]), stacked)

    def spec_for(name, a):
        parts = [axis] + [None] * (a.ndim - 1)
        d_from_end = _tp_dim_from_end(name, tp) if model_axis else None
        if d_from_end is not None:
            d = a.ndim - d_from_end
            if a.shape[d] % tp == 0:
                parts[d] = model_axis
            else:
                import logging
                logging.getLogger(__name__).warning(
                    "TP placement: %s dim %d (size %d) not divisible by "
                    "%s=%d — leaf stays replicated over the model axis",
                    name, d, a.shape[d], model_axis, tp)
        while parts and parts[-1] is None:
            parts.pop()
        return PartitionSpec(*parts)

    stacked = {k: jax.device_put(a, NamedSharding(mesh, spec_for(k, a)))
               for k, a in stacked.items()}
    embed = {k: params[k] for k in ("wte", "wpe", "ln_f_g", "ln_f_b")}
    return embed, stacked


def make_stage_fn(cfg: GPT2Config, layers_per_stage: int):
    """Stage body for collective_pipeline: applies this stage's layer slice
    (leading dim layers_per_stage) by scanning transformer_block."""

    def stage_fn(stage_params, x):
        def body(h, layer_params):
            return transformer_block(layer_params, h, cfg), None

        h, _ = jax.lax.scan(body, x, stage_params)
        return h

    return stage_fn


def pipelined_loss_fn(params, stacked_blocks, tokens, cfg: GPT2Config,
                      mesh, num_micro: int, axis: str = "stage",
                      model_axis: Optional[str] = None):
    """Next-token CE with the block stack run as a collective pipeline.

    ``params``: embedding/final-norm leaves (wte/wpe/ln_f_*), replicated.
    ``stacked_blocks``: [S, L/S, ...] leaves sharded over ``axis`` (and,
    with ``model_axis``, Megatron-sharded over it — PP x TP in one jit;
    use shard_stacked_for_stages(..., model_axis=...) for the placement).
    """
    from tepdist_tpu.ops.collective_pipeline import collective_pipeline

    S = mesh.shape[axis]
    layers_per_stage = cfg.n_layer // S
    B, Tfull = tokens.shape
    T = Tfull - 1
    inputs = tokens[:, :-1]
    targets = tokens[:, 1:]
    x = _embedded(params, inputs, cfg)
    # Micro-batch the embedded activations: [M, mb, T, D].
    mb = B // num_micro
    x_micro = x.reshape(num_micro, mb, T, cfg.n_embd)
    pipelined = collective_pipeline(
        make_stage_fn(cfg, layers_per_stage), mesh, axis=axis,
        model_axis=model_axis)
    y_micro = pipelined(stacked_blocks, x_micro)
    y = y_micro.reshape(B, T, cfg.n_embd)
    return cross_entropy(_final_norm(params, y), params["wte"], targets,
                         cfg.loss_chunk)


def fake_batch(cfg: GPT2Config, batch_size: int, seq_len: Optional[int] = None,
               seed: int = 0):
    """FAKE_INPUT-mode batch (reference: fake_input configs / FAKE_INPUT env)."""
    T = seq_len or cfg.n_ctx
    key = jax.random.PRNGKey(seed)
    return jax.random.randint(key, (batch_size, T + 1), 0, cfg.vocab_size,
                              dtype=jnp.int32)
