"""Jamba (AI21 ``AI21-Jamba2-3B``, ``model_type: jamba``: 28 layers, hidden
2560, Mamba-1 state-space layers of 5120 channels and 16 states around one
multi-query attention layer in 14, a SwiGLU MLP of 8192 in every layer,
vocabulary 65,536 tied, no positional encoding).

Layer ``i`` is attention iff ``i % attn_layer_period == attn_layer_offset``,
Mamba otherwise. Both kinds, pre-norm, RMSNorm without biases:

    x = x + mixer(rms(x; input_ln))
    a = rms(x; ff_ln);  x = x + W_down (silu(W_gate a) * (W_up a))

Attention mixer: ``q = a Wq`` (H heads), ``k = a Wk``, ``v = a Wv`` (Hkv
heads shared by H / Hkv query heads each), causal softmax attention at scale
``head_dim ** -0.5``, no rotary embedding and no QK-norm, then ``Wo``.

Mamba mixer, for a sequence ``a_1..a_T``:

    [u, z] = a W_in                                  (Di channels each)
    c_t = silu(b_conv + sum_j w_conv[j] * u_{t-(d_conv-1)+j})   (depth-wise,
                                        causal, zeros before the sequence)
    [r, B, C] = c W_x;  r, B, C = rms(r; dt_norm), rms(B; b_norm), rms(C; c_norm)
    delta = softplus(r W_dt + b_dt);  A = -exp(A_log)
    h_t = exp(delta_t A) * h_{t-1} + (delta_t * c_t) B_t^T,  h_0 = 0
    y_t = h_t C_t + D * c_t;   out = (y * silu(z)) W_out

with ``x0 = tok_emb[tokens]``, a final RMSNorm and logits through the tied
embedding. The recurrence runs in ``ops/pallas/selective_scan.py`` (chunked
over the sequence, the state carried in VMEM; float32 state, ``delta``,
``exp`` and accumulation), the conv with its SiLU in
``ops/pallas/causal_conv.py`` (float32 products, sums and SiLU).

bf16 weights and activations; norms, ``delta``, the scan, softmax statistics
and the loss in float32; ``A_log``, ``D`` and ``dt_bias`` float32 leaves.
Parameters: ``l{i}`` per-layer dicts (``init_params``), or **a stack a run
of layers of one kind** (``stacked_init_params``: run 0 the Mamba layers
before the first attention layer, run 1 that attention layer, run 2 the
Mamba layers after it, ...), each walked with ``models/layers.py:scan_blocks``
in the published order, so that every run's leaves are the parameter tree's
own and a gradient-accumulation step finds them. A run's stacked leaves lie
in up to three groups at the top of the tree, by what they are (``GROUPS``):
``run{r}`` the matmuls' matrices, ``vec{r}`` the per-channel leaves (norm
gains, the conv's taps and bias, ``dt_bias``, ``D``) and, for a Mamba run,
``decay{r}`` its ``A_log``, the one leaf whose gradient only the scan's
state reaches; so an optimizer's exception or a check of a step can name the
small leaves of a walk without its matrices. ``loss_fn`` takes either layout.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from tepdist_tpu.models.decoder import (
    fake_batch,  # noqa: F401 (the model's, as every decoder's)
    run_stacks,
    runs,
    stack_layers,
    walk_layers,
)
from tepdist_tpu.models.layers import (
    cross_entropy,
    gqa_heads,
    part,
    rms_norm,
)
from tepdist_tpu.ops.pallas.causal_conv import causal_conv
from tepdist_tpu.ops.pallas.selective_scan import (
    BLOCK_D,
    CHUNK,
    selective_scan,
)

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # Flash attention tile sizes (0 = kernel default), every block
    # rematerialised in the backward pass (layers.scan_blocks) and the loss
    # chunk: gpt2.GPT2Config's vocabulary. The scan kernel's time steps and
    # channels a grid step.
    flash_block_q: int = 0
    flash_block_k: int = 0
    remat: bool = False
    loss_chunk: int = 0
    ssm_chunk: int = CHUNK
    ssm_block_d: int = BLOCK_D

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(
            ATTENTION if i % self.attn_layer_period == self.attn_layer_offset
            else MAMBA for i in range(self.num_hidden_layers))

    @property
    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """(kind, first layer, layers) of each run of one kind, in order."""
        return runs(self.layer_kinds)


CONFIGS: Dict[str, JambaConfig] = {
    "2-3b": JambaConfig(),
    # Attention at layer 2 of 5: runs of 2, 1 and 2 layers.
    "test": JambaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=1,
        attn_layer_period=4, attn_layer_offset=2, mamba_d_state=8,
        mamba_dt_rank=8, dtype=jnp.float32, ssm_chunk=16),
}

_OUTSIDE_BLOCKS = ("tok_emb", "norm_f")
# The stacked layout's groups of a run's leaves (``models/decoder.py``), and
# the leaves that are not in the first.
GROUPS = ("run", "vec", "decay")
_GROUP_OF = {**dict.fromkeys(
    ("input_ln", "ff_ln", "conv_w", "conv_b", "dt_norm", "b_norm", "c_norm",
     "dt_bias", "D"), "vec"), "A_log": "decay"}


def _layer_params(cfg: JambaConfig, kind: str, key, std: float):
    d, f, Di = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
    N, R, hd = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.head_dim
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    ks = jax.random.split(key, 10)
    f32 = jnp.float32

    def norm(k, shape):
        return (jax.random.normal(k, shape, f32) * std).astype(cfg.dtype)

    def ones(n):             # a buffer each: a plan donates every leaf
        return jnp.ones((n,), f32)

    mlp = {"input_ln": ones(d), "ff_ln": ones(d),
           "w_gate": norm(ks[0], (d, f)), "w_up": norm(ks[1], (d, f)),
           "w_down": norm(ks[2], (f, d))}
    if kind == ATTENTION:
        return {**mlp,
                "wq": norm(ks[3], (d, H * hd)), "wk": norm(ks[4], (d, Hkv * hd)),
                "wv": norm(ks[5], (d, Hkv * hd)), "wo": norm(ks[6], (H * hd, d))}
    # Mamba-1's published initialisation: dt = exp(U(log 1e-3, log 1e-1)),
    # the bias its inverse softplus; A_log = log(1..N) for every channel.
    dt = jnp.exp(jax.random.uniform(
        ks[9], (Di,), f32, jnp.log(1e-3), jnp.log(1e-1)))
    return {**mlp,
            "in_proj": norm(ks[3], (d, 2 * Di)),
            "conv_w": norm(ks[4], (cfg.mamba_d_conv, Di)),
            "conv_b": jnp.zeros((Di,), cfg.dtype),
            "x_proj": norm(ks[5], (Di, R + 2 * N)),
            "dt_norm": ones(R), "b_norm": ones(N), "c_norm": ones(N),
            "dt_proj": norm(ks[6], (R, Di)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=f32)), (Di, N)),
            "D": ones(Di),
            "out_proj": norm(ks[7], (Di, d))}


def init_params(cfg: JambaConfig, key, std: float = 0.02) -> Dict[str, Any]:
    """normal(std) matrices, unit norm gains, Mamba-1's ``A_log``, ``D`` and
    ``dt_bias``; ``l{i}`` per-layer dicts."""
    keys = jax.random.split(key, 1 + cfg.num_hidden_layers)
    params: Dict[str, Any] = {
        "tok_emb": (jax.random.normal(
            keys[0], (cfg.vocab_size, cfg.hidden_size), jnp.float32)
            * std).astype(cfg.dtype),
        "norm_f": jnp.ones((cfg.hidden_size,), jnp.float32)}
    for i, kind in enumerate(cfg.layer_kinds):
        params[f"l{i}"] = _layer_params(cfg, kind, keys[1 + i], std)
    return params


def stacked_init_params(cfg: JambaConfig, key, std: float = 0.02):
    """``init_params`` with each run of one kind stacked, [layers of the
    run, ...] a leaf, in the run's groups (``run{r}``, ``vec{r}``,
    ``decay{r}``)."""
    return stack_layers(init_params(cfg, key, std),
                        run_stacks(cfg.layer_kinds), _OUTSIDE_BLOCKS, GROUPS,
                        _GROUP_OF)


def attention(blk, a, cfg: JambaConfig):
    """a [B, T, d] (the normed input) -> the heads through ``wo``."""
    o = gqa_heads(
        blk, a, n_head=cfg.num_attention_heads,
        n_kv_head=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        eps=cfg.rms_norm_eps, window=0, windowed=False,
        block_q=cfg.flash_block_q, block_k=cfg.flash_block_k)
    return o @ blk["wo"]


# The two below are elementwise work over the mixer's and the MLP's widest
# arrays with float32 intermediates. Each is rematerialised inside the
# block's own backward pass (its float32 intermediates are made again from
# its bf16 operands, not held), which changes no value. The convolution
# before the scan is a kernel pair whose backward does the same
# (``ops/pallas/causal_conv.py``).
@jax.checkpoint
def step_sizes(r, dt_proj, dt_bias):
    """``delta = softplus(r W_dt + b_dt)`` in float32."""
    return jax.nn.softplus(
        jnp.dot(r, dt_proj, preferred_element_type=jnp.float32) + dt_bias)


@jax.checkpoint
def gated(gate, up):
    return jax.nn.silu(gate) * up


def mamba_mixer(blk, a, cfg: JambaConfig):
    """a [B, T, d] (the normed input) -> the state-space mixer's output."""
    N, R, eps = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.rms_norm_eps
    with jax.named_scope("ssm_in_proj"):
        u, z = jnp.split(a @ blk["in_proj"], 2, axis=-1)
    with jax.named_scope("ssm_conv"):
        c = causal_conv(u, blk["conv_w"], blk["conv_b"])
    with jax.named_scope("ssm_scan"):
        r, B, C = jnp.split(c @ blk["x_proj"], (R, R + N), axis=-1)
        r = rms_norm(r, blk["dt_norm"], eps)
        B = rms_norm(B, blk["b_norm"], eps)
        C = rms_norm(C, blk["c_norm"], eps)
        y = selective_scan(
            c, step_sizes(r, blk["dt_proj"], blk["dt_bias"]),
            -jnp.exp(blk["A_log"].astype(jnp.float32)), B, C, blk["D"], z,
            chunk=cfg.ssm_chunk, block_d=cfg.ssm_block_d)
    with jax.named_scope("ssm_out_proj"):
        return y @ blk["out_proj"]


def mlp(blk, a):
    return gated(a @ blk["w_gate"], a @ blk["w_up"]) @ blk["w_down"]


def block(blk, x, cfg: JambaConfig, kind: str):
    eps = cfg.rms_norm_eps
    mixer = attention if kind == ATTENTION else mamba_mixer
    with part("mixer"):
        x = x + mixer(blk, rms_norm(x, blk["input_ln"], eps), cfg)
    with part("mlp"):
        return x + mlp(blk, rms_norm(x, blk["ff_ln"], eps))


def hidden_states(params, tokens, cfg: JambaConfig):
    """tokens int32 [B, T] -> final normalised hidden [B, T, d]."""
    with part("embed"):
        x = params["tok_emb"][tokens].astype(cfg.dtype)
    x = walk_layers(lambda blk, h, kind: block(blk, h, cfg, kind), x,
                    params, run_stacks(cfg.layer_kinds), cfg.layer_kinds,
                    cfg.remat, GROUPS)
    with part("head_loss"):
        return rms_norm(x, params["norm_f"], cfg.rms_norm_eps)


def forward(params, tokens, cfg: JambaConfig):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    x = hidden_states(params, tokens, cfg)
    return (x @ params["tok_emb"].T).astype(jnp.float32)


def loss_fn(params, tokens, cfg: JambaConfig):
    """Cross entropy of tokens [B, T+1] over the tied embedding."""
    x = hidden_states(params, tokens[:, :-1], cfg)
    return cross_entropy(x, params["tok_emb"], tokens[:, 1:], cfg.loss_chunk)
