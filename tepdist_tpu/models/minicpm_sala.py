"""MiniCPM-SALA (openbmb ``MiniCPM-SALA``, ``model_type: minicpm_sala``, 9B:
32 layers, hidden 4096, a SwiGLU MLP of 16,384 in every layer, vocabulary
73,448 untied; 8 InfLLM-v2 block-sparse attention layers among 24 Lightning
linear-attention layers, ``mixer_types`` says which).

The stream, with MiniCPM's three scalings:

    h = scale_emb * E[tokens]
    h = h + r * mixer(rms(h; input_ln));   r = scale_depth / sqrt(published
    h = h + r * W_down(silu(W_gate a) * (W_up a)),  a = rms(h; ff_ln)   layers)
    logits = (rms(h; norm_f) / (hidden_size / dim_model_base)) W_head^T

``lightning-attn`` mixer (``H`` heads of ``D``; ``a`` the normed input):

    q, k, v = a Wq, a Wk, a Wv;  q, k = rms per head (q_norm, k_norm)
    q, k = rope(q), rope(k) (theta, the whole head);  q = q / sqrt(D)
    S_t = lam_h S_{t-1} + k_t^T v_t;  o_t = q_t S_t          (no softmax)
    out = (sigmoid(a Wg) * rms(o; o_norm over all H D channels)) Wo

with ``lam_h = exp(-slope_h)``, ``slope_h = 2^(-8 (h + 1) / H) * (1 - l /
(published layers - 1) + 1e-5)`` for published layer ``l``
(:func:`log_decays`; the kernel, ``ops/pallas/lightning_attention.py``,
takes ``log lam`` as an operand).

``minicpm4`` mixer (``H`` query heads over ``Hkv`` key/value heads, no
position encoding, QK-norm per head): up to ``dense_len`` positions plain
causal softmax attention (the flash kernels); past it InfLLM-v2, each query
token attending to the keys at or before it in ``topk`` key blocks chosen
for its key/value group (``ops/pallas/block_topk_attention.py``: the choice
and the kernels); then ``out = (sigmoid(a Wg) * o) Wo``.

**A block's token-wise parts run in chunks of the sequence** (norms,
projections, rotary, gates, the MLP: ``models/layers.py:over_sequence``),
each chunk rematerialised in the block's own backward, so a block's working
set holds ``[T, hidden]`` arrays and never a ``[T, intermediate]`` one; only
the two mixing kernels see the whole sequence. The chunk is chosen from the
shapes (``tokens_a_chunk``). Gradients of a weight are summed over the chunks
in the weight's dtype, as a gradient-accumulation step sums micro batches.

bf16 weights and activations; norms, rotary, gates' sigmoid, softmax
statistics, the linear-attention state and the loss in float32. Parameters:
``l{i}`` per-layer dicts (``init_params``), or **a stack a run of
consecutive layers of one kind** (``stacked_init_params``), each walked with
``models/layers.py:scan_blocks`` in the published order, its leaves in two
groups at the top of the tree: ``run{r}`` the matrices and ``vec{r}`` the
norm gains, so a check of a step can name a walk's small leaves.
``loss_fn`` takes either layout.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tepdist_tpu.models.decoder import (
    fake_batch,  # noqa: F401 (the model's, as every decoder's)
    run_stacks,
    runs,
    stack_layers,
    walk_layers,
)
from tepdist_tpu.models.layers import (
    cross_entropy,
    over_sequence,
    part,
    rms_norm,
)
from tepdist_tpu.ops.pallas.block_topk_attention import (
    BlockGeometry,
    kept_choice,
    topk_attention,
)
from tepdist_tpu.ops.pallas.flash_attention import flash_attention
from tepdist_tpu.ops.pallas.lightning_attention import lightning_attention
from tepdist_tpu.telemetry import traced

traced.declare(
    "topk_attn_dense_calls", "sparse layers a micro batch run as plain "
    "causal attention (a sequence at or under dense_len)")

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

_PUBLISHED = (SPARSE,) + (LIGHTNING,) * 8 + (SPARSE,) + (LIGHTNING,) * 6 \
    + (SPARSE, SPARSE) + (LIGHTNING,) * 4 + (SPARSE,) + (LIGHTNING,) * 6 \
    + (SPARSE,) * 3


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_head_dim: int = 128
    # The layers held, in order, and where the first of them stands among
    # the published ones (the decay slopes and the residual scale are the
    # published depth's, whatever is held).
    mixer_types: Tuple[str, ...] = _PUBLISHED
    first_layer: int = 0
    published_layers: int = 32
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    sparse: BlockGeometry = BlockGeometry()
    dtype: Any = jnp.bfloat16
    # Flash attention tile sizes (0 = kernel default), every block
    # rematerialised in the backward pass (layers.scan_blocks) and the loss
    # chunk: gpt2.GPT2Config's vocabulary.
    flash_block_q: int = 0
    flash_block_k: int = 0
    remat: bool = False
    loss_chunk: int = 0

    @property
    def num_hidden_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_layers)

    @property
    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """(kind, first layer held, layers) of each run of one kind."""
        return runs(self.mixer_types)


CONFIGS: Dict[str, MiniCPMSALAConfig] = {
    "9b": MiniCPMSALAConfig(),
    # The published structure small: 2 key/value groups, runs of 1, 2, 1
    # and 1 layers, and a geometry under which 128 positions are past
    # ``dense_len``.
    "test": MiniCPMSALAConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        lightning_nh=4, lightning_head_dim=16,
        mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE, LIGHTNING),
        published_layers=8, dim_model_base=32,
        sparse=BlockGeometry(block_size=8, kernel_size=4, kernel_stride=2,
                             init_blocks=1, window_size=16, topk=4,
                             dense_len=32),
        dtype=jnp.float32),
}

_OUTSIDE_BLOCKS = ("tok_emb", "lm_head", "norm_f")
# The stacked layout's groups of a run's leaves (``models/decoder.py``), and
# the leaves that are not in the first.
GROUPS = ("run", "vec")
_GROUP_OF = dict.fromkeys(
    ("input_ln", "ff_ln", "q_norm", "k_norm", "o_norm"), "vec")


def log_decays(cfg: MiniCPMSALAConfig, layer: int) -> np.ndarray:
    """``log lam_h`` float32 [H] of held layer ``layer``: Lightning
    Attention-2's slopes with MiniMax-01's per-layer factor, at the layer's
    published index."""
    H = cfg.lightning_nh
    slope = 2.0 ** (-8.0 * np.arange(1, H + 1) / H)
    at = cfg.first_layer + layer
    return (-slope * (1.0 - at / (cfg.published_layers - 1) + 1e-5)).astype(
        np.float32)


def _layer_params(cfg: MiniCPMSALAConfig, kind: str, key, std: float):
    d, f = cfg.hidden_size, cfg.intermediate_size
    ks = jax.random.split(key, 8)

    def norm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(
            cfg.dtype)

    def ones(n):             # a buffer each: a plan donates every leaf
        return jnp.ones((n,), jnp.float32)

    if kind == SPARSE:
        hd = cfg.head_dim
        q_dim, kv_dim = cfg.num_attention_heads * hd, \
            cfg.num_key_value_heads * hd
        more = {}
    else:
        hd = cfg.lightning_head_dim
        q_dim = kv_dim = cfg.lightning_nh * hd
        more = {"o_norm": ones(q_dim)}
    return {"input_ln": ones(d), "ff_ln": ones(d),
            "w_gate": norm(ks[0], (d, f)), "w_up": norm(ks[1], (d, f)),
            "w_down": norm(ks[2], (f, d)),
            "wq": norm(ks[3], (d, q_dim)), "wk": norm(ks[4], (d, kv_dim)),
            "wv": norm(ks[5], (d, kv_dim)), "wg": norm(ks[6], (d, q_dim)),
            "wo": norm(ks[7], (q_dim, d)),
            "q_norm": ones(hd), "k_norm": ones(hd), **more}


def init_params(cfg: MiniCPMSALAConfig, key, std: float = 0.02):
    """normal(std) matrices, unit norm gains; ``l{i}`` per-layer dicts."""
    keys = jax.random.split(key, 2 + cfg.num_hidden_layers)

    def table(k):
        return (jax.random.normal(k, (cfg.vocab_size, cfg.hidden_size),
                                  jnp.float32) * std).astype(cfg.dtype)

    params: Dict[str, Any] = {
        "tok_emb": table(keys[0]), "lm_head": table(keys[1]),
        "norm_f": jnp.ones((cfg.hidden_size,), jnp.float32)}
    for i, kind in enumerate(cfg.mixer_types):
        params[f"l{i}"] = _layer_params(cfg, kind, keys[2 + i], std)
    return params


def stacked_init_params(cfg: MiniCPMSALAConfig, key, std: float = 0.02):
    """``init_params`` with each run of one kind stacked, [layers of the
    run, ...] a leaf, in the run's groups (``run{r}``, ``vec{r}``)."""
    return stack_layers(init_params(cfg, key, std),
                        run_stacks(cfg.mixer_types), _OUTSIDE_BLOCKS, GROUPS,
                        _GROUP_OF)


def rope(x, start, theta: float):
    """Rotary embedding (rotate-half, the whole head) over x [B, T, H, D] at
    positions ``start ..``; float32 inside, back in x's dtype."""
    T, half = x.shape[1], x.shape[3] // 2
    with jax.named_scope("rope_plain"):
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
        angles = (start + jnp.arange(T)).astype(jnp.float32)[:, None] \
            * freqs[None, :]
        cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
        x1, x2 = x[..., :half].astype(jnp.float32), \
            x[..., half:].astype(jnp.float32)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1)


def mixer_inputs(blk, a, cfg: MiniCPMSALAConfig, kind: str, start):
    """The normed input's chunk -> q, k, v as the kind's kernel wants them."""
    B, T, _ = a.shape
    eps = cfg.rms_norm_eps
    hd = cfg.head_dim if kind == SPARSE else cfg.lightning_head_dim
    q = rms_norm((a @ blk["wq"]).reshape(B, T, -1, hd), blk["q_norm"], eps)
    k = rms_norm((a @ blk["wk"]).reshape(B, T, -1, hd), blk["k_norm"], eps)
    v = a @ blk["wv"]
    if kind == SPARSE:
        return q, k, v.reshape(B, T, -1, hd)
    q = (rope(q, start, cfg.rope_theta) / math.sqrt(hd)).astype(a.dtype)
    k = rope(k, start, cfg.rope_theta).astype(a.dtype)
    return q.reshape(B, T, -1), k.reshape(B, T, -1), v


def sparse_attention(q, k, v, cfg: MiniCPMSALAConfig):
    """q [B, T, H, D], k, v [B, T, Hkv, D] -> [B, T, H * D]: plain causal
    attention up to ``dense_len`` positions, the chosen blocks past it."""
    B, T, H, D = q.shape
    if T <= cfg.sparse.dense_len:
        traced.count("topk_attn_dense_calls")
        o = flash_attention(
            *(x.transpose(0, 2, 1, 3) for x in (q, k, v)), causal=True,
            block_q=cfg.flash_block_q or None,
            block_k=cfg.flash_block_k or None).transpose(0, 2, 1, 3)
    else:
        with jax.named_scope("topk_select"):
            idx = kept_choice(q, k, cfg.sparse)
        with jax.named_scope("topk_attend"):
            o = topk_attention(q, k, v, idx, cfg.sparse)
    return o.reshape(B, T, H * D)


def block(blk, x, cfg: MiniCPMSALAConfig, kind: str, log_decay=None):
    """One layer: x [B, T, d] -> [B, T, d]. ``log_decay`` float32 [H]: a
    lightning layer's ``log lam`` (no parameter, no gradient)."""
    eps, r = cfg.rms_norm_eps, cfg.residual_scale
    widest = cfg.intermediate_size

    def before(start, xc):
        return mixer_inputs(blk, rms_norm(xc, blk["input_ln"], eps), cfg,
                            kind, start)

    with part("mixer"):
        with jax.named_scope("mixer_in"):
            q, k, v = over_sequence(before, widest, x)
        if kind == SPARSE:
            o = sparse_attention(q, k, v, cfg)
        else:
            with jax.named_scope("lin_attn"):
                o = lightning_attention(q, k, v, log_decay)

    def after(start, xc, oc):
        del start
        with part("mixer"):
            gate = jax.nn.sigmoid((rms_norm(xc, blk["input_ln"], eps)
                                   @ blk["wg"]).astype(jnp.float32))
            if kind == LIGHTNING:
                oc = rms_norm(oc, blk["o_norm"], eps)
            xc = xc + (r * ((gate * oc).astype(xc.dtype)
                            @ blk["wo"])).astype(xc.dtype)
        with part("mlp"):
            a = rms_norm(xc, blk["ff_ln"], eps)
            up = jax.nn.silu(a @ blk["w_gate"]) * (a @ blk["w_up"])
            return xc + (r * (up @ blk["w_down"])).astype(xc.dtype)

    with jax.named_scope("mixer_out_mlp"):
        return over_sequence(after, widest, x, o)


def hidden_states(params, tokens, cfg: MiniCPMSALAConfig):
    """tokens int32 [B, T] -> final normalised hidden [B, T, d], divided by
    ``hidden_size / dim_model_base`` as the head wants it."""
    with part("embed"):
        x = (params["tok_emb"][tokens].astype(jnp.float32)
             * cfg.scale_emb).astype(cfg.dtype)
    # A layer's kind by what its block holds (``_layer_params``).
    x = walk_layers(
        lambda blk, h, log_decay: block(
            blk, h, cfg, LIGHTNING if "o_norm" in blk else SPARSE, log_decay),
        x, params, run_stacks(cfg.mixer_types),
        [log_decays(cfg, i) for i in range(cfg.num_hidden_layers)],
        cfg.remat, GROUPS)
    with part("head_loss"):
        x = rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
        return (x.astype(jnp.float32)
                / (cfg.hidden_size / cfg.dim_model_base)).astype(x.dtype)


def forward(params, tokens, cfg: MiniCPMSALAConfig):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    x = hidden_states(params, tokens, cfg)
    return (x @ params["lm_head"].T).astype(jnp.float32)


def loss_fn(params, tokens, cfg: MiniCPMSALAConfig):
    """Cross entropy of tokens [B, T+1] through the untied head."""
    x = hidden_states(params, tokens[:, :-1], cfg)
    return cross_entropy(x, params["lm_head"], tokens[:, 1:], cfg.loss_chunk)
