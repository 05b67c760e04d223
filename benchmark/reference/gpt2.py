"""Plain float32 GPT-2: forward, next-token loss and gradients.

The yardstick every cell's ``correct`` is decided against. Straight
``jax.numpy`` following the published model (Radford et al. 2019; OpenAI
``gpt-2/src/model.py``): learned position embeddings, pre-LayerNorm blocks,
fused qkv projection, causal softmax attention, tanh-GELU MLP of width 4d,
final LayerNorm, logits through the tied embedding. No kernel, no cache, no
batching tricks, no import from the program under test.

Everything is computed in float32 with ``precision=highest`` (on a TPU a
float32 matmul otherwise runs in bf16 passes). Departures from a textbook
implementation, all of them about memory and none about arithmetic:

* weights arrive in the dtype they are served in (bf16 values are exact in
  float32) and are widened where they are used, so a 1.5B model needs no
  second float32 copy of itself;
* each block is wrapped in ``jax.checkpoint`` so the backward pass of 48
  layers fits beside the weights;
* ``cast`` is applied to both operands of every matmul. The identity gives
  the reference; the control (``fp8_cast``) puts the reference in the
  program's place one precision step below bf16.

Parameter layout: ``wte`` [V, d], ``wpe`` [n_ctx, d], ``ln_f_g``, ``ln_f_b``
and ``blocks``: a list of per-layer dicts, or one dict of the same leaves
stacked on a leading layer axis, which is walked with ``lax.scan`` (the same
block, the same order; 48 layers then compile as one) (``ln1_g``, ``ln1_b``,
``attn_qkv_w`` [d, 3d], ``attn_qkv_b``, ``attn_proj_w``, ``attn_proj_b``,
``ln2_g``, ``ln2_b``, ``mlp_fc_w`` [d, 4d], ``mlp_fc_b``, ``mlp_proj_w``,
``mlp_proj_b``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def identity(x):
    return x


@jax.custom_vjp
def fp8_cast(x):
    """Per-tensor scaled e4m3 rounding: what a matmul operand looks like
    one precision step below bf16 (the control, never the reference). The
    gradient passes straight through, as a scaled-fp8 training recipe has
    it: only the forward operands are rounded, which is the mildest form
    of the step down and so the hardest for a limit to catch."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


fp8_cast.defvjp(lambda x: (fp8_cast(x), None), lambda _, g: (g,))


def _mm(a, b, cast):
    return jnp.matmul(cast(a.astype(F32)), cast(b.astype(F32)),
                      precision=HIGHEST)


def _layer_norm(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(F32) \
        + b.astype(F32)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(blk, x, n_head, cast):
    B, T, d = x.shape
    hd = d // n_head
    h = _layer_norm(x, blk["ln1_g"], blk["ln1_b"])
    qkv = _mm(h, blk["attn_qkv_w"], cast) + blk["attn_qkv_b"].astype(F32)
    q, k, v = (t.reshape(B, T, n_head, hd).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("bhqd,bhkd->bhqk", cast(q), cast(k),
                   precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", cast(p), cast(v), precision=HIGHEST)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, d)
    x = x + _mm(o, blk["attn_proj_w"], cast) \
        + blk["attn_proj_b"].astype(F32)
    h = _layer_norm(x, blk["ln2_g"], blk["ln2_b"])
    h = _gelu(_mm(h, blk["mlp_fc_w"], cast) + blk["mlp_fc_b"].astype(F32))
    return x + _mm(h, blk["mlp_proj_w"], cast) \
        + blk["mlp_proj_b"].astype(F32)


def hidden(params, tokens, n_head, cast=identity):
    """tokens int32 [B, T] -> final normalised hidden states [B, T, d]."""
    T = tokens.shape[1]
    x = params["wte"][tokens].astype(F32) + params["wpe"][:T].astype(F32)
    block = jax.checkpoint(lambda blk, h: _block(blk, h, n_head, cast))
    if isinstance(params["blocks"], dict):      # one [L, ...] array a leaf
        x, _ = jax.lax.scan(lambda h, blk: (block(blk, h), None), x,
                            params["blocks"])
    else:
        for blk in params["blocks"]:
            x = block(blk, x)
    return _layer_norm(x, params["ln_f_g"], params["ln_f_b"])


def logits(params, tokens, n_head, cast=identity):
    """tokens int32 [B, T] -> float32 logits [B, T, V]."""
    return _mm(hidden(params, tokens, n_head, cast), params["wte"].T, cast)


def loss(params, tokens, n_head, cast=identity, weights=None):
    """Next-token cross entropy of tokens [B, T+1] (inputs are the first T,
    targets the last T), one sequence's logits at a time: the mean over
    the batch, or with ``weights`` [B] the sum of each sequence's mean
    weighted by its entry (a batch that repeats sequences is then computed
    from the distinct ones)."""
    x = hidden(params, tokens[:, :-1], n_head, cast)
    targets = tokens[:, 1:]
    B, T = targets.shape
    if weights is None:
        weights = jnp.full((B,), 1.0 / B, F32)

    @jax.checkpoint
    def one(xs, ts):
        lg = _mm(xs, params["wte"].T, cast)
        gold = jnp.take_along_axis(lg, ts[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    return sum(one(x[i], targets[i]) * weights[i] for i in range(B)) / T
