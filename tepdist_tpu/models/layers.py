"""Pieces more than one model of the zoo uses: RMSNorm, rotary positions and
the (optionally chunked) next-token cross entropy. One copy, so that a
change for one model is seen by the others' tests and benchmark cells."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, g, eps: float = 1e-5):
    """x / rms(x) * g over the last dim, in float32, back in x's dtype."""
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (x32 * scale * g).astype(x.dtype)


def rope(x, theta: float):
    """Rotary embedding over [B, H, T, hd] (rotate-half formulation)."""
    B, H, T, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, None, :, :]
    sin = jnp.sin(angles)[None, None, :, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def cross_entropy(x, head, targets, chunk: int = 0):
    """Mean next-token cross entropy from final hidden states ``x``
    [B, T, D] through the output head ``head`` [V, D] (GPT-2 hands in its
    tied embedding, a model with an untied head that head), optionally
    chunked.

    Dense path: logits = x @ head.T in one [B, T, V] fp32 tensor. Chunked
    path (chunk > 0): lax.scan over token chunks with the chunk body
    checkpointed — forward AND backward hold only [chunk, V] logits at a
    time; the backward recomputes each chunk's logits from the saved
    [chunk, D] hidden slice. Summation order changes (per-chunk partial
    sums), so results match the dense path to float tolerance, not
    bit-exactly."""
    B, T, D = x.shape
    n_tokens = B * T
    if chunk <= 0:
        logits = (x @ head.T).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    # Non-dividing counts get a zero-padded, masked tail chunk — the LM
    # loss always shifts tokens (n_tokens = B*(T-1) at the call site), so
    # a divisibility fallback would silently disable chunking for every
    # power-of-two chunk size.
    n_chunks = -(-n_tokens // chunk)
    pad = n_chunks * chunk - n_tokens
    xf = x.reshape(n_tokens, D)
    tf = targets.reshape(n_tokens)
    valid = jnp.ones((n_tokens,), jnp.float32)
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad, D), x.dtype)])
        tf = jnp.concatenate([tf, jnp.zeros((pad,), targets.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.float32)])
    xf = xf.reshape(n_chunks, chunk, D)
    tf = tf.reshape(n_chunks, chunk)
    valid = valid.reshape(n_chunks, chunk)

    @jax.checkpoint
    def body(acc, inp):
        xc, tc, mc = inp
        logits = (xc @ head.T).astype(jnp.float32)       # [chunk, V]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return acc + jnp.sum((logz - gold) * mc), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (xf, tf, valid))
    return total / n_tokens
