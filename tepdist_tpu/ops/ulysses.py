"""Ulysses (DeepSpeed-style) sequence parallelism via head<->sequence
all-to-all.

Reference parity: NONE in the reference (SURVEY.md §5.7) — first-class here.
Mechanism: with sequence sharded over ``axis_name`` (P devices) and H heads,
an all-to-all re-shards [B, H, T/P, D] -> [B, H/P, T, D]; attention then runs
with FULL sequence locally on H/P heads, and a second all-to-all restores
sequence sharding. Both all-to-alls ride ICI; requires H % P == 0.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool,
                   scale: Optional[float],
                   inner: Optional[Callable],
                   return_lse: bool = False):
    # Local shapes: [B, H, T/P, D]. all_to_all: split heads, gather seq.
    def to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)   # [B, H/P, T, D]
    if return_lse:
        from tepdist_tpu.ops.pallas.flash_attention import (
            flash_attention_with_lse,
        )
        fn = inner or functools.partial(flash_attention_with_lse,
                                        causal=causal, scale=scale)
        oh, lseh = fn(qh, kh, vh)                        # lse [B, H/P, T]
        # Transport the LSE back with the same head<->seq all-to-all
        # (one trailing singleton dim to match the 4-d transpose).
        lse = to_seq(lseh[..., None])[..., 0]            # [B, H, T/P]
        return to_seq(oh), lse
    if inner is None:
        from tepdist_tpu.ops.ring_attention import reference_attention
        oh = reference_attention(qh, kh, vh, causal=causal, scale=scale)
    else:
        oh = inner(qh, kh, vh)
    return to_seq(oh)                                     # [B, H, T/P, D]


def ulysses_attention(q, k, v, mesh: Mesh, axis_name: str = "seq",
                      causal: bool = True, scale: Optional[float] = None,
                      inner: Optional[Callable] = None,
                      return_lse: bool = False):
    """Sequence-parallel attention via double all-to-all. q,k,v: [B,H,T,D]
    with T sharded over ``axis_name``; H must be divisible by the axis size.
    ``inner`` optionally overrides the local attention (e.g. a pallas flash
    kernel). ``return_lse``: also return the [B, H, T] log-sum-exp —
    ``inner`` must then return (o, lse) (default: the pallas
    flash_attention_with_lse)."""
    H = q.shape[1]
    size = mesh.shape[axis_name]
    if H % size != 0:
        raise ValueError(f"heads {H} not divisible by axis {axis_name}={size}")
    spec = P(None, None, axis_name, None)
    fn = functools.partial(_ulysses_local, axis_name=axis_name,
                           causal=causal, scale=scale, inner=inner,
                           return_lse=return_lse)
    return shard_map(
        fn, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, P(None, None, axis_name)) if return_lse else spec,
        # pallas_call inner kernels don't annotate varying-mesh-axes (vma);
        # skip the check so flash-attention inners compose.
        check_vma=False,
    )(q, k, v)
