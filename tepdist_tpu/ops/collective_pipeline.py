"""Collective pipeline parallelism: the whole pipeline in ONE XLA program.

Reference parity: the reference's pipeline is a multi-program task DAG with
NCCL Send/Recv between stages (SURVEY §3.4). The TPU-native alternative —
used here alongside the task-graph runtime — keeps every stage, micro-batch
rotation, and inter-stage transfer INSIDE one jitted program: stages live on
a 'stage' mesh axis, activations hop stage->stage via ``lax.ppermute`` (one
ICI neighbor hop), and the schedule is a ``lax.scan`` over S+M-1 ticks
(GPipe wavefront). XLA overlaps the permute with the next tick's compute,
and autodiff differentiates straight through (ppermute transposes to the
reverse permute), so fwd+bwd+optimizer all stay in a single compilation —
no host round-trips between micro-batches at all.

Requirements: homogeneous stages (same stage_fn, stacked per-stage params)
— the standard transformer-stack shape. Heterogeneous graphs use the
task-graph runtime instead.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_local(stage_params, x_micro, *, stage_fn, axis: str,
                    num_stages: int, num_micro: int, vary_axes=None):
    """Per-device body under shard_map: runs the GPipe wavefront.

    stage_params: this stage's params (leading stage dim of size 1 squeezed
    by shard_map in_specs). x_micro: [M, mb, ...] replicated micro batches.
    Returns [M, mb, ...] pipeline outputs, replicated via a final psum mask.
    """
    S, M = num_stages, num_micro
    idx = lax.axis_index(axis)
    T = S + M - 1
    mb_shape = x_micro.shape[1:]

    perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t):
        state, out_buf = carry
        # Stage 0 ingests micro batch t (zeros once drained).
        feed = jnp.where(t < M, x_micro[jnp.minimum(t, M - 1)],
                         jnp.zeros(mb_shape, x_micro.dtype))
        inp = jnp.where(idx == 0, feed, state)
        y = stage_fn(stage_params, inp)
        # Last stage banks micro t-(S-1) when valid.
        mi = t - (S - 1)
        valid = jnp.logical_and(idx == S - 1,
                                jnp.logical_and(mi >= 0, mi < M))
        out_buf = lax.cond(
            valid,
            lambda b: lax.dynamic_update_index_in_dim(
                b, y, jnp.maximum(mi, 0), 0),
            lambda b: b,
            out_buf)
        state = lax.ppermute(y, axis, perm)
        return (state, out_buf), None

    state0 = jnp.zeros(mb_shape, x_micro.dtype)
    out0 = jnp.zeros((M,) + mb_shape, x_micro.dtype)
    vary = tuple(vary_axes) if vary_axes else (axis,)
    state0 = lax.pcast(state0, vary, to="varying")
    out0 = lax.pcast(out0, vary, to="varying")
    (_, out_buf), _ = lax.scan(tick, (state0, out0), jnp.arange(T))
    # Only the last stage holds real outputs; psum makes them replicated.
    mask = (idx == S - 1).astype(x_micro.dtype)
    return lax.psum(out_buf * mask, axis)


def collective_pipeline(
    stage_fn: Callable,
    mesh: Mesh,
    axis: str = "stage",
    data_axis: Optional[str] = None,
    model_axis: Optional[str] = None,
    stage_param_spec: Optional[Any] = None,
) -> Callable:
    """Build ``pipelined(stacked_params, x_micro) -> y_micro``.

    ``stacked_params``: pytree whose leaves have a leading stage dim of size
    S (sharded over ``axis`` — each device holds its stage's slice).
    ``x_micro``: [M, mb, ...] micro-batched input.
    ``stage_fn(params_slice, x) -> y`` with y.shape == x.shape.

    ``data_axis``: optional second mesh axis for PP x DP hybrid — the
    micro-batch row dim (dim 1 of x_micro) shards over it, params replicate
    over it, and activations hop stage->stage WITHIN each data slice (the
    reference's nested stage x spmd ordinals, one program).

    ``model_axis``: optional third mesh axis for PP x TP hybrid (the
    reference's 3-ordinal stage x spmd nesting). The pipeline wavefront
    stays MANUAL over ``axis``/``data_axis`` (ppermute hops) while
    ``model_axis`` is left in AUTO mode: shard the stacked params over it
    before the call (e.g. ``device_put`` with a ``P(axis, ..., model)``
    NamedSharding) and GSPMD propagates the TP sharding through every
    stage_fn application, inserting the intra-stage collectives — stages,
    dp and tp compose in ONE jitted program.
    """
    S = mesh.shape[axis]

    def pipelined(stacked_params, x_micro):
        M = x_micro.shape[0]
        vary = (axis,) + ((data_axis,) if data_axis else ())
        local = functools.partial(
            _pipeline_local, stage_fn=stage_fn, axis=axis,
            num_stages=S, num_micro=M, vary_axes=vary)
        param_specs = jax.tree_util.tree_map(
            lambda _: P(axis), stacked_params)
        x_spec = P(None, data_axis) if data_axis else P()
        kw = {}
        if model_axis is not None:
            # Partial-manual shard_map: the model axis stays auto (GSPMD).
            kw["axis_names"] = {axis} | (
                {data_axis} if data_axis else set())
        inner = shard_map(
            lambda p, x: local(
                jax.tree_util.tree_map(lambda a: a[0], p), x),
            mesh=mesh,
            in_specs=(param_specs, x_spec),
            out_specs=x_spec,
            **kw,
        )
        return inner(stacked_params, x_micro)

    return pipelined


def sequential_reference(stage_fn: Callable, stacked_params, x_micro):
    """Unpipelined semantics for testing: apply stages in order per micro."""
    S = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

    def apply_all(x):
        def body(h, s):
            p = jax.tree_util.tree_map(lambda a: a[s], stacked_params)
            return stage_fn(p, h), None

        h, _ = lax.scan(body, x, jnp.arange(S))
        return h

    return jax.vmap(apply_all)(x_micro)
