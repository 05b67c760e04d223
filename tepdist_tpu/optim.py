"""Memory-lean optimizers for single-chip large-model training.

``adamw_bf16`` stores BOTH Adam moments in bfloat16 (optax's ``mu_dtype``
only covers the first moment): optimizer state drops from 12 bytes/param
to 4 bytes/param, which is what lets GPT-2 1.5B train with Adam on one
16 GB v5e chip. All moment math runs in fp32; only the *storage* is bf16.

Reference parity: the reference's ZeRO-style ``MemSavePlan``
(cost_spmd_strategy.h:900-911) attacks optimizer memory by sharding state
across devices; on a single chip the TPU-native lever is storage dtype
instead. Composes with ``apply_mem_save`` sharding when devices allow.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax


class AdamBf16State(NamedTuple):
    count: jnp.ndarray
    mu: optax.Params
    nu: optax.Params


def scale_by_adam_bf16(b1: float = 0.9, b2: float = 0.95,
                       eps: float = 1e-8) -> optax.GradientTransformation:
    """Adam moment tracking with bf16 moment storage, fp32 math."""

    def init(params):
        zeros = lambda p: jnp.zeros_like(p, dtype=jnp.bfloat16)
        return AdamBf16State(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(zeros, params),
            nu=jax.tree_util.tree_map(zeros, params))

    def update(grads, state, params=None):
        del params
        count = state.count + 1
        f32 = lambda t: t.astype(jnp.float32)

        def upd_mu(g, m):
            return b1 * f32(m) + (1 - b1) * f32(g)

        def upd_nu(g, n):
            return b2 * f32(n) + (1 - b2) * jnp.square(f32(g))

        mu32 = jax.tree_util.tree_map(upd_mu, grads, state.mu)
        nu32 = jax.tree_util.tree_map(upd_nu, grads, state.nu)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)

        def direction(m, n, g):
            # Cast straight back to the grad/param dtype: a full fp32
            # updates tree would cost 4 bytes/param of transient HBM.
            return ((m / c1) / (jnp.sqrt(n / c2) + eps)).astype(g.dtype)

        updates = jax.tree_util.tree_map(direction, mu32, nu32, grads)
        bf16 = lambda t: t.astype(jnp.bfloat16)
        return updates, AdamBf16State(
            count=count,
            mu=jax.tree_util.tree_map(bf16, mu32),
            nu=jax.tree_util.tree_map(bf16, nu32))

    return optax.GradientTransformation(init, update)


def adamw_bf16(learning_rate: float, b1: float = 0.9, b2: float = 0.95,
               eps: float = 1e-8, weight_decay: float = 0.01,
               mask: Optional[optax.Params] = None
               ) -> optax.GradientTransformation:
    """AdamW with bf16 moment storage (4 bytes/param optimizer state)."""
    return optax.chain(
        scale_by_adam_bf16(b1=b1, b2=b2, eps=eps),
        optax.add_decayed_weights(weight_decay, mask=mask),
        optax.scale(-learning_rate),
    )


def sign_and_centre(rate: float) -> optax.GradientTransformation:
    """The update of a router's selection bias (auxiliary-loss-free load
    balancing, torchtitan's form): what arrives as the leaf's "gradient" is
    ``n``, how often the step's batch chose each expert (the last axis;
    any scale: only its order about the mean is read), and the update is
    ``delta - mean(delta)`` with ``delta = rate * sign(mean(n) - n)``: an
    expert chosen less than the average is raised, and the biases keep
    their sum. No state: the bias is its own."""

    def update(counts, state, params=None):
        del params

        def one(n):
            n = n.astype(jnp.float32)
            delta = rate * jnp.sign(n.mean(-1, keepdims=True) - n)
            return delta - delta.mean(-1, keepdims=True)

        return jax.tree_util.tree_map(one, counts), state

    return optax.GradientTransformation(lambda params: optax.EmptyState(),
                                        update)


ROUTER_BIAS = "router_bias"


def adamw_bf16_router_bias(learning_rate: float, bias_rate: float = 0.001,
                           **adamw) -> optax.GradientTransformation:
    """``adamw_bf16`` for every leaf but those named ``router_bias``, which
    take :func:`sign_and_centre` at ``bias_rate`` (their "gradient" is the
    step's per-expert counts: ``models/afmoe.py``). A leaf is labelled by
    its own path, so a sub-tree's state has the paths the whole tree's has."""

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "bias" if getattr(
                path[-1], "key", None) == ROUTER_BIAS else "adamw", params)

    return optax.multi_transform(
        {"adamw": adamw_bf16(learning_rate, **adamw),
         "bias": sign_and_centre(bias_rate)}, labels)


# ----------------------------------------------------------------------
# Declarative optimizer specs (the wire form of an optimizer)
# ----------------------------------------------------------------------
#
# The RPC service's fully-automatic explore mode (reference:
# RunExplorationlMode invoked from BuildExecutionPlan,
# service/parallel/auto_parallel.cc:236 + service_rt.cc:218-308) may pick
# a PIPELINE stage cut, which the server materializes by composing
# per-stage optimizer applies itself — so the client ships the optimizer
# declaratively (name + hyperparams) instead of as opaque traced jaxprs
# (a whole-model update jaxpr cannot be re-cut per stage).

_OPTIMIZERS = {
    "sgd": optax.sgd,
    "adam": optax.adam,
    "adamw": optax.adamw,
    "adamw_bf16": adamw_bf16,
    "adamw_bf16_router_bias": adamw_bf16_router_bias,
}


def optimizer_spec(name: str, **kwargs) -> dict:
    """Build a wire-serializable optimizer spec; validates the name."""
    if name not in _OPTIMIZERS:
        raise KeyError(f"unknown optimizer {name!r}; "
                       f"known: {sorted(_OPTIMIZERS)}")
    return {"name": name, **kwargs}


def make_optimizer(spec: dict):
    """Reconstruct the optax transform from its wire spec."""
    spec = dict(spec)
    name = spec.pop("name")
    if name not in _OPTIMIZERS:
        raise KeyError(f"unknown optimizer {name!r}; "
                       f"known: {sorted(_OPTIMIZERS)}")
    return _OPTIMIZERS[name](**spec)
