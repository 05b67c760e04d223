"""What the decoders of the zoo share of their bookkeeping, so that a new one
brings its mixers, its block and its configuration and nothing else: the two
parameter layouts (``l{i}`` per-layer dicts, or the layers stacked: a stack
each run of one kind, or each of the model's own names) with the loop over
the layers of either, the experts a layer holds of its router's, the heads a
layer holds of the model's, and the batch of random tokens. The pieces of the layers themselves are
``models/layers.py``.

A stack is ``(name, first layer, layers)``; its leaves lie under the
top-level keys ``group + name`` for each of the model's ``groups`` (a run's
number after ``run`` / ``vec`` / ``decay``; a name of its own after the one
group ``""``), so that an optimizer's exception or a check of a step can name
a walk's small leaves without its matrices."""

from __future__ import annotations

from typing import (
    Any,
    Callable,
    Dict,
    Hashable,
    Optional,
    Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from tepdist_tpu.models.layers import held_routing_stats, scan_blocks

Stack = Tuple[Any, int, int]
# The leaves of a SwiGLU expert layer, [experts, ...] each: what a model
# names to ``walk_layers`` for the grouped-matmul kernels to read in place
# (an expert without a gate matrix has the last two).
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def fake_batch(cfg, batch_size: int, seq_len: Optional[int] = None,
               seed: int = 0):
    """Uniform random tokens int32 [batch_size, seq_len + 1] over
    ``cfg.vocab_size``: a sequence and its next tokens (``seq_len`` None:
    ``cfg.max_position_embeddings``, for a configuration that has one)."""
    T = seq_len or cfg.max_position_embeddings
    return jax.random.randint(jax.random.PRNGKey(seed),
                              (batch_size, T + 1), 0, cfg.vocab_size,
                              dtype=jnp.int32)


def runs(kinds: Sequence) -> Tuple[Tuple[Any, int, int], ...]:
    """(kind, first layer, layers) of each run of one kind, in order."""
    out = []
    for i, kind in enumerate(kinds):
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, i, 1))
    return tuple(out)


def units(kinds: Sequence[str], closing: str) -> Tuple[str, ...]:
    """The layers of a model whose layer is one part alone (a mixer, or a
    feed-forward part) in units a walk takes as its layers: a unit ends with
    a part of kind ``closing`` (the feed-forward part, as every other
    model's layer ends with one) or before a kind it already holds, so that
    a unit's parts have leaves of different names and lie side by side in
    one dict. A kind is a letter and a unit the string of its parts' kinds:
    ``"MEMEM*EME"`` closing with ``"E"`` is ``("ME", "ME", "M*E", "ME")``,
    three runs where the layers alone are nine."""
    out = [""]
    for kind in kinds:
        if out[-1].endswith(closing) or kind in out[-1]:
            out.append("")
        out[-1] += kind
    return tuple(u for u in out if u)


def run_stacks(kinds: Sequence) -> Tuple[Stack, ...]:
    """A stack a run of one kind: (run, first layer, layers)."""
    return tuple((r, first, count)
                 for r, (_, first, count) in enumerate(runs(kinds)))


def run_blocks(params, name, groups: Sequence[str] = ("",)) -> Dict[str, Any]:
    """The stacked leaves of stack ``name`` (a run's number, or the stack's
    own name), its groups side by side: the tree's own leaves, so a
    gradient-accumulation step finds the walk over them."""
    return {k: v for g in groups
            for k, v in params.get(f"{g}{name}", {}).items()}


def stack_layers(params, stacks: Sequence[Stack], outside: Sequence[str],
                 groups: Sequence[str] = ("",),
                 group_of: Optional[Dict[str, str]] = None):
    """``l{i}`` per-layer dicts -> the stacked layout: the leaves ``outside``
    the blocks as they are and each stack's layers stacked, [layers of the
    stack, ...] a leaf, leaf ``k`` in group ``group_of[k]`` (the first of
    ``groups`` where it names none)."""
    out = {k: params[k] for k in outside}
    for name, first, count in stacks:
        layers = [params[f"l{i}"] for i in range(first, first + count)]
        for k in layers[0]:
            group = (group_of or {}).get(k, groups[0])
            out.setdefault(f"{group}{name}", {})[k] = jnp.stack(
                [blk[k] for blk in layers])
    return out


def layer_dicts(params, stacks: Sequence[Stack],
                groups: Sequence[str] = ("",)):
    """Every layer's own dict, in order, whichever the layout."""
    if "l0" in params:
        return [params[f"l{i}"] for _, first, count in stacks
                for i in range(first, first + count)]
    return [jax.tree_util.tree_map(lambda a, i=i: a[i],
                                   run_blocks(params, name, groups))
            for name, _, count in stacks for i in range(count)]


def walk_layers(layer: Callable, x, params, stacks: Sequence[Stack],
                rows: Sequence, remat: bool, groups: Sequence[str] = ("",),
                experts: Sequence[str] = ()):
    """``x`` through every layer in order, whichever the layout:
    ``layer(blk, h, row) -> h`` with ``rows[i]`` what layer ``i`` is given
    besides its parameters: its kind (hashable), or a NumPy row of numbers
    that are no parameter and have no gradient. ``x`` (and every ``h``) is
    the walk's carry, an array or a pytree of arrays (``scan_blocks``): a
    model whose layers hand on more than the residual stream walks the pair.

    ``l{i}`` dicts: a Python loop, each layer under ``jax.checkpoint`` with
    ``remat``; a kind is static, a row of numbers an array. Stacked: a
    ``models/layers.py:scan_blocks`` a stack. A stack of one kind has it as
    the Python value; one of unequal kinds (of one shape: a window here,
    none there) hands each layer its entry of the int32 array of them,
    traced, for the layer to branch on by ``lax.cond``; rows of numbers
    ride beside the blocks likewise.

    ``experts``: the names of the leaves that hold a layer's experts'
    weights ``[experts, K, N]`` and that ``layer`` hands to
    ``ops/grouped_matmul.py:routed_experts`` as they are and uses nowhere
    else. Where a stack has them (``[layers, experts, K, N]``; a dense
    stack's leaf of the same name is no such) a walk that accumulates
    gradients gives the layer an ``ExpertStack`` for each
    (``scan_blocks(in_place=)``). A layer that runs its experts once a chunk
    of the sequence hands them to ``over_sequence(weights=)`` and closes
    over none of them (``models/sarvam_mla.py:block``)."""
    if "l0" in params:
        for i, row in enumerate(rows):
            static = isinstance(row, Hashable)
            step = jax.checkpoint(
                layer, static_argnums=(2,) if static else ()) if remat \
                else layer
            x = step(params[f"l{i}"], x, row if static else jnp.asarray(row))
        return x
    for name, first, count in stacks:
        mine = list(rows[first:first + count])
        kinds = isinstance(mine[0], Hashable)
        ride = None if kinds and len(set(mine)) == 1 else np.asarray(
            mine, np.int32 if kinds else None)
        blocks = run_blocks(params, name, groups)
        x = scan_blocks(
            lambda h, blk, row=mine[0]: (layer(blk, h, row), None), x,
            blocks, ride, remat, tuple(
                k for k in experts if k in blocks and blocks[k].ndim == 4))[0]
    return x


# -- an expert layer that holds a share of the router's experts --------------

def held_mask(experts, held: Tuple[int, int]):
    """Which of the chosen ``experts`` the layer holds: ``held = (first,
    count)`` of the router's."""
    first, count = held
    return (experts >= first) & (experts < first + count)


def held_weights(weights, experts, held: Tuple[int, int], num_experts: int):
    """``weights`` with 0 for a choice held elsewhere, which is what
    ``ops/grouped_matmul.py:routed_experts`` asks of its caller; as they are
    where the layer holds every expert."""
    if held[1] >= num_experts:
        return weights
    return jnp.where(held_mask(experts, held), weights, 0.0)


# -- an attention layer that holds a share of the heads -----------------------

def held_heads(w, held: Tuple[int, int], width: int, axis: int = -1):
    """The held heads' part of a matrix laid out a head after a head along
    ``axis``, ``width`` entries each: the columns of a projection to the
    heads (``axis`` -1) or the rows of the one back from them (0), for
    ``held = (first, count)`` of the model's heads."""
    first, count = held
    return jax.lax.slice_in_dim(w, first * width, (first + count) * width,
                                axis=axis % w.ndim)


def routing_stats(expert_choices: Callable, params, tokens, cfg) -> dict:
    """What the routers did with ``tokens`` [B, T+1], outside any step: the
    expert ids of every expert layer (``experts`` [layers, S, k], the
    model's ``expert_choices(params, tokens, cfg)``), the rows each held
    expert got (``held_rows`` [layers, count]) and the counters and gauges
    of ``models/layers.py:held_routing_stats``."""
    return held_routing_stats(
        expert_choices(params, tokens[:, :-1], cfg), cfg.num_experts,
        cfg.moe_tile_m, cfg.experts_held)
