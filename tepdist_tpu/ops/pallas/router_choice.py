"""An expert layer's choice: each token's ``k`` experts, their scores and
those scores' gradient, with no sort, no look-up of single elements and no
scatter. One function, :func:`choose`, behind ``olmoe.router``,
``mellum.router`` and ``afmoe.router``.

``jax.lax.top_k`` sorts: of a router's 770 us a call at ``[8192, 512]``,
``k = 10`` on a v5e the sort was 596, where HBM reads the scores in 20; and
the gradient of its values is a scatter of ``S x k`` numbers into ``[S, E]``,
705 us a call there (PERF.md section 6, PR 60). A look-up of single elements
(``take_along_axis``) runs at 8 ns an element and has the same scatter for a
gradient; a compare-and-sum over ``[S, k, E]`` in its place cost as much as
the sort (209 us at ``[8192, 256]``, ``k = 8``). The kernel here takes 88 us
at the first shape and wins at every ``(E, k)`` the cells have, 64 to 512
experts and ``k`` of 6 to 10 (``tools/router_bench.py``), so there is one
form. What is here:

- **the ids** by ``k`` rounds of max-and-mask in one kernel,
  ``tepdist_router_choice``, **experts on the major axis and tokens on the
  lanes**: a round takes every token's highest key, the lowest expert that
  holds it, and takes that expert out. Across ``[E, tokens]`` each of those
  is element-wise between vector registers (one sublane reduction a round
  and 128 tokens), six vector operations a register and round, and the
  scores are read from HBM once. The scores go through it as keys, the
  float's bits as an int32 whose signed order is the floats' total order
  (``-inf`` lowest, ``-0.0`` under ``+0.0``), so that a taken expert's mark,
  the lowest int32, lies under every score that is no NaN: a row of
  ``-inf`` is chosen from as ``lax.top_k`` chooses;
- **the scores of the chosen** out of the same rounds: a round's maximum
  where the choice is made by the scores that are read, else (a selection
  bias: ``select``) the sum over the experts of the read score where the
  round's expert is, one term of it no zero;
- **their gradient** by compare-and-sum, ``d scores[s, e] = sum_j (ids[s, j]
  == e) * d chosen[s, j]``, a sum of ``k`` selects that XLA fuses with what
  reads it (no ``[S, k, E]`` array is ever named).

The transpositions round the kernel are the compiler's to place: a
``[S, E]`` array laid out experts-major is the router matmul's output in
another order, and the compiled steps hold no copy for them
(``tests/test_tpu_compile_qwen3_next.py``).

The kernel's name shows in a device trace and in the compiled HLO. Off a
TPU (the CPU tests) the same rounds run as XLA operations on the whole
arrays, not as an interpreted kernel (``_call``); ``interpret=True`` is the
kernel interpreted, which ``tests/test_router_choice.py`` holds to them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tepdist_tpu.telemetry import traced

LANES = 128
BLOCK = 1024                # tokens a grid step, at most
_CHUNK_ELEMENTS = 64 * 1024  # [E, tokens] a pass of the rounds: 64 registers
_TAKEN = -2 ** 31           # under every key of a score that is no NaN

traced.declare(
    "router_choice_calls", "differentiated expert choices a micro batch "
    "(ops/pallas/router_choice.py:choose): one a routed layer whose router "
    "goes through the shared choice (a layer whose token-wise parts run in "
    "chunks of the sequence counts once)")


def _flipped(bits):
    """A float32's bits as int32 <-> the int32 whose signed order is the
    floats' total order: a negative's low 31 bits flipped, its own
    inverse."""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _keys(select):
    """The scores the choice is made by, float32, as the rounds' keys."""
    return _flipped(jax.lax.bitcast_convert_type(select, jnp.int32))


def _round(key, read):
    """One round over ``key`` [E, W]: (every token's highest key's lowest
    expert [1, W], that expert's score [1, W], ``key`` with it taken out).
    ``read`` [E, W]: the scores that are read, where they are not the keys'
    own (else None)."""
    E = key.shape[0]
    expert = jax.lax.broadcasted_iota(jnp.int32, key.shape, 0)
    top = jnp.max(key, axis=0, keepdims=True)
    idx = jnp.min(jnp.where(key == top, expert, E), axis=0, keepdims=True)
    hit = expert == idx
    chosen = jax.lax.bitcast_convert_type(_flipped(top), jnp.float32) \
        if read is None else jnp.sum(
            jnp.where(hit, read, 0.0), axis=0, keepdims=True)
    return idx, chosen, jnp.where(hit, _TAKEN, key)


def _kernel(*refs, k: int, width: int):
    """``refs``: ``select`` [E, block] (and ``read`` [E, block], where the
    chosen scores are not the ones chosen by), then ``ids`` and ``chosen``
    [k, block]. ``width`` tokens at a time through the ``k`` rounds."""
    select_ref, read_ref = refs[0], refs[-3]
    ids_ref, chosen_ref = refs[-2:]

    def chunk(c, carry):
        lanes = pl.ds(pl.multiple_of(c * width, width), width)
        key = _keys(select_ref[:, lanes])
        read = None if read_ref is select_ref else read_ref[:, lanes]
        for j in range(k):      # (the last round's mask feeds nothing)
            ids_ref[j:j + 1, lanes], chosen_ref[j:j + 1, lanes], key = \
                _round(key, read)
        return carry

    jax.lax.fori_loop(0, select_ref.shape[1] // width, chunk, 0)


def _largest(n: int, most: int) -> int:
    """The largest power-of-two multiple of 128 that divides ``n`` (itself a
    multiple of 128) and is at most ``most``."""
    size = LANES
    while size * 2 <= most and n % (size * 2) == 0:
        size *= 2
    return size


def _call(scores, select, k: int, interpret):
    """``scores`` and ``select`` (or None) [E, S] float32 -> (chosen float32
    [k, S], ids int32 [k, S]). ``interpret`` None: the kernel on a TPU, and
    off it the same rounds as XLA operations on the whole arrays (the CPU
    tests: an interpreted kernel traces and compiles 0.8 s longer a call,
    and the suite compiles the routers some hundreds of times); True or
    False: the kernel, interpreted or compiled."""
    if interpret is None and jax.default_backend() == "cpu":
        def one(j, carry):
            chosen, ids, key = carry
            idx, score, key = _round(key, None if select is None else scores)
            return (jax.lax.dynamic_update_slice(chosen, score, (j, 0)),
                    jax.lax.dynamic_update_slice(ids, idx, (j, 0)), key)

        S = scores.shape[1]
        return jax.lax.fori_loop(0, k, one, (
            jnp.zeros((k, S), jnp.float32), jnp.zeros((k, S), jnp.int32),
            _keys(scores if select is None else select)))[:2]
    arrays = (scores,) if select is None else (select, scores)
    E, S = scores.shape
    rows, lanes = -(-E // 8) * 8, -(-S // LANES) * LANES
    if (rows, lanes) != (E, S):
        # Whole registers: experts that no token can prefer to a real one
        # (-inf, and a tie goes to the lower index), tokens nobody reads.
        arrays = [jnp.pad(a, ((0, rows - E), (0, lanes - S)),
                          constant_values=-jnp.inf) for a in arrays]
    block = _largest(lanes, BLOCK)
    width = _largest(block, max(_CHUNK_ELEMENTS // rows, LANES))
    ids, chosen = pl.pallas_call(
        functools.partial(_kernel, k=k, width=width),
        name="tepdist_router_choice",
        grid=(lanes // block,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (0, i))
                  for _ in arrays],
        out_specs=[pl.BlockSpec((k, block), lambda i: (0, i))] * 2,
        out_shape=[jax.ShapeDtypeStruct((k, lanes), jnp.int32),
                   jax.ShapeDtypeStruct((k, lanes), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=8 * k * rows * lanes, transcendentals=0,
            bytes_accessed=4 * lanes * (len(arrays) * rows + 2 * k)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=bool(interpret),
    )(*arrays)
    return chosen[:, :S], ids[:, :S]


# ``scores`` and ``select`` [E, S] (``select`` None: the scores themselves);
# ``static``: (k, E, interpret, layers), ``layers`` the runs one trace of
# the call stands for, as ``_flash`` takes it, for the forward rule's count
# of ``router_choice_calls``.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _chosen(scores, select, static):
    return _call(scores, select, static[0], static[2])


def _chosen_fwd(scores, select, static):
    traced.count("router_choice_calls", layers=static[-1])
    chosen, ids = _call(scores, select, static[0], static[2])
    return (chosen, ids), ids


def _chosen_bwd(static, ids, cts):
    k, E = static[:2]
    expert = jax.lax.broadcasted_iota(jnp.int32, (E, ids.shape[1]), 0)
    d_scores = functools.reduce(jnp.add, (
        jnp.where(ids[j:j + 1] == expert, cts[0][j:j + 1], 0.0)
        for j in range(k)))
    return d_scores, None


_chosen.defvjp(_chosen_fwd, _chosen_bwd)


def choose(scores, k: int, select=None, interpret=None):
    """scores [S, E] float32 -> (chosen float32 [S, k], ids int32 [S, k]):
    each token's ``k`` highest experts and their scores.

    **The set and its order are ``jax.lax.top_k``'s**: descending score, a
    tie to the lower expert index (among equal scores the lower index comes
    first, and at the ``k``-th place it is the one chosen), for every score
    that is no NaN, ``-inf`` included; floats compare in their total order
    (``-0.0`` under ``+0.0``, as ``lax.top_k`` has it). ``chosen`` is
    ``take_along_axis(scores, ids)`` bit for bit.

    ``select`` [S, E] float32: the choice is made by these and the chosen
    scores are read from ``scores`` (a router with a selection bias). No
    gradient reaches ``select``; ``scores`` receives ``d scores[s, e] =
    sum_j (ids[s, j] == e) * d chosen[s, j]``.

    ``interpret``: None, or the kernel interpreted (True) or compiled
    (False) whatever the backend (:func:`_call`).
    """
    if scores.ndim != 2 or scores.dtype != jnp.float32 \
            or not 0 < k <= scores.shape[1] \
            or (select is not None and (select.shape, select.dtype)
                != (scores.shape, scores.dtype)):
        raise ValueError(f"choose: scores {scores.dtype}{scores.shape}, "
                         f"k {k}, select {getattr(select, 'shape', None)}")
    chosen, ids = _chosen(
        scores.T, None if select is None else select.T,
        (k, scores.shape[1], interpret, traced.stood_for()))
    return chosen.T, ids.T
