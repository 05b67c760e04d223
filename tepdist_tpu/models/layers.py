"""Pieces more than one model of the zoo uses: RMSNorm, rotary positions
(the plain table and YaRN's), grouped-query attention over layers of two
kinds (QK-norm where the block has the gains for it: ``q_norm`` / ``k_norm``
among its leaves), the (optionally chunked) next-token cross entropy, the
walk over stacked blocks, a block's token-wise parts in chunks of the
sequence and the counters of an expert layer that holds a share of the
experts. One copy, so that a change for one model is seen by the others'
tests and benchmark cells. What decoders share of their bookkeeping (runs of
layers, the two parameter layouts, held experts) is ``models/decoder.py``.

A model may interleave walks of different shape: layers of one shape and
unequal kind share a stack (``scan_blocks(..., kinds=)``), layers of unequal
shape take a stack a run and a :func:`scan_blocks` call each, in the model's
own order (``models/jamba.py``). A gradient-accumulation step finds every
such walk whose stack is the parameter tree's own leaves."""

from __future__ import annotations

import contextvars
import functools
import math
from typing import Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from tepdist_tpu.ops.grouped_matmul import (
    at_rows,
    counting_kernel_calls,
    layout_index,
    layout_rows,
    route,
)
from tepdist_tpu.ops.pallas.grouped_matmul import ExpertStack
from tepdist_tpu.ops.pallas.flash_attention import (
    KeptForward,
    flash_attention_kept,
    hand_over,
    nothing_kept,
)
from tepdist_tpu.telemetry import metrics, traced

# The step's device time by the program's own parts: every model puts its
# work under one of these six ``jax.named_scope``s and the walk below puts its
# three phases round a block's runs, so a profile groups a step's operations
# the same way on every model (xprof's framework-operation view; the
# benchmark's ``scope_*_share.train`` readers). A scope is spelt ``part_<name>``
# and a phase ``walk_<name>``: JAX wraps scopes in its transforms' names
# (``transpose(jvp(part_mixer/mla_q))/dot_general``), so a reader looks for the
# whole word anywhere in an operation's name stack, and no primitive, einsum
# string or other scope of this repo is spelt so. Metadata only: a scope changes
# no instruction of the compiled step.
PARTS = ("embed", "mixer", "mlp", "moe", "head_loss", "optimizer")
PHASES = WALK_FWD, WALK_RECOMPUTE, WALK_BWD = (
    "walk_fwd", "walk_recompute", "walk_bwd")


def part(name: str):
    """``with part("mixer"):`` the operations traced inside belong to that
    part of the step (one of :data:`PARTS`)."""
    if name not in PARTS:
        raise ValueError(f"{name!r} is no part of a step: {PARTS}")
    return jax.named_scope("part_" + name)


traced.declare(
    "attn_kept_calls", "calls a micro batch (a flash, block top-k, latent or "
    "delta-rule call, a sparse layer's choice) that hand what their forward "
    "pass made to the backward pass, which does not run it again")
traced.declare(
    "attn_kept_bytes", "bytes of output, log-sum-exp, chosen sets, states "
    "and chunk inverses those calls keep from a micro batch's forward to its "
    "backward")
traced.declare(
    "moe_rows_sum_calls", "calls a micro batch of the expert layers' "
    "row-copy kernel (ops/pallas/rows_sum.py): 2 a walked layer that holds "
    "a share of the experts, 0 where the XLA gathers stayed")
traced.declare(
    "moe_stack_in_place_calls", "grouped-matmul calls a micro batch that "
    "read their weights out of the layers' stack or added into its "
    "accumulator where they lie: 12 a walked expert layer (a layer whose "
    "token-wise parts run in chunks of the sequence counts once: a chunk's "
    "trace stands for its chunks), 0 where the walk hands the kernels "
    "slices")
traced.declare(
    "moe_epilogue_calls", "grouped-matmul calls a micro batch that carry "
    "an epilogue (ops/grouped_matmul.py:activation): 2 a walked expert "
    "layer, the up projection's activation in the walk's first forward "
    "and the second input gradient's addend; 1 where an expert has two "
    "matrices and so one input gradient")
traced.declare(
    "ce_fused_chunks", "chunks of the loss whose gradients its forward chunk "
    "loop makes (0: the dense loss, or a call nobody differentiates)")
traced.declare(
    "ce_weighted_positions", "positions a micro batch hands to a cross "
    "entropy with a weight a position (0: every loss is a plain mean)")


class BlockGradSink:
    """What a gradient-accumulation step hands to :func:`scan_blocks`
    while it traces or differentiates a loss (``with BlockGradSink(...)``).

    ``leaves`` maps ``id(parameter leaf)`` to the caller's key for it; a
    walk over stacked leaves that are all in it is noted in ``walks`` as the
    tuple of their keys, any other walk is the plain scan. With ``acc=None``
    the sink only records: a noted walk returns zeros without reading its
    leaves, so that any other use of one shows in the traced jaxpr. With
    ``acc`` (key -> accumulator leaf, an input of the differentiation) a
    noted walk adds each layer's weight gradient into the accumulator inside
    the backward layer loop and hands the sum back as the accumulator's
    cotangent."""

    def __init__(self, leaves: Dict[int, int],
                 acc: Optional[Dict[int, jax.Array]] = None):
        self.leaves = leaves
        self.acc = acc
        self.walks: List[Tuple[int, ...]] = []

    def __enter__(self):
        self._token = _SINK.set(self)
        return self

    def __exit__(self, *exc):
        _SINK.reset(self._token)


_SINK: contextvars.ContextVar[Optional[BlockGradSink]] = \
    contextvars.ContextVar("tepdist_block_grad_sink", default=None)


def scan_blocks(body, x, blocks, kinds=None, remat: bool = True,
                in_place: Tuple[str, ...] = ()):
    """``jax.lax.scan(jax.checkpoint(body), x, blocks)``: ``body(h, block)
    -> (h, y)`` over blocks stacked on a leading layer dim, every block
    rematerialised in the backward pass but, where the backward is written
    out (below), for its attention kernels' output and log-sum-exp. Returns
    ``(x, ys)``. ``remat=False`` is the plain ``jax.lax.scan(body, ...)``.

    **The carry ``x`` is a pytree of arrays**, as ``lax.scan``'s is: one
    array for most models, ``(x, r)`` for one whose layers hand a second
    state on (``models/zaya.py``: the router's). Every path below treats it
    by ``tree_map`` and asks nothing of its structure; a block's kept input
    is then the whole carry as the block received it.

    ``kinds`` (a NumPy array, one entry a layer, no parameter and no
    gradient) makes it ``body(h, block, kind)``: layers of one shape and
    unequal kind (a window here, none there) in one stack, the body
    choosing by ``lax.cond`` on its layer's entry.

    One trace of ``body`` stands for every layer of the stack: it is traced
    inside ``telemetry/traced.py:stands_for(layers)``, on every path, so
    what a kernel counts of its calls counts once a layer.

    Under a :class:`BlockGradSink` that holds accumulators for ``blocks``
    (``parallel/sync_free.py:build_ga_step`` with several micro batches) the
    backward pass is written out: a reverse scan that recomputes the block
    under ``jax.vjp`` from its saved input (the carry, all its arrays),
    carries ``(dx, accumulators)`` with ``dx`` the carry's cotangent, array
    for array, and adds layer ``l``'s weight gradient into slice ``l`` in
    place, so the stacked gradient of a micro batch is never built. Same
    values as the
    plain scan's gradient added to the accumulator afterwards: the layer's
    gradient is rounded to its dtype, then the sum to the accumulator's.

    ``in_place`` names the leaves of ``blocks`` (a dict) that hold a layer's
    experts' weights, ``[layers, experts, K, N]``, and that the body hands to
    ``ops/grouped_matmul.py:routed_experts`` as they are and uses nowhere
    else. That walk does not scan them: its forward loop, its recomputation
    and its backward give the body an ``ExpertStack`` (the whole stack, which
    no loop changes, the loop's layer index and the stack's accumulator) in
    the leaf's place, the kernels read the layer's tiles where they lie, and
    the backward step takes the accumulator the block's pullback returns (the
    weight gradient added into slice ``l`` by the kernel) in place of ``a[l]
    + g``: no copy of a layer's experts out of the stack, none of their
    gradient in, the same two roundings (``ops/pallas/grouped_matmul.py``;
    the gauge ``moe_stack_in_place_calls``). Every other leaf and every
    other path (the plain scan, the recording pass, ``remat=False``) takes
    slices as before. A body must hand each accumulator back as a cotangent
    once: one that runs its expert layer once a chunk of the sequence
    (``models/sarvam_mla.py``) hands the layer's weights to
    :func:`over_sequence` (``weights=``), whose backward carries the
    accumulator from chunk to chunk where autodiff would sum one a chunk.

    That walk saves, beside a block's input, the ``(o, lse)`` of every flash
    and latent-attention call in it
    (``ops/pallas/flash_attention.py:KeptForward``), of every block top-k
    attention call with its chosen sets
    (``ops/pallas/block_topk_attention.py``), and the ``(o, states, inv)`` of
    every delta-rule call (``ops/pallas/kda_attention.py``: the state before
    every chunk and the chunk's inverse, which its backward kernel reads),
    stacked a layer as the inputs are, and the recomputation takes them
    back: the forward kernel (and the choice) runs once a layer and micro
    batch, not twice, for the kept
    arrays' bytes held from a micro batch's forward to its backward
    (the gauges ``attn_kept_calls`` / ``attn_kept_bytes``, summed over the
    walks of one loss; the walk also counts the calls a layer's expert part
    makes of its row-copy kernel, ``moe_rows_sum_calls``, of the grouped
    matmuls over a stack, ``moe_stack_in_place_calls``, and of those with an
    epilogue, ``moe_epilogue_calls``). The same values:
    they are the arrays the second run would make. A body wrapped in
    :func:`rematerialised_whole` keeps nothing. The plain scan (no sink: one
    micro batch, or a body that closes over a traced value) is left as it
    was, ``jax.checkpoint`` of the whole block, so there the forward kernel
    still runs twice and the gauges read 0: ``jax.checkpoint`` takes no
    arrays from outside, only names on what the kernel's own VJP saves and
    a policy over them, and a body that declines would have to reach into
    that rule, which JAX traces after the body has returned."""
    leaves = jax.tree_util.tree_leaves(blocks)
    # The entry rides beside the block; every path below sees a body of
    # (h, block) or (h, (block, kind)) whose parameters are still
    # ``blocks``' leaves.
    plain, n = body, leaves[0].shape[0]
    if kinds is not None:
        kinds = np.asarray(kinds)

    def body(h, layer):
        with traced.stands_for(n):
            return plain(h, layer) if kinds is None else plain(h, *layer)

    if not remat:
        return jax.lax.scan(body, x,
                            blocks if kinds is None else (blocks, kinds))
    sink = _SINK.get()
    keys = () if sink is None else tuple(
        sink.leaves.get(id(a)) for a in leaves)
    if keys and None not in keys and sink.acc is not None:
        sink.walks.append(keys)
        acc = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(blocks), [sink.acc[k] for k in keys])
        return _walk_accumulating(body, x, blocks, acc, kinds, in_place)
    if keys and None not in keys:
        # Recording. A body that closes over a traced value cannot be
        # differentiated by hand; that walk stays the plain scan.
        def aval(a, drop=0):
            return jax.ShapeDtypeStruct(a.shape[drop:], a.dtype)

        one = jax.tree_util.tree_map(lambda a: aval(a, 1), blocks)
        closed, (h, ys) = jax.make_jaxpr(body, return_shape=True)(
            jax.tree_util.tree_map(aval, x),
            one if kinds is None else (one, aval(kinds, 1)))
        if not any(isinstance(c, jax.core.Tracer) for c in closed.consts):
            sink.walks.append(keys)
            return jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), h), \
                jax.tree_util.tree_map(
                    lambda y: jnp.zeros((n,) + y.shape, y.dtype), ys)
    return jax.lax.scan(jax.checkpoint(body), x,
                        blocks if kinds is None else (blocks, kinds))


def rematerialised_whole(body):
    """``body`` for :func:`scan_blocks` with its flash calls rematerialised
    like the rest of the block: the walk keeps nothing of them (a recipe
    that pins full rematerialisation)."""
    @functools.wraps(body)
    def whole(*args):
        with nothing_kept():
            return body(*args)
    return whole


def _walk_accumulating(body, x, blocks, acc, kinds, in_place=()):
    """:func:`scan_blocks` with the backward written out. ``x``: the carry,
    a pytree; the forward keeps each block's input carry whole (stacked a
    layer, array for array) and the backward carries its cotangent.
    ``in_place``: the keys of ``blocks`` (a dict) whose layer the body is
    handed as an :class:`ExpertStack` and not as a slice."""
    n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    layers = jnp.arange(n_layers)

    def apart(tree):
        """(what is scanned or added to a slice at a time, what stays whole)
        of ``blocks`` or of their accumulators."""
        if not in_place:
            return tree, {}
        return {k: v for k, v in tree.items() if k not in in_place}, \
            {k: tree[k] for k in in_place}

    def whole(layer, block, kind, stacks, into):
        """What the body takes for a layer: its scanned leaves (and entry)
        with the handles of what stayed whole."""
        if in_place:
            index = layer.astype(jnp.int32).reshape(1)
            block = {**block, **{k: ExpertStack(stacks[k], index, into[k])
                                 for k in in_place}}
        return block if kinds is None else (block, kind)

    @jax.custom_vjp
    def walk(x, blocks, acc):
        del acc
        with jax.named_scope(WALK_FWD):
            return jax.lax.scan(body, x,
                                blocks if kinds is None else (blocks, kinds))

    def fwd(x, blocks, acc):
        kernel_calls = []       # one entry a trace of the body: a layer's
        scanned, stacks = apart(blocks)
        into = apart(acc)[1]    # no forward pass writes it

        def step(h, per_layer):
            with KeptForward() as keep, counting_kernel_calls() as calls, \
                    jax.named_scope(WALK_FWD):
                out, y = body(h, whole(*per_layer, stacks, into))
            kernel_calls.append(calls)
            return out, (h, y, keep.kept)

        out, (inputs, ys, kept) = jax.lax.scan(
            step, x, (layers, scanned, kinds))
        traced.count("moe_rows_sum_calls",
                     n_layers * kernel_calls[0]["rows_sum"])
        traced.count("moe_stack_in_place_calls",
                     n_layers * kernel_calls[0]["stack_in_place"])
        traced.count("moe_epilogue_calls",
                     n_layers * kernel_calls[0]["epilogue"])
        traced.count("attn_kept_calls", n_layers * len(kept))
        traced.count("attn_kept_bytes", sum(
            a.nbytes for a in jax.tree_util.tree_leaves(kept)))
        return (out, ys), (inputs, kept, blocks, acc)

    def bwd(res, cts):
        inputs, kept, blocks, acc = res
        d_out, d_ys = cts
        scanned, stacks = apart(blocks)

        def step(carry, per_layer):
            dh, acc = carry
            layer, h, saved, block, kind, d_y = per_layer
            acc, into = apart(acc)

            def recompute(h, block, into):
                with KeptForward(saved):
                    return body(h, whole(layer, block, kind, stacks, into))

            with jax.named_scope(WALK_RECOMPUTE):
                _, pull = jax.vjp(recompute, h, block, into)
            # A stack's accumulator comes back with its layer's gradient
            # added where it lies; the other leaves' are added here.
            with jax.named_scope(WALK_BWD):
                dh, d_block, into = pull((dh, d_y))
                with part("optimizer"):     # gradient accumulation
                    acc = jax.tree_util.tree_map(
                        lambda a, g: jax.lax.dynamic_update_index_in_dim(
                            a, jax.lax.dynamic_index_in_dim(
                                a, layer, keepdims=False) + g.astype(a.dtype),
                            layer, 0),
                        acc, d_block)
            return (dh, {**acc, **into} if in_place else acc), None

        (dx, acc), _ = jax.lax.scan(
            step, (d_out, acc),
            (layers, inputs, kept, scanned, kinds, d_ys), reverse=True)
        return dx, None, acc

    walk.defvjp(fwd, bwd)
    return walk(x, blocks, acc)


# Elements of the widest array a chunk of the sequence may make (a chunk of
# 2,048 tokens at an MLP 16,384 wide: 64 MiB in bf16).
_CHUNK_ELEMENTS = 2 ** 25


def tokens_a_chunk(B: int, T: int, widest: int) -> int:
    """The largest divisor of ``T`` whose ``[B, chunk, widest]`` array stays
    under ``_CHUNK_ELEMENTS`` (``T`` itself where it does, 1 at worst)."""
    most = max(1, _CHUNK_ELEMENTS // (B * widest))
    return next(c for c in range(min(T, most), 0, -1) if T % c == 0)


def over_sequence(fn, widest: int, *xs, weights=None):
    """``fn(start, *chunks)`` over chunks of the sequence (axis 1 of every
    ``x`` [B, T, ...]; ``start`` the chunk's first position), each chunk
    rematerialised in the backward pass; the results [B, T, ...] again.
    ``fn`` returns an array or a tuple of arrays, of any number, trailing
    widths and dtypes: a block's first half hands the mixer between the
    halves its inputs this way (a latent-attention layer's three, a KDA
    layer's five: the projections, the float32 log decays and ``beta``
    [B, T, heads]). For a block's token-wise
    parts (norms, projections, gates, an MLP), so that its working set holds
    ``[T, hidden]`` arrays and never a ``[T, widest]`` one; gradients of a
    weight are summed over the chunks in the weight's dtype.

    ``weights`` (a pytree) hands ``fn`` its parameters, ``fn(weights, start,
    *chunks)``, where it would close over them. One algorithm either way: a
    ``jax.lax.map`` over a ``jax.checkpoint`` of the chunk, whose transpose
    starts every weight's gradient at zeros and adds a chunk's to it, last
    chunk first. Where one of ``weights`` is an :class:`ExpertStack` and the
    sequence is more than one chunk that backward is written out
    (:func:`_chunks_carrying`), the same sums for every plain leaf, because
    a stack's accumulator may not be summed: the weight-gradient kernel
    hands it back with the chunk's gradient added where it lies, and that
    array is the next chunk's accumulator, so a layer inside a walk that
    accumulates gradients (``scan_blocks(in_place=)``) adds each chunk's
    expert gradients straight into the walk's accumulator."""
    B, T = xs[0].shape[:2]
    chunk = tokens_a_chunk(B, T, widest)
    n = T // chunk

    def cut(x):
        return jnp.moveaxis(x.reshape(B, n, chunk, *x.shape[2:]), 1, 0)

    def joined(y):
        return jnp.moveaxis(y, 0, 1).reshape(B, T, *y.shape[3:])

    starts = jnp.arange(n, dtype=jnp.int32) * chunk
    if n > 1 and any(map(_is_stack, jax.tree_util.tree_leaves(
            weights, is_leaf=_is_stack))):
        out = _chunks_carrying(fn, weights, starts, tuple(map(cut, xs)))
        return jax.tree_util.tree_map(joined, out)
    if weights is not None:
        fn = functools.partial(fn, weights)
    fn = jax.checkpoint(fn)
    if n == 1:
        return fn(jnp.int32(0), *xs)
    out = jax.lax.map(lambda args: fn(*args), (starts, *map(cut, xs)))
    return jax.tree_util.tree_map(joined, out)


def _is_stack(w) -> bool:
    return isinstance(w, ExpertStack)


def _chunks_carrying(fn, weights, starts, xs):
    """``jax.lax.map(lambda start, *chunks: fn(weights, start, *chunks),
    (starts, *xs))`` (``xs``: the chunks stacked, [n, B, chunk, ...]) with
    the backward written out: a scan over the chunks, last first as the
    transposed ``lax.map`` takes them, that makes a chunk again under
    ``jax.vjp`` of its ``jax.checkpoint`` and carries

    * for every plain leaf of ``weights`` the sum of the chunks' gradients
      in the leaf's dtype, from zeros (what the transposed loop carries);
    * for every :class:`ExpertStack` its ``into``: the chunk's pullback
      returns the accumulator with that chunk's gradient added into slice
      ``layer`` by ``tepdist_gmm_dw``, and that array, not a sum of such
      arrays, is the next chunk's. After the last chunk it is the cotangent
      of the stack's accumulator, which is what
      ``_walk_accumulating``'s backward step expects of a body.

    The stacks and their layer indices are closed over by what is
    differentiated and never its operands (a pullback makes zeros, as large,
    for an operand nothing reaches), as in ``ops/grouped_matmul.py:_switch``.
    Against the sums of the plain form an expert leaf's total loses one
    rounding a layer and micro batch: there the chunks' gradients are added
    to a zeroed carry and the total to the accumulator, here each straight
    to the accumulator."""
    ws, tree = jax.tree_util.tree_flatten(weights, is_leaf=_is_stack)
    # The backward rule is traced after the walk's body has returned: what
    # it traces of ``fn`` stands for the layers this call stands for.
    layers = traced.stood_for()

    def whole(plain, fixed, intos):
        """``weights`` again: its plain leaves, its stacks with their layer
        indices, and the stacks' accumulators."""
        plain = iter(plain)
        handed = (ExpertStack(*at, into) for at, into in zip(fixed, intos))
        return tree.unflatten([
            next(handed if _is_stack(w) else plain) for w in ws])

    def over(plain, fixed, intos, xs):
        weights = whole(plain, fixed, intos)
        return jax.lax.map(lambda args: fn(weights, *args), (starts, *xs))

    chunked = jax.custom_vjp(over)

    def fwd(plain, fixed, intos, xs):
        return over(plain, fixed, intos, xs), (plain, fixed, intos, xs)

    def bwd(res, d_out):
        plain, fixed, intos, xs = res

        def step(carry, per_chunk):
            sums, intos = carry
            start, chunks, d = per_chunk
            _, pull = jax.vjp(
                jax.checkpoint(lambda plain, intos, *chunks: fn(
                    whole(plain, fixed, intos), start, *chunks)),
                plain, intos, *chunks)
            # A stack's accumulator comes back with this chunk's gradient
            # added where it lies; the plain leaves' are added here.
            d_plain, intos, *d_chunks = pull(d)
            with part("optimizer"):     # gradient accumulation
                sums = [s + g for s, g in zip(sums, d_plain)]
            return (sums, intos), tuple(d_chunks)

        with traced.stands_for(layers / traced.stood_for()):
            (sums, intos), d_xs = jax.lax.scan(
                step, ([jnp.zeros_like(w) for w in plain], intos),
                (starts, xs, d_out), reverse=True)
        return sums, None, intos, d_xs

    chunked.defvjp(fwd, bwd)
    return chunked(
        [w for w in ws if not _is_stack(w)],
        [(w.stack, w.layer) for w in ws if _is_stack(w)],
        [w.into for w in ws if _is_stack(w)], xs)


def rms_norm(x, g, eps: float = 1e-5):
    """x / rms(x) * g over the last dim, in float32, back in x's dtype."""
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (x32 * scale * g).astype(x.dtype)


def rms_norm0(x, w, eps: float = 1e-6):
    """The zero-centred RMSNorm: ``x / rms(x) * (1 + w)`` over the last dim,
    the leaf ``w`` starting at 0. The gain is made in float32 before the
    product (``1 + w`` in bf16 is 1 for ``|w| < 2^-8``); back in x's
    dtype."""
    return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def _hyper_lanes(maps) -> int:
    """``n`` of maps ``n + n + n^2`` wide."""
    n = math.isqrt(maps.shape[-1] + 1) - 1
    if n * n + 2 * n != maps.shape[-1]:
        raise ValueError(f"{maps.shape[-1]} is no n^2 + 2n of hyper maps")
    return n


def _total(terms):
    """The terms' sum, first to last (no zero to start from)."""
    return functools.reduce(lambda a, t: a + t, terms)


def _columns(M):
    """[n, n, ...] -> its columns' sums [n, ...], adds of whole slices."""
    return _total(M[i] for i in range(M.shape[0]))


def _rows(M):
    return _total(M[:, j] for j in range(M.shape[1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _sinkhorn(M, rounds: int, hc_eps: float):
    """``rounds`` times ``M / (colsum(M) + hc_eps)`` then ``M / (rowsum(M)
    + hc_eps)`` on ``M`` [n, n, ...] (entry ``[i, j]`` a slice): one
    reciprocal a column or row and a multiply, **a loop of ``rounds``
    trips** and not the rounds written out. Written out they are one chain
    of elementwise operations, and the compiler does not keep a round's
    matrix for the two things that want it: it makes it again from the
    first one inside each (20 rounds: 4,000 fused operations and 108,000
    instructions in the Xing cell's step, 3 minutes to compile, with
    autodiff's backward or with this one). The loop costs nothing to see
    (9 us of a 1,066 us read at the cell's shape; 4 or 20 rounds a trip
    time no better: ``tools/mhc_bench.py``, PR 61). The backward pass
    keeps the ``2 x rounds`` denominators [n, ...] and walks the rounds
    back from the result, each round's input from its output: ``M_in =
    M_out x denominator``."""
    return _sinkhorn_fwd(M, rounds, hc_eps)[0]


def _sinkhorn_fwd(M, rounds, hc_eps):
    def one_round(M, _):
        columns = _columns(M) + hc_eps
        M = M * (1.0 / columns)
        rows = _rows(M) + hc_eps
        return M * (1.0 / rows)[:, None], (columns, rows)

    M, kept = jax.lax.scan(one_round, M, None, length=rounds)
    return M, (M, kept)


def _sinkhorn_bwd(rounds, hc_eps, res, g):
    # out = M / den, den = sum(M) + hc_eps: d M = (g - sum(g out)) / den
    def back(carry, kept):
        g, M = carry
        columns, rows = kept
        g = (g - _rows(g * M)[:, None]) * (1.0 / rows)[:, None]
        M = M * rows[:, None]
        g = (g - _columns(g * M)) * (1.0 / columns)
        return (g, M * columns), None

    (g, _), _ = jax.lax.scan(back, (g, res[0]), res[1], reverse=True)
    return (g,)


_sinkhorn.defvjp(_sinkhorn_fwd, _sinkhorn_bwd)


def hyper_maps(x, phi, b, alpha, *, rounds: int, eps: float,
               hc_eps: float = 1e-6, clamp: Tuple[float, float] = (-30, 30)):
    """The three maps of one sub-layer under manifold-constrained
    hyper-connections (mHC, arXiv:2512.24880): ``x`` [B, T, n d], the
    residual stream's ``n`` lanes side by side, ``phi`` [n d, n^2 + 2n],
    ``b`` [n^2 + 2n], ``alpha`` [3] -> float32 [B, T, n^2 + 2n], a token's
    ``H_pre`` [n] | ``H_post`` [n] | ``H_res`` [n, n] row by row:

        m      = RMSNorm(x_t) phi       over the n d joined channels, no gain
        H_pre  = sigmoid(alpha_0 m_pre + b_pre)
        H_post = 2 sigmoid(alpha_1 m_post + b_post)
        M_0    = exp(clip(alpha_2 m_res + b_res, clamp))
        M     <- M / (colsum(M) + hc_eps), M <- M / (rowsum(M) + hc_eps)
                 ``rounds`` times; H_res = M: positive, rows and columns
                 summing to one (Sinkhorn-Knopp)

    The norm has no gain, so ``RMSNorm(x) phi`` is ``(x phi) / rms(x)``: the
    product takes the stream as it is stored (bf16 operands where it is
    bf16, accumulated in float32) and no normalised copy of it is made.
    Everything after the product is float32 with the tokens along the
    lanes and the sublanes, ``m`` as ``[n^2 + 2n, 8, B T / 8]`` and the
    mixing matrix ``[n, n, 8, B T / 8]``, however the caller divides its
    tokens into ``B`` and ``T`` (a plane ``[B, T]`` is tiled by its ``B``,
    two sublanes a tile at two sequences and one of eight at one, and the
    maps' seconds followed the chunk's shape: PERF.md, PR 65; ``[B, T]``
    only where the tokens do not divide by eight): a Sinkhorn round is adds
    of its rows or columns as whole slices, one reciprocal a column or row
    and a multiply, nothing is reduced across lanes and no ``reduce`` is
    asked for (:func:`_sinkhorn`)."""
    n = _hyper_lanes(phi)
    tokens = x.shape[:-1]
    count = math.prod(tokens)
    plane = tokens if count % 8 else (8, count // 8)
    with jax.named_scope("mhc_maps"):
        x32 = x.astype(jnp.float32)
        scale = jax.lax.rsqrt((x32 * x32).mean(-1) + eps).reshape(plane)
        m = jnp.moveaxis(jnp.dot(x, phi, preferred_element_type=jnp.float32),
                         -1, 0).reshape(-1, *plane) * scale
        alpha = alpha.astype(jnp.float32)
        b = b.astype(jnp.float32)[:, None, None]
        pre = jax.nn.sigmoid(alpha[0] * m[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + b[n:2 * n])
        M = jnp.exp(jnp.clip(alpha[2] * m[2 * n:] + b[2 * n:], *clamp)
                    ).reshape(n, n, *plane)
        M = _sinkhorn(M, rounds, hc_eps)
        return jnp.moveaxis(jnp.concatenate(
            [pre, post, M.reshape(n * n, *plane)]).reshape(-1, *tokens),
            0, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def lanes_of(x, n: int):
    """``x`` [..., n d] -> its ``n`` lanes [..., d], float32: slices of
    whole lane tiles of the last axis, never a reshape to ``[.., n, d]``
    (another tiling on the chip). Their cotangents go back joined, one
    array written once, where a slice's own transpose is a zero-padded
    array the stream's size a lane, and their sum."""
    d = x.shape[-1] // n
    return tuple(x[..., i * d:(i + 1) * d].astype(jnp.float32)
                 for i in range(n))


def _lanes_fwd(x, n):
    # The residual is there for its dtype alone.
    return lanes_of(x, n), jnp.zeros((), x.dtype)


def _lanes_bwd(n, like, cts):
    return (jnp.concatenate(cts, axis=-1).astype(like.dtype),)


lanes_of.defvjp(_lanes_fwd, _lanes_bwd)


def _entry(maps, c: int):
    """Entry ``c`` of every token's maps, [B, T, 1]: a slice."""
    return jax.lax.slice_in_dim(maps, c, c + 1, axis=-1)


def hyper_read(x, maps):
    """A sub-layer's input: ``y_t = sum_i H_pre[i] x_t[i]`` [B, T, d] from
    the stream ``x`` [B, T, n d] and :func:`hyper_maps`' ``maps``; the sum
    in float32, back in ``x``'s dtype. A lane is a slice of whole lane
    tiles of the last axis (``d`` a multiple of 128 at any published
    width): the stream is never reshaped to ``[.., n, d]``."""
    n = _hyper_lanes(maps)
    with jax.named_scope("mhc_read"):
        return _total(_entry(maps, i) * lane for i, lane in enumerate(
            lanes_of(x, n))).astype(x.dtype)


def hyper_write(x, maps, f):
    """The stream after a sub-layer: ``x'_t[i] = sum_j H_res[i, j] x_t[j] +
    H_post[i] f_t`` [B, T, n d] from the stream ``x``, the maps the
    sub-layer's input was read under and its output ``f`` [B, T, d]; float32
    sums, back in ``x``'s dtype. With one lane and maps of one this is ``x +
    f``."""
    n = _hyper_lanes(maps)
    with jax.named_scope("mhc_write"):
        lanes = lanes_of(x, n)
        f32 = f.astype(jnp.float32)
        out = [_total(_entry(maps, 2 * n + i * n + j) * lanes[j]
                      for j in range(n)) + _entry(maps, n + i) * f32
               for i in range(n)]
        return jnp.concatenate(out, axis=-1).astype(x.dtype)


def heads_matrix(width: int, heads: int):
    """float32 [width, heads], 1 where a channel is its head's: a head's
    sum as a matmul and a head's number spread over its channels as the
    transposed one, so that a ``[T, heads * D]`` array is never reshaped to
    ``[T, heads, D]`` (on the chip that is another tiling: a copy of the
    array each way, 6.5 ms at the Kimi Linear cell's ``[8192, 4096]`` in
    float32)."""
    head = jnp.arange(width, dtype=jnp.int32) // (width // heads)
    return (head[:, None] == jnp.arange(heads, dtype=jnp.int32)[None, :]
            ).astype(jnp.float32)


def scaled_by_head(x32, heads: int, eps: float, mean: bool):
    """x32 [B, T, heads * D] float32 times, a head, ``rsqrt`` of its
    channels' sum of squares (their mean: ``mean``) plus ``eps``."""
    ones = heads_matrix(x32.shape[-1], heads)
    highest = jax.lax.Precision.HIGHEST
    total = jnp.einsum("btc,ch->bth", x32 * x32, ones, precision=highest)
    if mean:
        total = total / (x32.shape[-1] // heads)
    return x32 * jnp.einsum("bth,ch->btc", jax.lax.rsqrt(total + eps), ones,
                            precision=highest)


def attn_gate(o, a, wa):
    """The heads' outputs ``o`` [B, T, heads * D] times ``sigmoid(a wa)``,
    element by element, float32 inside: an output gate from the layer's
    normed input ``a`` (Trinity's, Qwen3-Next's)."""
    with jax.named_scope("attn_gate"):
        gate = jax.nn.sigmoid((a @ wa).astype(jnp.float32))
        return (o.astype(jnp.float32) * gate).astype(o.dtype)


class RopeTable(NamedTuple):
    """A rotary table that is not the plain one: the angle a position
    advances in each of the ``head_dim / 2`` rotated pairs, and what cos and
    sin are multiplied by. Plain floats, so that a configuration that holds
    one stays hashable."""
    inv_freq: Tuple[float, ...]
    scale: float = 1.0
    name: str = "rope_table"


def yarn_table(head_dim: int, theta: float, factor: float,
               original_max_position: int, beta_fast: float = 32.0,
               beta_slow: float = 1.0,
               attention_factor: Optional[float] = None) -> RopeTable:
    """YaRN's table (Peng et al. 2023, arXiv:2309.00071, as
    ``transformers``' ``_compute_yarn_parameters`` with ``truncate`` at its
    default): pair ``i`` turns by ``theta ** (-i / half)`` a position where
    it completes more than ``beta_fast`` turns over the original context
    (``i <= low``), by ``1 / factor`` of that where it completes fewer than
    ``beta_slow`` (``i >= high``), and by the blend in between; cos and sin
    times ``attention_factor`` (``0.1 ln(factor) + 1`` where none is
    given)."""
    half = head_dim // 2

    def correction(turns):
        return head_dim * math.log(original_max_position
                                   / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    freqs = np.float32(theta) ** (np.arange(half, dtype=np.float32) / half)
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0, 1)
    inv_freq = (1 - ramp) / freqs + ramp / (factor * freqs)
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return RopeTable(tuple(float(f) for f in inv_freq.astype(np.float32)),
                     float(attention_factor), "rope_yarn")


traced.declare(
    "rope_calls", "differentiated rotary embeddings a micro batch, an array "
    "of heads each (a layer's q and its k: 2 a layer that rotates, 0 in a "
    "model without rotary)")


def _rotate_half(hd: int, rotary_dim: int, dtype):
    """``P`` [hd, hd] with ``x @ P`` the rotate-half of ``x``'s first
    ``rotary_dim`` channels, ``[-x2, x1]``, and 0 past them: -1 where
    channel ``i + half`` feeds channel ``i``, +1 where ``i`` feeds
    ``i + half``. ``P^T = -P``. Made of two iotas, which the compiler
    folds, and not a NumPy array: as an array constant it rode a walk's
    loop as one more operand, and in that program the compiler inlined a
    walk of one layer only after the passes that merge its recomputation
    with its forward pass (Trinity's dense layer ran twice: seen in the
    pass dumps and on the chip, gone with the iotas; which condition of
    the loop simplifier the operand trips was not found)."""
    half = rotary_dim // 2
    source = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
    to = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
    return (((to == source + half) & (to < rotary_dim)).astype(dtype)
            - ((source == to + half) & (source < rotary_dim)).astype(dtype))


def _turn(x, cos, sin, rotary_dim: int):
    """``x * cos + (x @ P) * sin`` in float32, cast to ``x.dtype`` once:
    the head's lanes are never split or joined. The product is exact (a
    signed permutation: one term a sum, entries 0 and +-1): for bfloat16 one
    pass of the matrix unit, its result asked for in bfloat16; for any
    wider dtype at the highest precision, where the chip's default would
    round ``x`` to bfloat16. A channel past ``rotary_dim`` is ``x * 1 + 0 *
    0``: ``x`` itself, but that a ``-0.0`` comes out ``+0.0``. A select
    that kept the sign was tried and taken out: with it the CPU's compiled
    float32 values differed by a unit in the last place between a scan and
    its unrolled twin, without it they do not (why was not found: pinning
    every rounding in here changed nothing)."""
    exact = None if x.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST
    half_turned = jax.lax.dot_general(
        x, _rotate_half(x.shape[-1], rotary_dim, x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())), precision=exact,
        preferred_element_type=x.dtype)
    return (x.astype(jnp.float32) * cos
            + half_turned.astype(jnp.float32) * sin).astype(x.dtype)


# ``layers``: the runs one trace of the call stands for, as ``_flash`` takes
# it, for the forward rule's count of ``rope_calls``.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rotated(x, cos, sin, rotary_dim, layers):
    return _turn(x, cos, sin, rotary_dim)


def _rotated_fwd(x, cos, sin, rotary_dim, layers):
    traced.count("rope_calls", layers=layers)
    return _turn(x, cos, sin, rotary_dim), (cos, sin)


def _rotated_bwd(rotary_dim, layers, tables, g):
    # The two channels of a pair share their angle, so ``(g * sin) @ P^T ==
    # -(g @ P) * sin``: the cotangent is the rotation by minus the angle, on
    # ``g`` in its own dtype (autodiff's transpose would hand the matrix
    # unit a float32 ``g * sin``, which the chip rounds to bfloat16).
    cos, sin = tables
    return _turn(g, cos, -sin, rotary_dim), None, None


_rotated.defvjp(_rotated_fwd, _rotated_bwd)


def rope(x, table: Union[float, RopeTable], start=0,
         rotary_dim: Optional[int] = None):
    """Rotary embedding over [B, H, T, hd] (rotate-half formulation) at
    positions ``start ..`` (a chunk of a sequence: its first position).
    ``table``: the plain table's ``theta`` (pair ``i`` turns by ``theta **
    (-i / half)`` a position), or a :class:`RopeTable`. ``rotary_dim``: the
    head's first channels that are rotated, ``half = rotary_dim / 2`` pairs
    (channel ``i`` with ``i + half``); the channels past them pass as they
    are (a partial rotary embedding). None: the whole head.

    cos and sin are made at the head's full width (channel ``i`` and
    ``i + half`` carry one angle; past ``rotary_dim`` cos is 1 and sin 0)
    and rotate-half is a product with a signed permutation (:func:`_turn`):
    a reshape, slice or concatenate that splits a head's lanes is a relayout
    on the chip. The values are those of ``x1 * cos - x2 * sin``, ``x1 * sin
    + x2 * cos`` bit for bit, and so is the gradient (but for the sign of a
    zero past ``rotary_dim``)."""
    B, H, T, hd = x.shape
    rotary_dim = hd if rotary_dim is None else rotary_dim
    half = rotary_dim // 2
    plain = not isinstance(table, RopeTable)
    with jax.named_scope("rope_plain" if plain else table.name):
        channel = jnp.arange(hd, dtype=jnp.int32)
        if plain:
            pair = jax.lax.rem(channel, half)
            freqs = 1.0 / (table ** (pair.astype(jnp.float32) / half))
        else:
            freqs = jnp.asarray(np.asarray(table.inv_freq, np.float32)[
                np.arange(hd) % half])
        scale = 1.0 if plain else table.scale
        if rotary_dim < hd:     # past the rotary width: angle 0, scale 1
            rotated = channel < rotary_dim
            freqs = jnp.where(rotated, freqs, 0.0)
            scale = jnp.where(rotated, jnp.float32(scale), 1.0)
        positions = jnp.arange(T, dtype=jnp.float32)
        if not (isinstance(start, int) and start == 0):
            positions = positions + jnp.asarray(start, jnp.float32)
        angles = positions[:, None] * freqs[None, :]
        cos = jnp.cos(angles)[None, None, :, :]
        sin = jnp.sin(angles)[None, None, :, :]
        if not plain and table.scale != 1.0:
            cos, sin = cos * scale, sin * scale
        return _rotated(x, cos, sin, rotary_dim, traced.stood_for())


def gqa_heads(blk, a, *, n_head: int, n_kv_head: int, head_dim: int,
              eps: float, window: int, windowed, rope_window=None,
              rope_global=None, block_q: int = 0, block_k: int = 0,
              rotary_dim: Optional[int] = None, norm=rms_norm,
              scale: Optional[float] = None):
    """a [B, T, d] (the normed input) -> the attention heads' outputs side
    by side [B, T, n_head * head_dim], before any gate and before ``wo``:
    ``n_head`` query heads over ``n_kv_head`` key/value heads, RMSNorm over
    each head of q and k where the block has the gains for it (``blk``'s
    ``q_norm``, ``k_norm`` [head_dim]; a block without those leaves has no
    QK-norm), then by the layer's kind the rotary embedding (``rope_window`` /
    ``rope_global``: a ``theta``, a :class:`RopeTable`, or None for no
    position encoding; ``rotary_dim``: the head's first channels that are
    rotated, None the whole head) and the flash kernels, with ``window`` on a
    window layer (key j visible to query i iff ``0 <= i - j < window``) and
    plain causal on a global one. ``windowed``: this layer's kind, a bool or
    a traced scalar (a stack of both kinds: the branch is a ``lax.cond``).
    ``norm``: the QK-norm, :func:`rms_norm` or :func:`rms_norm0`.
    ``scale``: what multiplies the scores, None for ``head_dim ** -0.5`` (a
    model whose multiplier is its own: ``models/granite_hybrid.py``).
    Inside a block that :func:`scan_blocks` walks the call hands its
    forward pass to the walk as ``flash_attention`` does."""
    B, T, _ = a.shape

    def heads(t, n):
        return t.reshape(B, T, n, head_dim).transpose(0, 2, 1, 3)

    def attend(forward, q, k, v, windowed: bool):
        table = rope_window if windowed else rope_global
        if table is not None:
            with jax.named_scope("attn_rope"):
                q = rope(q, table, rotary_dim=rotary_dim)
                k = rope(k, table, rotary_dim=rotary_dim)
        with jax.named_scope("attn_core"):
            return flash_attention_kept(
                q, k, v, forward, causal=True, scale=scale,
                window=window if windowed else None,
                block_q=block_q or None, block_k=block_k or None)

    with jax.named_scope("attn_qkv"):
        q, k = heads(a @ blk["wq"], n_head), heads(a @ blk["wk"], n_kv_head)
        if "q_norm" in blk:
            q = norm(q, blk["q_norm"], eps)
            k = norm(k, blk["k_norm"], eps)
        v = heads(a @ blk["wv"], n_kv_head)

    def either(forward):
        # What a walk keeps of this call goes in and comes out here, around
        # the ``cond``: both kinds' ``(o, lse)`` have one shape.
        if isinstance(windowed, (bool, np.bool_)):
            return attend(forward, q, k, v, bool(windowed))
        # Both branches are traced and one runs a layer: what each counts of
        # its calls (``flash_bwd_calls``) counts half, a layer's sum once.
        with traced.stands_for(1 / 2):
            return jax.lax.cond(windowed != 0,
                                functools.partial(attend, windowed=True),
                                functools.partial(attend, windowed=False),
                                forward, q, k, v)

    o = hand_over(either)
    return o.transpose(0, 2, 1, 3).reshape(B, T, n_head * head_dim)


def held_routing_stats(ids, num_experts: int, tile_m: int,
                       held: Tuple[int, int]) -> dict:
    """What a model's ``routing_stats`` reports of an expert layer that
    holds ``held = (first, count)`` of the router's ``num_experts``, from
    the expert ids its routers chose (``ids`` [layers, S, k]), outside any
    step: the rows each held expert got (``held_rows`` [layers, count]) and
    the telemetry counters ``moe_assignments_held`` /
    ``moe_assignments_elsewhere``, ``moe_tokens_dropped`` (assignments to a
    held expert that reached no row of the layout: 0 by construction,
    counted from the layout itself), ``moe_layout_worst_case`` (layers
    whose routing took the worst-case size) and gauges
    ``moe_held_rows_max``, ``moe_held_rows_mean`` (rows one held expert got
    in one layer), ``moe_layout_live_share`` (rows holding an assignment
    over the rows of the worst case), ``moe_layout_rows_share`` (rows of
    the size each layer takes over the worst case's, mean over layers) and
    ``moe_rows_fetched_share`` (rows the row-copy kernel out of the layout
    copies over the ``S * k`` the XLA gathers fetched: the held assignments
    over all). Each
    layer is laid out at the size ``routed_experts`` chooses for it on the
    device (``layout_rows``, ``layout_index``)."""
    S, k = ids.shape[1:]
    ladder = layout_rows(S, k, held[1], num_experts, tile_m)
    sizes, placed, rows = [], 0, []
    for experts in ids:
        r = route(experts, num_experts, tile_m, held)
        rows.append(ladder[int(layout_index(r.n_tiles, ladder, tile_m))])
        r = at_rows(r, rows[-1], tile_m)
        placed += int(jnp.sum(r.row_token < S))
        sizes.append(r.group_sizes)
    sizes = jnp.stack(sizes)
    n_held = int(sizes.sum())
    out = {"moe_assignments_held": n_held,
           "moe_assignments_elsewhere": int(ids.size) - n_held,
           "moe_tokens_dropped": n_held - placed,
           "moe_layout_worst_case": sum(
               m == ladder[-1] for m in rows) if len(ladder) > 1 else 0,
           "moe_held_rows_max": int(sizes.max()),
           "moe_held_rows_mean": float(sizes.mean()),
           "moe_layout_live_share": n_held / (len(rows) * ladder[-1]),
           "moe_layout_rows_share": sum(rows) / (len(rows) * ladder[-1]),
           "moe_rows_fetched_share": n_held / int(ids.size)}
    for name, value in out.items():
        if name.startswith("moe_assignments") or name in (
                "moe_tokens_dropped", "moe_layout_worst_case"):
            metrics().counter(name).inc(value)
        else:
            metrics().gauge(name).set(value)
    return {**out, "experts": ids, "held_rows": sizes}


def cross_entropy(x, head, targets, chunk: int = 0, weights=None):
    """Mean next-token cross entropy from final hidden states ``x``
    [B, T, D] through the output head ``head`` [V, D] (GPT-2 hands in its
    tied embedding, a model with an untied head that head), optionally
    chunked. ``weights`` [B, T]: a weight a position, the mean taken over
    the weights' sum (a loss that leaves positions out carries 0 there, and
    needs no slice of the sequence that no chunk size divides:
    ``models/xing.py``'s second loss); None is every position at 1, and
    the program it always was. A model with two losses calls this twice on
    one ``head``, whose gradient is then the sum of the two calls'.

    Dense path (``chunk <= 0``): logits = x @ head.T in one [B, T, V] fp32
    tensor, differentiated by autodiff.

    Chunked path (``chunk > 0``): ``lax.scan`` over token chunks, one
    [chunk, V] logits matmul a chunk. Under differentiation the same loop
    also makes the gradients (a ``jax.custom_vjp`` whose forward rule is
    the loop): the loss is a scalar and the last thing the forward does,
    so each chunk's ``softmax - onehot`` is known the moment its logits
    are, and ``dx_chunk = d @ head`` and ``dhead += d.T @ xc`` run right
    there. **Kept** for the backward: ``dx`` [n_chunks, chunk, D] and
    ``dhead`` [V, D], in their operands' dtypes, which the backward would
    hold anyway; it multiplies them by the upstream scalar. **Never
    built**: a [chunk, V] array outside its own chunk's iteration, a
    second logits matmul, any [tokens, V] array. A call that is not
    differentiated runs the plain loop. The value is the plain loop's bit
    for bit; against the dense path the summation order changes (per-chunk
    partial sums), so results match it to float tolerance.

    The gauge ``ce_fused_chunks`` is set while the call is traced: the
    chunks whose gradients the forward loop makes, 0 for a dense or an
    undifferentiated call; ``ce_weighted_positions`` counts the positions
    of the calls that carry ``weights``. All of it is the step's
    ``head_loss`` part."""
    with part("head_loss"):
        return _cross_entropy(x, head, targets, chunk, weights)


def _cross_entropy(x, head, targets, chunk, weights):
    B, T, D = x.shape
    n_tokens = B * T
    traced.note("ce_fused_chunks", 0)
    if weights is not None:
        traced.count("ce_weighted_positions", n_tokens)
        weights = weights.astype(jnp.float32)
    if chunk <= 0:
        logits = (x @ head.T).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        if weights is None:
            return jnp.mean(logz - gold)
        return jnp.sum((logz - gold) * weights) / jnp.sum(weights)

    # Non-dividing counts get a zero-padded, masked tail chunk — the LM
    # loss always shifts tokens (n_tokens = B*(T-1) at the call site), so
    # a divisibility fallback would silently disable chunking for every
    # power-of-two chunk size.
    n_chunks = -(-n_tokens // chunk)
    pad = n_chunks * chunk - n_tokens
    xf = x.reshape(n_tokens, D)
    tf = targets.reshape(n_tokens)
    valid = jnp.ones((n_tokens,), jnp.float32) if weights is None \
        else weights.reshape(n_tokens)
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad, D), x.dtype)])
        tf = jnp.concatenate([tf, jnp.zeros((pad,), targets.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.float32)])
    xf = xf.reshape(n_chunks, chunk, D)
    tf = tf.reshape(n_chunks, chunk)
    valid = valid.reshape(n_chunks, chunk)

    def weight_sum(valid):
        """What the sum is divided by: the positions, or their weights'."""
        return n_tokens if weights is None else jnp.sum(valid)

    def chunk_loss(xc, head, tc, mc):
        logits = (xc @ head.T).astype(jnp.float32)       # [chunk, V]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return logits, logz, jnp.sum((logz - gold) * mc)

    @jax.custom_vjp
    def mean_loss(xf, head, tf, valid):
        def body(acc, inp):
            xc, tc, mc = inp
            return acc + chunk_loss(xc, head, tc, mc)[2], None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                (xf, tf, valid))
        return total / weight_sum(valid)

    def fwd(xf, head, tf, valid):
        traced.note("ce_fused_chunks", n_chunks)
        # The dtype autodiff hands the logits' cotangent back in.
        d_dtype = jnp.result_type(xf.dtype, head.dtype)
        over = weight_sum(valid)

        def body(carry, inp):
            acc, dhead = carry
            xc, tc, mc = inp
            logits, logz, term = chunk_loss(xc, head, tc, mc)
            onehot = jax.nn.one_hot(tc, logits.shape[-1], dtype=logits.dtype)
            d = ((jnp.exp(logits - logz[:, None]) - onehot)
                 * (mc / over)[:, None]).astype(d_dtype)
            dxc = (d @ head).astype(xc.dtype)
            dhead = dhead + jax.lax.dot_general(
                d, xc, (((0,), (0,)), ((), ()))).astype(dhead.dtype)
            return (acc + term, dhead), dxc

        (total, dhead), dx = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros_like(head)),
            (xf, tf, valid))
        return total / over, (dx, dhead)

    def bwd(residuals, g):
        dx, dhead = residuals
        with part("head_loss"):     # traced by the backward pass, later
            return ((dx * g).astype(dx.dtype),
                    (dhead * g).astype(dhead.dtype), None, None)

    mean_loss.defvjp(fwd, bwd)
    return mean_loss(xf, head, tf, valid)
