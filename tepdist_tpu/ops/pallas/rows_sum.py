"""Pallas TPU kernels for the rows out of an expert layer's layout: each
token's ``k`` rows fetched by row copies and summed in fast memory.

``ops/grouped_matmul.py:combine`` and ``dispatch``'s backward sum, for each
of ``S`` tokens, the ``k`` rows of ``y`` [M, d] that ``dest`` [S, k] names.
As XLA gathers that is ``k`` gathers of ``S`` rows each from a source that
stays in HBM, 39-55 ns a row on a v5e where HBM delivers a row in 5, and a
gather has a static row count: under a share of the experts three choices
in four are held elsewhere, name the one zero row of the spare tile, and
are fetched all the same (PERF.md section 5).

``tepdist_rows_sum`` takes a block of tokens a grid step. It reads their
``dest`` from SMEM and compacts, without a branch, the choices **under the
live bound** (``n_tiles * tile_m``) into a list; starts one asynchronous
copy for each of those and no other, all of a block's copies in flight
together on one semaphore; zeroes the slots of the choices it skipped (the
rows past the bound hold zeros by the grouped-matmul kernels' contract, so
a skipped slot and a fetched one add the same thing); and sums the ``k``
slots in float32 in slot order, writing one ``[block, d]`` block:
``_sum_of_rows``' value bit for bit.

**The source's form is the compiler's.** A row of a ``[M, d]`` array is no
whole tile of its own in HBM (bf16 is tiled ``(8,128)(2,1)``: a row shares
its 32-bit words with its neighbour), and Mosaic refuses a one-row slice of
it. A row of ``[M, d / 128, 128]`` copied by its leading index is whole
tiles where ``d / 128`` is a multiple of 8 (Mellum2's 2304 = 18 x 128 goes
to 24 x 128). ``tepdist_rows_tiled`` writes that form: a block of rows in,
reshaped in registers, the same block out, and **only the blocks under the
live bound**, so it moves the live rows' bytes where an XLA copy of ``y``
moves all of them twice (the pad, then the tiles' transposition). The sum
writes ``[S, d]`` as the layer reads it: the way back is a reshape of eight
tokens' sums in registers.

What a copy costs is its start, not its bytes: 17 ns a live row of 4 or
6 KiB (HBM delivers one in 5), beside 7 ns a choice of scalar and vector
work whatever the choice (``tools/rows_sum_bench.py``; PERF.md section 6,
PR 39). So the kernel wins by what it skips, 3 times at a live share of a
quarter. Where every choice is live the two kernels take 30-36 ns a row
and the gathers 34-39: nothing to win, and a whole layer keeps the gathers
(``ops/grouped_matmul.py:_rows_out``).

Whole-number division in the kernels and their index maps is ``lax.div``
and ``lax.rem`` (nothing here is negative): ``//`` and ``%`` each lower
through a sign helper that Pallas traces anew every time, which made every
lowering of a cell's step 1.7 s longer on the chip's host, twice a set-up,
and the scalar core no faster.

The kernels' names show in a device trace and in the compiled HLO. They run
in interpret mode off-TPU (tests), compiled on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tepdist_tpu.ops.pallas import _interpret

LANES = 128
BLOCK = 128                        # tokens a grid step of the sum
TILE_ROWS = 256                    # rows a grid step of the relayout
_CHUNK = 8                         # tokens summed at a time (in registers)
_UNROLL = 8                        # copies started, or awaited, a loop trip
_VMEM_LIMIT = 64 * 1024 * 1024     # of 128 MiB; the default scope is 16 MiB


def _traced_once(fn):
    """``fn(*arrays, **static)`` as a jaxpr made once for each signature and
    evaluated wherever it is called. An inlined ``jit`` traces again for
    each trace context, and a layer's forward and backward walks differ in
    theirs; two traces are two ``pallas_call``s to the lowering, which
    shares one kernel's MLIR among the calls whose parameters are the same
    objects. So a layer's ``combine``, its recomputation and ``dispatch``'s
    backward lower one kernel a layout size between them: 0.3-0.5 s each on
    the chip's host, twice a set-up (PERF.md section 6, PR 39)."""
    @functools.lru_cache(maxsize=None)
    def traced(avals, static):
        return jax.make_jaxpr(functools.partial(fn, **dict(static)))(*avals)

    @functools.wraps(fn)
    def call(*args, **static):
        closed = traced(
            tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args),
            tuple(sorted(static.items())))
        out, = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *args)
        return out
    return call


def _sublanes(d: int) -> int:
    """``d / 128`` rounded up to whole tiles of a row's own (8 sublanes)."""
    return -(-d // (LANES * 8)) * 8


def _tile_kernel(bound, y_ref, o_ref, *, rows: int):
    @pl.when(pl.program_id(0) * rows < bound[0])
    def _():
        c = y_ref.shape[1] // LANES
        o_ref[:, :c, :] = y_ref[...].reshape(rows, c, LANES)


def _row_block(M: int, want: int) -> int:
    """Largest multiple of 8 that divides ``M`` and is at most ``want``
    (``M`` itself where there is none)."""
    for b in range(min(want, M) // 8 * 8, 0, -8):
        if M % b == 0:
            return b
    return M


@_traced_once
def rows_tiled(y, bound, *, interpret=None):
    """y [M, d] -> [M, r, 128], ``r = d / 128`` rounded up to a multiple of
    8: each row under ``bound`` [1] laid out as whole tiles of its own, which
    is how ``rows_sum_tiled`` copies one. Blocks of rows wholly at or past
    ``bound`` are neither read nor written, and the lanes past ``d`` never:
    nothing reads either."""
    M, d = y.shape
    if d % LANES:
        raise ValueError(f"rows_tiled: y {y.shape}")
    r, rows = _sublanes(d), _row_block(M, TILE_ROWS)

    def live(i, bound):
        return jax.lax.min(i, jax.lax.div(jax.lax.max(bound[0] - 1, 0), rows))

    return pl.pallas_call(
        functools.partial(_tile_kernel, rows=rows),
        name="tepdist_rows_tiled",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(M // rows,),
            in_specs=[pl.BlockSpec((rows, d), lambda i, b: (live(i, b), 0))],
            out_specs=pl.BlockSpec((rows, r, LANES),
                                   lambda i, b: (live(i, b), 0, 0))),
        out_shape=jax.ShapeDtypeStruct((M, r, LANES), y.dtype),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=y.dtype.itemsize * M * (d + r * LANES)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(interpret),
    )(bound, y)


def _kernel(bound, dest_ref, y_ref, o_ref, buf, slots, rows, sem, *, k: int,
            block: int):
    live_rows = bound[0]
    choices = block * k

    # The block's live choices, compacted without a branch: every choice is
    # written where the next live one belongs and only a live one moves
    # that place on.
    def note(t, n):
        for j in range(k):
            i = t * k + j
            row = dest_ref[i]
            slots[n] = i
            rows[n] = row
            n = n + (row < live_rows).astype(jnp.int32)
        return n

    n_live = jax.lax.fori_loop(0, block, note, jnp.int32(0))
    # The list is walked ``_UNROLL`` entries a trip: what fills the last trip
    # copies row 0 into a slot past the block's own, which nothing reads.
    for u in range(_UNROLL - 1):
        slots[n_live + u] = choices
        rows[n_live + u] = 0
    trips = jax.lax.div(n_live + (_UNROLL - 1), _UNROLL)

    # A choice held elsewhere gets no copy: its slot is zero. (The stores
    # are issued before any copy starts; a whole layer skips them.)
    @pl.when(n_live < choices)
    def _():
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

    def row_copy(row, i):
        return pltpu.make_async_copy(
            y_ref.at[row], buf.at[jax.lax.div(i, k), jax.lax.rem(i, k)], sem)

    def start(trip, carry):
        for u in range(_UNROLL):
            n = trip * _UNROLL + u
            row_copy(rows[n], slots[n]).start()
        return carry

    def wait(trip, carry):
        for u in range(_UNROLL):
            row_copy(0, 0).wait()
        return carry

    jax.lax.fori_loop(0, trips, start, None)
    jax.lax.fori_loop(0, trips, wait, None)

    d = o_ref.shape[1]

    def add(c, carry):
        tokens = pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK)
        total = buf[tokens, 0].astype(jnp.float32)
        for j in range(1, k):
            total = total + buf[tokens, j].astype(jnp.float32)
        o_ref[tokens] = total.reshape(_CHUNK, -1)[:, :d].astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block // _CHUNK, add, None)


@_traced_once
def rows_sum_tiled(y, dest, bound, *, d: int, interpret=None):
    """The sum on the source as the kernel copies it: ``y`` [M, r, 128]
    (``rows_tiled``), ``dest`` [S, k] int32, ``bound`` [1] int32 -> [S, d],
    the float32 sum in slot order of each token's rows under ``bound`` (a
    ``dest`` at or past it adds nothing), in ``y``'s dtype."""
    M, r, lanes = y.shape
    S, k = dest.shape
    # ``dest`` reaches SMEM flat, in blocks of whole 1024-word tiles.
    block = BLOCK
    while block * k % 1024 and block < S:
        block *= 2
    block = min(block, S)
    if lanes != LANES or r != _sublanes(d) or S % block or block % _CHUNK:
        raise ValueError(f"rows_sum: y {y.shape}, dest {dest.shape}, d={d}, "
                         f"block={block}")
    return pl.pallas_call(
        functools.partial(_kernel, k=k, block=block),
        name="tepdist_rows_sum",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S // block,),
            in_specs=[pl.BlockSpec((block * k,), lambda i, b: (i,),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, d), lambda i, b: (i, 0)),
            scratch_shapes=[pltpu.VMEM((block + 1, k, r, LANES), y.dtype),
                            pltpu.SMEM((block * k + _UNROLL,), jnp.int32),
                            pltpu.SMEM((block * k + _UNROLL,), jnp.int32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((S, d), y.dtype),
        cost_estimate=pl.CostEstimate(
            flops=S * k * d, transcendentals=0,
            bytes_accessed=y.dtype.itemsize * (k + 1) * S * d),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(interpret),
    )(bound, dest.reshape(S * k), y)


def rows_sum(y, dest, bound, *, interpret=None):
    """y [M, d] -> [S, d]: for each token the float32 sum, slot 0 first, of
    the rows ``dest`` [S, k] names, back in ``y``'s dtype; a ``dest`` at or
    past ``bound`` [1] (the layout's live rows; the rows from there on hold
    zeros) is not fetched."""
    d = y.shape[1]
    if d % LANES:       # no model's width; the relayout wants whole lanes
        y = jnp.pad(y, ((0, 0), (0, -d % LANES)))
    return rows_sum_tiled(rows_tiled(y, bound, interpret=interpret), dest,
                          bound, d=d, interpret=interpret)
