"""Pallas TPU selective scan (Mamba-1's recurrence), forward and backward.

For one sequence ``c_1..c_T`` of ``Di`` channels with ``N`` states a channel:

    h_t = exp(delta_t A) * h_{t-1} + (delta_t * c_t) B_t^T       h_0 = 0
    y_t = h_t C_t + D * c_t                  out_t = y_t * silu(z_t)

``h`` [Di, N]; ``delta_t``, ``c_t``, ``z_t`` [Di] broadcast over the states,
``B_t``, ``C_t`` [N] over the channels, ``A`` [Di, N], ``D`` [Di]. The decay
differs per (channel, state) pair, so the recurrence has no matmul form: it
is ``N * Di`` state elements a token on the vector unit.

**Memory in the sequence is O(chunk).** The grid is ``(batch, time chunks,
channel blocks)``, every axis sequential; the state of all channel blocks
lives in a VMEM scratch ``[Di / bd, N, bd]`` float32 from one chunk to the
next. Inside a grid step a loop walks the chunk's time steps with the block's
state in registers: states on the sublanes, channels on the lanes, one
``[N, 128]`` tile a lane tile. The forward writes ``out`` and, when it will
be differentiated, the pre-gate ``y`` and the state at each chunk's start
(``[T / chunk, N, Di]`` float32: 42e6 bytes at T = 8192, Di = 5120 and a
chunk of 64, where all states would be 2.7e9). The backward visits the chunks
last to first: it recomputes the chunk's states from its saved start into a
VMEM scratch, walks the chunk in reverse with the gradient of the state
carried as the state was, and gives the gradients of ``c``, ``delta``, ``z``
(as wide as their operands), ``B``, ``C`` and, summed over batch and sequence
in float32, ``A`` and ``D``.

What crosses lanes is kept off the time loop: ``B`` and ``C`` arrive with
each ``[N]`` row laid on the sublanes and repeated over 128 lanes (``[T, N,
128]``, made by XLA: the channel blocks of a chunk share the block, so it
crosses HBM once a chunk), and the gradients of ``B`` and ``C`` leave as
``[T, N, 128]`` partial sums over the lane tiles, summed over the lanes
outside. The sums over states (``y``, and two in the backward) are sublane
reductions.

Precision: state, ``delta``, ``exp`` and every accumulation float32 whatever
the operands; ``c``, ``z``, ``B``, ``C`` are widened as they are read.

Kernel names ``tepdist_ssm_fwd`` / ``tepdist_ssm_bwd`` show in a device
trace and in the compiled HLO. Runs in interpret mode off the TPU (tests),
compiled on it. ``tools/ssm_bench.py`` times both alone over channel block
and chunk.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tepdist_tpu.ops.pallas import _interpret
from tepdist_tpu.telemetry import traced

LANES = 128
CHUNK = 64                  # time steps a grid step; a state is saved a chunk
BLOCK_D = 1024              # channels a grid step (tools/ssm_bench.py)
_VMEM_LIMIT = 64 * 1024 * 1024      # of 128 MiB; the default scope is 16 MiB
_F32 = jnp.float32

# Operations a state element (one channel's one state at one time step), as
# ``cost_estimate`` tells the planner: forward delta*A, the decay times the
# state, the input times B, their sum, the state times C and its sum into y
# (the exp is a transcendental, counted apart); the backward runs the forward
# recurrence again and about 16 more.
FWD_FLOPS, BWD_FLOPS = 6, 22


traced.declare(
    "ssm_scan_calls", "forward selective-scan kernel calls a micro batch (a "
    "rematerialised layer's second run counted)")
traced.declare(
    "ssm_boundary_bytes", "bytes of chunk-boundary states one differentiated "
    "selective-scan call holds from its forward to its backward")


def _block_d(Di: int, want: int) -> int:
    """Largest multiple of 128 that divides ``Di`` and is at most ``want``
    (one lane tile where ``want`` is less)."""
    return next(b for b in range(max(min(want, Di), LANES) // LANES * LANES,
                                 0, -LANES) if Di % b == 0)


def _lane_tiles(bd: int):
    """The lane tiles of a channel block, as static slices."""
    return [slice(i, i + LANES) for i in range(0, bd, LANES)]


# The time loop takes ``ROWS`` steps a trip: a trip reads an aligned
# ``[ROWS, 128]`` tile of each per-token operand and takes its rows by static
# index (Mosaic loads no single row at a dynamic sublane), and gathers the
# rows it makes into such a tile by select before it stores them.
ROWS = 8


def _tile(ref, g, lanes):
    return ref[pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS), lanes]


def _put_tile(ref, g, lanes, tile):
    ref[pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS), lanes] = tile


def _with_row(tile, i, row):
    """``tile`` [ROWS, w] with row ``i`` replaced by ``row`` [1, w]."""
    at = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.where(at == i, row, tile)


def _scan_chunk(h, dl_ref, du_ref, bb_ref, a, tiles, chunk, each,
                flush=None):
    """The recurrence over one chunk from state ``h`` (a tuple of ``[N, 128]``
    tiles, one a lane tile); ``each(g, i, h, seen)`` sees the state after
    step ``g * ROWS + i`` and threads ``seen`` through a trip (``None`` at
    its start); ``flush(g, seen)`` gets the trip's last. Returns the last
    state."""
    def trip(g, h):
        dl = [_tile(dl_ref, g, lanes) for lanes in tiles]
        du = [_tile(du_ref, g, lanes) for lanes in tiles]
        seen = None
        for i in range(ROWS):
            b_t = bb_ref[0, g * ROWS + i].astype(_F32)          # [N, 128]
            h = tuple(jnp.exp(dl_l[i:i + 1] * a_l) * h_l + du_l[i:i + 1] * b_t
                      for dl_l, du_l, a_l, h_l in zip(dl, du, a, h))
            seen = each(g, i, h, seen)
        if flush is not None:
            flush(g, seen)
        return h
    return jax.lax.fori_loop(0, chunk // ROWS, trip, h)


def _silu_parts(z):
    sig = jax.nn.sigmoid(z)
    return sig, z * sig


def _fwd_kernel(c_ref, dl_ref, z_ref, bb_ref, cb_ref, a_ref, d_ref, *refs,
                chunk: int, bd: int, save: bool):
    if save:
        out_ref, y_ref, hb_ref, h_scr, dl_scr, du_scr, y_scr = refs
    else:
        out_ref, h_scr, dl_scr, du_scr, y_scr = refs
    k, j = pl.program_id(1), pl.program_id(2)
    tiles = _lane_tiles(bd)

    @pl.when(k == 0)
    def _():
        h_scr[j] = jnp.zeros(h_scr.shape[1:], _F32)

    if save:
        hb_ref[0, 0, 0] = h_scr[j]
    c = c_ref[0].astype(_F32)
    dl_scr[...] = dl_ref[0].astype(_F32)
    du_scr[...] = dl_scr[...] * c
    a = tuple(a_ref[:, lanes] for lanes in tiles)

    def store(g, y):
        for lanes, y_l in zip(tiles, y):
            _put_tile(y_scr, g, lanes, y_l)

    def each(g, i, h, y):
        if y is None:
            y = [jnp.zeros((ROWS, LANES), _F32)] * len(tiles)
        c_t = cb_ref[0, g * ROWS + i].astype(_F32)
        return [_with_row(y_l, i, jnp.sum(h_l * c_t, axis=0, keepdims=True))
                for y_l, h_l in zip(y, h)]

    h = _scan_chunk(tuple(h_scr[j, :, lanes] for lanes in tiles),
                    dl_scr, du_scr, bb_ref, a, tiles, chunk, each, store)
    for lanes, h_l in zip(tiles, h):
        h_scr[j, :, lanes] = h_l
    y = y_scr[...] + d_ref[...] * c
    if save:
        y_ref[0] = y.astype(y_ref.dtype)
    out_ref[0] = (y * _silu_parts(z_ref[0].astype(_F32))[1]).astype(
        out_ref.dtype)


def _bwd_kernel(c_ref, dl_ref, z_ref, bb_ref, cb_ref, a_ref, d_ref, y_ref,
                hb_ref, do_ref, dc_ref, ddl_ref, dz_ref, dbb_ref, dcb_ref,
                da_ref, dd_ref, dh_scr, hs_scr, dl_scr, du_scr, dy_scr,
                g_scr, e_scr, *, chunk: int, bd: int):
    k, j = pl.program_id(1), pl.program_id(2)
    tiles = _lane_tiles(bd)

    @pl.when(k == 0)                     # the sequence's last chunk
    def _():
        dh_scr[j] = jnp.zeros(dh_scr.shape[1:], _F32)
        da_ref[0, j] = jnp.zeros(da_ref.shape[2:], _F32)
        dd_ref[0, j] = jnp.zeros(dd_ref.shape[2:], _F32)

    @pl.when(j == 0)                     # the chunk's first channel block
    def _():
        dbb_ref[...] = jnp.zeros(dbb_ref.shape, _F32)
        dcb_ref[...] = jnp.zeros(dcb_ref.shape, _F32)

    c = c_ref[0].astype(_F32)
    z = z_ref[0].astype(_F32)
    do = do_ref[0].astype(_F32)
    sig, gate = _silu_parts(z)
    dy = do * gate
    dz_ref[0] = (do * y_ref[0].astype(_F32)
                 * (sig * (1.0 + z * (1.0 - sig)))).astype(dz_ref.dtype)
    dl_scr[...] = dl_ref[0].astype(_F32)
    du_scr[...] = dl_scr[...] * c
    dy_scr[...] = dy
    dd_ref[0, j] += jnp.sum(dy * c, axis=0, keepdims=True)
    a = tuple(a_ref[:, lanes] for lanes in tiles)

    # The chunk's states again, from its saved start: slot t + 1 holds h_t.
    hs_scr[0] = hb_ref[0, 0, 0]

    def keep(g, i, h, seen):
        for lanes, h_l in zip(tiles, h):
            hs_scr[g * ROWS + i + 1, :, lanes] = h_l

    _scan_chunk(tuple(hs_scr[0, :, lanes] for lanes in tiles),
                dl_scr, du_scr, bb_ref, a, tiles, chunk, keep)

    def back(trip, carry):
        g = chunk // ROWS - 1 - trip
        dh, da = carry
        dl = [_tile(dl_scr, g, lanes) for lanes in tiles]
        du = [_tile(du_scr, g, lanes) for lanes in tiles]
        dy = [_tile(dy_scr, g, lanes) for lanes in tiles]
        # Of d loss / d (delta_t * c_t) and of d loss / d (delta_t * A),
        # the latter times A: their sums over the states, a row a step.
        gs = [jnp.zeros((ROWS, LANES), _F32)] * len(tiles)
        es = list(gs)
        for i in reversed(range(ROWS)):
            t = g * ROWS + i
            b_t = bb_ref[0, t].astype(_F32)
            c_t = cb_ref[0, t].astype(_F32)
            db = jnp.zeros(b_t.shape, _F32)
            dc = jnp.zeros(b_t.shape, _F32)
            new_dh, new_da = [], []
            for n, (lanes, a_l, dh_l, da_l) in enumerate(
                    zip(tiles, a, dh, da)):
                dy_t, dl_t = dy[n][i:i + 1], dl[n][i:i + 1]
                h_t, h_prev = hs_scr[t + 1, :, lanes], hs_scr[t, :, lanes]
                dh_l = dh_l + dy_t * c_t                   # d loss / d h_t
                dc = dc + h_t * dy_t
                db = db + dh_l * du[n][i:i + 1]
                gs[n] = _with_row(gs[n], i, jnp.sum(dh_l * b_t, axis=0,
                                                    keepdims=True))
                decay = jnp.exp(dl_t * a_l)
                d_exponent = dh_l * h_prev * decay
                es[n] = _with_row(es[n], i, jnp.sum(
                    d_exponent * a_l, axis=0, keepdims=True))
                new_da.append(da_l + d_exponent * dl_t)
                new_dh.append(dh_l * decay)
            dbb_ref[0, t] += db
            dcb_ref[0, t] += dc
            dh, da = tuple(new_dh), tuple(new_da)
        for lanes, g_l, e_l in zip(tiles, gs, es):
            _put_tile(g_scr, g, lanes, g_l)
            _put_tile(e_scr, g, lanes, e_l)
        return dh, da

    dh, da = jax.lax.fori_loop(
        0, chunk // ROWS, back,
        (tuple(dh_scr[j, :, lanes] for lanes in tiles),
         tuple(da_ref[0, j, :, lanes] for lanes in tiles)))
    for lanes, dh_l, da_l in zip(tiles, dh, da):
        dh_scr[j, :, lanes] = dh_l
        da_ref[0, j, :, lanes] = da_l
    g = g_scr[...]
    ddl_ref[0] = (e_scr[...] + g * c).astype(ddl_ref.dtype)
    dc_ref[0] = (g * dl_scr[...] + d_ref[...] * dy_scr[...]).astype(
        dc_ref.dtype)


def _wide(x):
    """``[B, T, N]`` -> ``[B, T, N, 128]``: each row on the sublanes, the
    same in every lane."""
    return jnp.broadcast_to(x[..., None], x.shape + (LANES,))


def _specs(chunk, bd, N, at):
    """Block specs shared by both kernels; ``at(k)`` is the chunk a grid
    step's second index stands for."""
    wide = pl.BlockSpec((1, chunk, bd), lambda b, k, j: (b, at(k), j))
    rows = pl.BlockSpec((1, chunk, N, LANES),
                        lambda b, k, j: (b, at(k), 0, 0))
    a = pl.BlockSpec((N, bd), lambda b, k, j: (0, j))
    d = pl.BlockSpec((1, bd), lambda b, k, j: (0, j))
    start = pl.BlockSpec((1, 1, 1, N, bd),
                         lambda b, k, j: (b, at(k), j, 0, 0))
    return wide, rows, a, d, start


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _cost(B, T, Di, N, flops, wide_bytes):
    """What the planner (graph/cost.py) and XLA's scheduler are told."""
    return pl.CostEstimate(
        flops=flops * B * T * Di * N, transcendentals=B * T * Di * N,
        bytes_accessed=B * T * Di * wide_bytes + 2 * B * T * N * LANES * 4)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "chunk", "block_d", "save", "interpret"))
def _fwd_call(c, delta, at, bb, cb, d, z, *, chunk, block_d, save,
              interpret):
    """Operands padded to whole chunks; ``at`` [N, Di], ``bb``/``cb``
    [B, T, N, 128], ``d`` [1, Di]. Returns ``out`` and, with ``save``, the
    pre-gate ``y`` and the chunks' starting states."""
    B, T, Di = c.shape
    N = at.shape[0]
    bd = _block_d(Di, block_d)
    nd, nk = Di // bd, T // chunk
    wide, rows, a_spec, d_spec, start = _specs(chunk, bd, N, lambda k: k)
    out_shape = [jax.ShapeDtypeStruct((B, T, Di), c.dtype)]
    out_specs = [wide]
    if save:
        out_shape += [jax.ShapeDtypeStruct((B, T, Di), c.dtype),
                      jax.ShapeDtypeStruct((B, nk, nd, N, bd), _F32)]
        out_specs += [wide, start]
    size = c.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, bd=bd, save=save),
        name="tepdist_ssm_fwd",
        grid=(B, nk, nd),
        in_specs=[wide, wide, wide, rows, rows, a_spec, d_spec],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((nd, N, bd), _F32)]
        + [pltpu.VMEM((chunk, bd), _F32)] * 3,
        cost_estimate=_cost(B, T, Di, N, FWD_FLOPS,
                            (3 + save) * size + delta.dtype.itemsize),
        compiler_params=_params(), interpret=interpret,
    )(c, delta, z, bb, cb, at, d)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "chunk", "block_d", "interpret"))
def _bwd_call(c, delta, at, bb, cb, d, z, y, starts, do, *, chunk, block_d,
              interpret):
    B, T, Di = c.shape
    N = at.shape[0]
    bd = _block_d(Di, block_d)
    nd, nk = Di // bd, T // chunk
    wide, rows, a_spec, d_spec, start = _specs(
        chunk, bd, N, lambda k: nk - 1 - k)
    whole = lambda *shape: pl.BlockSpec(                     # noqa: E731
        (1, nd) + shape, lambda b, k, j: (b, 0, 0, 0))
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, _F32)   # noqa: E731
    size = c.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, bd=bd),
        name="tepdist_ssm_bwd",
        grid=(B, nk, nd),
        in_specs=[wide, wide, wide, rows, rows, a_spec, d_spec, wide, start,
                  wide],
        out_specs=[wide, wide, wide, rows, rows, whole(N, bd), whole(1, bd)],
        out_shape=[jax.ShapeDtypeStruct((B, T, Di), c.dtype),
                   jax.ShapeDtypeStruct((B, T, Di), delta.dtype),
                   jax.ShapeDtypeStruct((B, T, Di), z.dtype),
                   f32(B, T, N, LANES), f32(B, T, N, LANES),
                   f32(B, nd, N, bd), f32(B, nd, 1, bd)],
        scratch_shapes=[pltpu.VMEM((nd, N, bd), _F32),
                        pltpu.VMEM((chunk + 1, N, bd), _F32)]
        + [pltpu.VMEM((chunk, bd), _F32)] * 5,
        cost_estimate=_cost(B, T, Di, N, BWD_FLOPS,
                            6 * size + 2 * delta.dtype.itemsize),
        compiler_params=_params(), interpret=interpret,
    )(c, delta, z, bb, cb, at, d, y, starts, do)


def _padded(T: int, chunk: int) -> int:
    return -(-T // chunk) * chunk


def _pad(x, T: int):
    """Zero rows after the sequence: ``delta = 0`` leaves the state as it is
    and ``c = 0`` adds nothing to it."""
    return x if x.shape[1] == T else jnp.pad(
        x, ((0, 0), (0, T - x.shape[1])) + ((0, 0),) * (x.ndim - 2))


def _operands(c, delta, A, B, C, D, z, chunk):
    Tp = _padded(c.shape[1], chunk)
    return (_pad(c, Tp), _pad(delta, Tp), A.astype(_F32).T,
            _wide(_pad(B, Tp)), _wide(_pad(C, Tp)),
            D.astype(_F32)[None, :], _pad(z, Tp))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _scan(c, delta, A, B, C, D, z, chunk, block_d, interpret, layers):
    traced.count("ssm_scan_calls", layers=layers)
    out, = _fwd_call(*_operands(c, delta, A, B, C, D, z, chunk), chunk=chunk,
                     block_d=block_d, save=False, interpret=interpret)
    return out[:, :c.shape[1]]


def _scan_fwd(c, delta, A, B, C, D, z, chunk, block_d, interpret, layers):
    traced.count("ssm_scan_calls", layers=layers)
    traced.note("ssm_boundary_bytes", max(
        traced.values()["ssm_boundary_bytes"], boundary_bytes(
            c.shape[0], c.shape[1], c.shape[2], A.shape[1], chunk)))
    ops = _operands(c, delta, A, B, C, D, z, chunk)
    out, y, starts = _fwd_call(*ops, chunk=chunk, block_d=block_d, save=True,
                               interpret=interpret)
    return out[:, :c.shape[1]], (ops, y, starts, A, B, C, D)


def _scan_bwd(chunk, block_d, interpret, layers, res, do):
    ops, y, starts, A, B, C, D = res
    T, Tp = do.shape[1], y.shape[1]
    dc, ddl, dz, dbb, dcb, da, dd = _bwd_call(
        *ops, y, starts, _pad(do, Tp), chunk=chunk, block_d=block_d,
        interpret=interpret)
    # [B, nd, N, bd] -> [N, Di] -> A's [Di, N]; the lanes of a B or C
    # gradient hold the lane tiles' partial sums.
    da = da.sum(0).transpose(1, 0, 2).reshape(A.shape[1], A.shape[0]).T
    return (dc[:, :T], ddl[:, :T], da.astype(A.dtype),
            dbb.sum(-1)[:, :T].astype(B.dtype),
            dcb.sum(-1)[:, :T].astype(C.dtype),
            dd.sum(0).reshape(D.shape).astype(D.dtype), dz[:, :T])


_scan.defvjp(_scan_fwd, _scan_bwd)


def boundary_bytes(B: int, T: int, Di: int, N: int, chunk: int = CHUNK):
    """Bytes of the chunk-boundary states one differentiated call holds
    from its forward to its backward."""
    return B * (_padded(T, chunk) // chunk) * N * Di * 4


def selective_scan(c, delta, A, B, C, D, z, *, chunk: int = CHUNK,
                   block_d: int = BLOCK_D,
                   interpret: Optional[bool] = None):
    """``(h C + D c) * silu(z)`` of the recurrence above: ``c``, ``delta``,
    ``z`` [batch, T, Di], ``A`` [Di, N], ``B``, ``C`` [batch, T, N], ``D``
    [Di] -> [batch, T, Di] in ``c``'s dtype, ``Di`` a multiple of 128 (and ``chunk`` of 8).
    Differentiable in all seven.
    Any ``T``: the last chunk is padded with steps that leave the state as
    it is. ``chunk`` time steps and ``block_d`` channels a grid step.

    Counts, while it is traced, each forward kernel call in
    ``ssm_scan_calls`` and raises ``ssm_boundary_bytes`` to what a
    differentiated call holds (``telemetry/traced.py``)."""
    if c.shape != delta.shape or c.shape != z.shape \
            or A.shape != (c.shape[2], B.shape[2]) or B.shape != C.shape \
            or B.shape[:2] != c.shape[:2] or D.shape != c.shape[2:] \
            or c.shape[2] % LANES or chunk % ROWS:
        raise ValueError(
            f"selective_scan: c {c.shape}, delta {delta.shape}, z {z.shape},"
            f" A {A.shape}, B {B.shape}, C {C.shape}, D {D.shape}")
    return _scan(c, delta, A, B, C, D, z, chunk, block_d,
                 _interpret(interpret), traced.stood_for())
