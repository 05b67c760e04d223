"""What the kernels of a linear-attention state share
(``lightning_attention.py``, ``kda_attention.py``, ``gdn_attention.py``): a
float32 matmul on a matrix unit that takes bf16, the state as it goes from
chunk to chunk, and the sequence padded to whole chunks.

Precision: the state, the decay factors and every accumulation are float32.
The matrix unit takes bf16, so a float32 operand of a matmul goes in as two
bf16 parts, ``x = hi + lo``, each product accumulated in float32: 16 bits of
mantissa where one rounding to bf16 keeps 8. bf16 operands are read as they
come. With float32 operands (the CPU tests) every matmul is float32."""

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_BF16 = jnp.bfloat16
_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def _parts(x, narrow: bool):
    """What the matrix unit is handed for ``x``: itself where it is narrow
    already, else its bf16 rounding and the rounding of what that left (a
    third part moved nothing on the chip: 3.8e-5 from the recurrence either
    way, all of it the device's logarithm of a decay near 1)."""
    if not narrow or x.dtype != _F32:
        return (x,)
    hi = x.astype(_BF16)
    return hi, (x - hi.astype(_F32)).astype(_BF16)


def _dot(a, b, dims, narrow: bool):
    """float32 ``dot_general``. ``narrow``: the call's operands are bf16,
    so float32 factors go in as two bf16 parts; else one float32 matmul."""
    if not narrow:
        return jax.lax.dot_general(a.astype(_F32), b.astype(_F32), dims,
                                   precision=_HIGHEST,
                                   preferred_element_type=_F32)
    return sum(jax.lax.dot_general(x, y, dims, preferred_element_type=_F32)
               for x in _parts(a, narrow) for y in _parts(b, narrow))


def _carried(state, state_dtype):
    """The state as it goes to the next chunk: float32, or through
    ``state_dtype`` first (a check's control: what a narrower carry costs)."""
    return state if state_dtype is None else \
        state.astype(state_dtype).astype(_F32)


def _padded(x, chunk: int):
    """Zero rows after the sequence, to whole chunks."""
    T = x.shape[1]
    Tp = -(-T // chunk) * chunk
    return x if Tp == T else jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))
