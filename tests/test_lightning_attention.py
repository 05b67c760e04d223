"""The linear-attention kernels (``ops/pallas/lightning_attention.py``;
interpret mode: their own code) against the sequential recurrence of
``benchmark/kernels/lightning_check.py``: values and gradients, the custom
VJP, a padded last chunk, float32 state under bf16 operands, the check's
control and the shapes refused."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.kernels import lightning_check
from tepdist_tpu.models import minicpm_sala as sala
from tepdist_tpu.ops.pallas import lightning_attention as la

CFG = sala.CONFIGS["test"]


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def lightning_inputs(B, T, H, D, dtype=jnp.float32, seed=0):
    return lightning_check.make_inputs((B, T, H, D), dtype, seed)


def kernels(chunk, **how):
    def run(q, k, v, log_decay, do):
        return (la.forward(q, k, v, log_decay, chunk=chunk, **how),) \
            + la.backward(q, k, v, log_decay, do, chunk=chunk, **how)
    return run


LAM = jnp.exp(jnp.asarray(sala.log_decays(CFG, 1)))          # 0.44 to 0.92


# 40 positions in chunks of 16 (the last one padded), 64 in chunks of 16 and
# in one chunk, one position alone.
@pytest.mark.parametrize("T,chunk", [(40, 16), (64, 16), (64, 64), (1, 8)])
def test_kernels_match_the_sequential_recurrence(T, chunk):
    inputs = lightning_inputs(2, T, 4, 16)
    read = lightning_check.against_sequential(kernels(chunk), inputs, LAM)
    assert max(read.values()) < 2e-6, read


def test_the_custom_vjp_is_the_kernels_backward():
    q, k, v, do = (x.reshape(2, 40, 64)
                   for x in lightning_inputs(2, 40, 4, 16, seed=2))
    ld = jnp.log(LAM)
    out, vjp = jax.vjp(lambda *a: la.lightning_attention(*a, ld, chunk=16),
                       q, k, v)
    want = kernels(16)(q, k, v, ld, do)
    for got, w in zip((out,) + vjp(do), want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
    # The decay is data: it takes a gradient of zeros, not an error.
    grad = jax.grad(lambda ld: la.lightning_attention(q, k, v, ld,
                                                      chunk=16).sum())(ld)
    assert not np.asarray(grad).any()


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_padded():
    q, k, v, _ = (x.reshape(1, 37, 64)
                  for x in lightning_inputs(1, 37, 4, 16, seed=3))
    ld = jnp.log(LAM)
    got = la.lightning_attention(q, k, v, ld, chunk=16)
    more = la.lightning_attention(
        *(jnp.pad(x, ((0, 0), (0, 11), (0, 0))) for x in (q, k, v)), ld,
        chunk=16)
    assert got.shape == (1, 37, 64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(more[:, :37]))


def test_state_and_accumulation_are_float32_under_bf16_operands():
    """bf16 operands, results asked for in float32: the kernels stand 1e-5
    from the float32 recurrence on the same (bf16-valued) operands, where
    one rounding of a float32 factor to bf16 would read 2e-3."""
    inputs = lightning_inputs(1, 256, 4, 16, jnp.bfloat16, seed=5)
    read = lightning_check.against_sequential(
        kernels(64, out_dtype=jnp.float32), inputs, LAM)
    assert max(read.values()) < 2e-5, read


def test_a_bfloat16_state_would_fail_the_kernels_comparison():
    """The control of ``kernels/lightning_check.py``: the same kernels with
    the carried state through bf16 read a hundred times the sound ones."""
    inputs = lightning_inputs(1, 256, 4, 16, jnp.bfloat16, seed=5)
    read = lightning_check.against_sequential(
        kernels(64, out_dtype=jnp.float32, state_dtype=jnp.bfloat16),
        inputs, LAM)
    assert min(read.values()) > 5e-4, read


def test_the_kernels_refuse_shapes_they_cannot_tile():
    q = jnp.zeros((1, 16, 64))
    with pytest.raises(ValueError, match="lightning_attention"):
        la.lightning_attention(q, q, q, jnp.zeros((3,)))     # 64 % 3
    with pytest.raises(ValueError, match="lightning_attention"):
        la.lightning_attention(q, q[:, :8], q, jnp.zeros((4,)))
    with pytest.raises(ValueError, match="lightning_attention"):
        la.lightning_attention(q, q, q, jnp.zeros((4,)), chunk=12)
