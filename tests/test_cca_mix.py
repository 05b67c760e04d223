"""The compressed attention's mixing kernels (``ops/pallas/cca_mix.py``) in
interpret mode at the published ``[10, 128]`` heads against their
``jax.numpy`` form: forward, the latents' gradients and all four weight
gradients over several time blocks and inside one, the zeros before the
sequence, the kernels' names and the shapes they refuse."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from kernel_checks import kernel_counts, rel_l2

from tepdist_tpu.ops.pallas import cca_mix as cm


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def mix_operands(B, T, dtype, seed=0, H=8, Hkv=2, D=128):
    N = H + Hkv
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    f32 = jnp.float32
    return (jax.random.normal(ks[0], (B, T, H * D), f32).astype(dtype),
            jax.random.normal(ks[1], (B, T, Hkv * D), f32).astype(dtype),
            0.5 * jax.random.normal(ks[2], (2, N * D), f32),
            0.1 * jax.random.normal(ks[3], (N * D,), f32),
            (0.05 * jax.random.normal(ks[4], (2, N, D, D), f32)).astype(
                dtype),
            0.1 * jax.random.normal(ks[5], (N * D,), f32)), (
        jax.random.normal(ks[6], (B, H, T, D), f32).astype(dtype),
        jax.random.normal(ks[7], (B, Hkv, T, D), f32).astype(dtype))


def mix_grads(fn, operands, cts):
    def loss(*ops):
        q, k = fn(*ops)
        return jnp.sum(q.astype(jnp.float32) * cts[0]) \
            + jnp.sum(k.astype(jnp.float32) * cts[1])
    return jax.jit(jax.grad(loss, argnums=range(6)))(*operands)


@pytest.mark.parametrize("T,dtype,limit", [
    (300, jnp.float32, 2e-6),        # five time blocks of 64, the last padded
    (40, jnp.float32, 2e-6),         # shorter than a block
    (256, jnp.bfloat16, 6e-3)], ids=["blocks", "short", "bf16"])
def test_the_mixing_kernels_are_the_jax_numpy_form(T, dtype, limit):
    """At the published ``[10, 128]`` heads, in interpret mode: the forward,
    the latents' gradients and all four weight gradients."""
    operands, cts = mix_operands(2, T, dtype, seed=T)
    kernel = lambda *ops: cm.cca_mix(*ops, block_t=64)        # noqa: E731
    got, want = kernel(*operands), jax.jit(cm.reference)(*operands)
    for g, w, shape in zip(got, want, ((2, 8, T, 128), (2, 2, T, 128))):
        assert g.shape == w.shape == shape and g.dtype == dtype
        assert rel_l2(g, w) < limit
    names = ("q0", "k0", "w1", "b1", "w2", "b2")
    for name, g, w, op in zip(names, mix_grads(kernel, operands, cts),
                              mix_grads(cm.reference, operands, cts),
                              operands):
        assert g.shape == op.shape and g.dtype == op.dtype, name
        assert rel_l2(g, w) < limit, name


def test_the_first_two_positions_see_zeros_before_the_sequence():
    """``u_{-1} = 0`` and ``c1_{-1} = 0`` (not ``b1``): positions 0 and 1 of
    one head written out."""
    (q0, k0, w1, b1, w2, b2), _ = mix_operands(1, 16, jnp.float32, seed=3)
    D = 128
    for fn in (cm.cca_mix, cm.reference):
        q, k = fn(q0, k0, w1, b1, w2, b2)
        for h, (out, u, mean) in {
                5: (q[0, 5], q0[0, :, 5 * D:6 * D],
                    (q0[0, :, 5 * D:6 * D] + k0[0, :, D:]) / 2),
                9: (k[0, 1], k0[0, :, D:],
                    (q0[0, :, 4 * D:].reshape(16, 4, D).mean(1)
                     + k0[0, :, D:]) / 2)}.items():
            lanes = slice(h * D, (h + 1) * D)
            tap, bias = w1[:, lanes], b1[lanes]
            c1_0 = bias + tap[1] * u[0]
            c1_1 = bias + tap[0] * u[0] + tap[1] * u[1]
            want0 = b2[lanes] + c1_0 @ w2[1, h] + mean[0]
            want1 = b2[lanes] + c1_0 @ w2[0, h] + c1_1 @ w2[1, h] + mean[1]
            np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want0),
                                       rtol=0, atol=2e-6)
            np.testing.assert_allclose(np.asarray(out[1]), np.asarray(want1),
                                       rtol=0, atol=2e-6)


def test_kernel_names_and_refused_shapes():
    operands, cts = mix_operands(1, 64, jnp.float32)
    found = kernel_counts(lambda *ops: mix_grads(
        cm.cca_mix, ops, cts), *operands)
    assert found == {"tepdist_cca_mix_fwd": 1, "tepdist_cca_mix_bwd": 1}
    assert kernel_counts(cm.cca_mix, *operands) == {"tepdist_cca_mix_fwd": 1}
    with pytest.raises(ValueError, match="cca_mix"):
        cm.cca_mix(operands[0], operands[1][:, :, :128], *operands[2:])
    narrow, _ = mix_operands(1, 8, jnp.float32, D=8)
    with pytest.raises(ValueError, match="multiples of 128"):
        cm.cca_mix(*narrow)
    assert cm.reference(*narrow)[0].shape == (1, 8, 8, 8)
