"""The selective-scan kernels (``ops/pallas/selective_scan.py``; interpret
mode: their own code) against the sequential float32 scan of
``benchmark/reference/jamba.py``: values and all seven gradients, the state
across chunks, float32 inside under bf16 operands, the shapes refused and the
cost the planner is told."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import jamba as ref
from kernel_checks import rel_l2
from tepdist_tpu.ops.pallas import selective_scan as ssm

# Level 1: at LLVM level 0 the carried state's two programs round apart
# (``test_state_is_carried_across_chunks_bit_for_bit``).
pytestmark = pytest.mark.usefixtures("optimized_programs")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def scan_inputs(batch, T, Di, N, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    shape = (batch, T, Di)
    delta = jax.nn.softplus(jax.random.normal(ks[1], shape) - 2.0)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (Di, N)))
    return (jax.random.normal(ks[0], shape).astype(dtype), delta, A,
            jax.random.normal(ks[3], (batch, T, N)).astype(dtype),
            jax.random.normal(ks[4], (batch, T, N)).astype(dtype),
            jax.random.normal(ks[5], (Di,)),
            jax.random.normal(ks[6], shape).astype(dtype)), \
        jax.random.normal(ks[7], shape)


def sequential(c, delta, A, B, C, D, z):
    f32 = jnp.float32
    c, delta, B, C, z = (x.astype(f32) for x in (c, delta, B, C, z))
    y = jnp.stack([ref.recurrence(c[i], delta[i], A, B[i], C[i])
                   for i in range(c.shape[0])])
    return (y + D * c) * jax.nn.silu(z)


NAMES = ("c", "delta", "A", "B", "C", "D", "z")


# Several whole chunks; a length the chunk does not divide (the last chunk
# is padded with steps that leave the state alone); two channel blocks of
# two lane tiles each; one chunk longer than the sequence.
@pytest.mark.parametrize("T,Di,N,chunk,block_d", [
    (48, 256, 16, 16, 128), (37, 128, 16, 16, 128), (40, 512, 8, 8, 256),
    (12, 128, 8, 16, 128)])
def test_kernel_matches_the_sequential_scan(T, Di, N, chunk, block_d):
    args, w = scan_inputs(2, T, Di, N)

    def through(scan):
        return jax.value_and_grad(
            lambda *a: jnp.sum(scan(*a) * w), argnums=tuple(range(7)))(*args)

    got_out = ssm.selective_scan(*args, chunk=chunk, block_d=block_d)
    want_out = sequential(*args)
    assert rel_l2(got_out, want_out) < 1e-6
    (_, got), (_, want) = through(lambda *a: ssm.selective_scan(
        *a, chunk=chunk, block_d=block_d)), through(sequential)
    for name, g, w_ in zip(NAMES, got, want):
        assert g.shape == w_.shape and g.dtype == w_.dtype, name
        assert rel_l2(g, w_) < 2e-6, name


def test_state_is_carried_across_chunks_bit_for_bit():
    """The same float32 sequence in chunks of 8, 16 and 64 steps (3, 2 and
    1 chunks with padding): the steps are the same steps in the same order
    whatever the chunk, so the output and every gradient but ``A``'s and
    ``D``'s agree bit for bit; those two are sums over the sequence taken a
    chunk at a time, and regroup."""
    args, w = scan_inputs(1, 24, 128, 8, seed=2)

    def run(chunk):
        out, pull = jax.vjp(lambda *a: ssm.selective_scan(
            *a, chunk=chunk, block_d=128), *args)
        return (out,) + pull(w)

    first = run(8)
    assert float(jnp.abs(first[0]).max()) > 0.1
    for chunk in (16, 64):
        for name, a, b in zip(("out",) + NAMES, first, run(chunk)):
            if name in ("A", "D"):
                assert rel_l2(b, a) < 1e-6, name
            else:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=name)


def test_state_and_accumulation_are_float32_under_bf16_operands():
    """bf16 ``c``, ``z``, ``B``, ``C``: against the sequential float32 scan
    of the same (rounded) operands the kernel differs by the rounding of its
    bf16 results alone, a sequence of 96 steps long."""
    args, w = scan_inputs(1, 96, 128, 16, seed=3, dtype=jnp.bfloat16)
    out, pull = jax.vjp(lambda *a: ssm.selective_scan(*a, chunk=16), *args)
    want, want_pull = jax.vjp(sequential, *args)
    assert out.dtype == jnp.bfloat16
    assert rel_l2(out, want) < 4e-3
    for name, g, w_ in zip(NAMES, pull(w.astype(jnp.bfloat16)),
                           want_pull(w.astype(jnp.bfloat16)
                                     .astype(jnp.float32))):
        assert g.dtype == w_.dtype, name
        assert rel_l2(g, w_) < (4e-3 if g.dtype == jnp.bfloat16 else 1e-5), \
            name


def test_a_bfloat16_state_would_fail_the_kernels_comparison():
    """The control of the tests above: the sequential scan with its state
    rounded to bfloat16 after every step, one precision below the float32
    the configuration states, in the kernel's place. At Mamba-1's step sizes
    (``delta`` from 1e-3 to 1e-1 against ``A`` = -1..-16, so a state sums a
    thousand steps) it stands thousands of times further from the float32
    scan than the 2e-6 the kernel is held to, in the output and in every
    gradient the state reaches."""
    T, Di, N = 256, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    c, z = (jax.random.normal(k, (T, Di)) for k in ks[:2])
    B, C = (jax.random.normal(k, (T, N)) for k in ks[2:4])
    delta = jnp.exp(jax.random.uniform(
        ks[4], (T, Di), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    A = -jnp.broadcast_to(jnp.arange(1.0, N + 1), (Di, N))
    w = jax.random.normal(ks[5], (T, Di))

    def rounded(c, delta, A, B, C):
        def step(h, x):
            c_t, d_t, B_t, C_t = x
            h = jnp.exp(d_t[None] * A.T) * h + B_t[:, None] * (d_t * c_t)[None]
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
            return h, jnp.sum(h * C_t[:, None], axis=0)
        return jax.lax.scan(step, jnp.zeros((N, Di)), (c, delta, B, C))[1]

    def through(scan):
        return jax.value_and_grad(lambda *a: jnp.sum(scan(*a) * w),
                                  argnums=(0, 1, 2, 3, 4))(c, delta, A, B, C)

    (_, want), (_, low) = through(ref.recurrence), through(rounded)
    assert rel_l2(rounded(c, delta, A, B, C),
                  ref.recurrence(c, delta, A, B, C)) > 1e-3
    for name, g, w_ in zip(NAMES, low, want):
        assert rel_l2(g, w_) > 1e-3, name
    # And the kernel, on the same inputs, is where the tests above hold it.
    args = (c[None], delta[None], A, B[None], C[None], jnp.ones((Di,)),
            z[None])
    assert rel_l2(ssm.selective_scan(*args, chunk=64),
                  sequential(*args)) < 2e-6


def test_the_kernel_refuses_shapes_it_cannot_tile():
    args, _ = scan_inputs(1, 16, 128, 8)
    with pytest.raises(ValueError):
        ssm.selective_scan(*args, chunk=12)
    with pytest.raises(ValueError):
        ssm.selective_scan(args[0][..., :64], args[1][..., :64],
                           args[2][:64], *args[3:5], args[5][:64],
                           args[6][..., :64])


def test_the_kernels_state_their_cost_to_the_planner():
    """``graph/cost.py`` prices a ``pallas_call`` by its ``cost_estimate``:
    the scan is not read as free."""
    from tepdist_tpu.graph.cost import jaxpr_flops
    args, w = scan_inputs(1, 32, 128, 8)
    fwd = jax.make_jaxpr(lambda *a: ssm.selective_scan(*a, chunk=16))(*args)
    both = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssm.selective_scan(*a, chunk=16) * w)))(*args)
    elements = 32 * 128 * 8
    assert jaxpr_flops(fwd.jaxpr) >= ssm.FWD_FLOPS * elements
    assert jaxpr_flops(both.jaxpr) >= (ssm.FWD_FLOPS + ssm.BWD_FLOPS) \
        * elements
