"""The selective scan alone against the sequential float32 scan.

A step check cannot see the precision inside the scan: with a state carried
in bf16 every number the Jamba cell's check compares moves by less than one
seed moves it (PERF.md section 2 has the readings), because bf16's rounding
of every activation, which the configuration states, already stands 0.03 to
0.05 from the float32 reference in every gradient, and the state's rounding
adds 0.008 to the gradient it touches most. Alone, on operands as a Mamba
layer at its initialisation hands them over, the same rounding is four
thousand times the kernel's own distance. So the scan is held here, and the
builder calls this where the cell's check runs.

``against_sequential`` gives the relative L2 distance of the output and of
the seven gradients from ``reference/jamba.py``'s one-step-a-token scan on
the same operands; ``tools/ssm_bench.py`` prints the same numbers.
"""

import jax
import jax.numpy as jnp

from benchmark.reference import jamba as ref

NAMES = ("out", "dc", "ddelta", "dA", "dB", "dC", "dD", "dz")


def make_inputs(shape, states: int, dtype, seed: int):
    """Operands as a Mamba layer at its initialisation hands them over:
    ``delta`` the softplus of a bias drawn as Mamba-1 draws it, ``A`` the
    negative of 1..N, ``B`` and ``C`` unit-RMS rows; the last is the
    cotangent of the output."""
    Bn, T, Di = shape
    ks = jax.random.split(jax.random.PRNGKey(seed % 2 ** 31), 7)
    f32 = jnp.float32

    def unit(k):
        x = jax.random.normal(k, (Bn, T, states), f32)
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
                ).astype(dtype)

    step = jnp.exp(jax.random.uniform(
        ks[1], (Di,), f32, jnp.log(1e-3), jnp.log(1e-1)))
    delta = jax.nn.softplus(
        jnp.log(jnp.expm1(step)) + 0.3 * jax.random.normal(ks[2], shape, f32))
    return (jax.random.normal(ks[0], shape, f32).astype(dtype) * 0.5, delta,
            -jnp.broadcast_to(jnp.arange(1, states + 1, dtype=f32),
                              (Di, states)),
            unit(ks[3]), unit(ks[4]), jnp.ones((Di,), f32),
            jax.random.normal(ks[5], shape, f32).astype(dtype),
            jax.random.normal(ks[6], shape, f32).astype(dtype))


def sequential(c, delta, A, B, C, D, z):
    """``(h C + D c) silu(z)`` through the reference's scan, float32."""
    f32 = jnp.float32
    c, delta, B, C, z = (x.astype(f32) for x in (c, delta, B, C, z))
    y = jnp.stack([ref.recurrence(*one, A, *rows)
                   for one, rows in zip(zip(c, delta), zip(B, C))])
    return (y + D * c) * jax.nn.silu(z)


def rel_l2(got, want) -> float:
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def out_and_gradients(scan, inputs):
    """``scan``'s output and its seven gradients under the inputs' last."""
    @jax.jit
    def run(*operands, do):
        out, vjp = jax.vjp(scan, *operands)
        return (out,) + vjp(do.astype(out.dtype))
    return jax.block_until_ready(run(*inputs[:-1], do=inputs[-1]))


def against_sequential(scan, inputs, want=None) -> dict:
    """``{name: relative L2 distance}`` of ``scan`` from :func:`sequential`
    (``want``: the latter's ``out_and_gradients``, where one has them)."""
    if want is None:
        want = out_and_gradients(sequential, inputs)
    return {n: rel_l2(g, w) for n, g, w in zip(
        NAMES, out_and_gradients(scan, inputs), want)}
