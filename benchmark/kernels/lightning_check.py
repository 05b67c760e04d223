"""The decayed linear-attention kernels alone against the sequential float32
recurrence.

A step check cannot see the precision inside the kernels: bf16's rounding of
every activation, which the configuration states, already stands some
hundredths from the float32 reference in every gradient (PERF.md section 2
has the Jamba scan's case), and a state carried in bf16 adds less than seeds
move. Alone, with results asked for in float32, the kernels stand about 1e-5
from the recurrence and a bf16 carry a hundred times that. So the kernels
are held here, and the builder calls this where the cell's check runs.

``against_sequential`` gives the relative L2 distance of the output and of
the three gradients from ``reference/minicpm_sala.py``'s one-step-a-token
recurrence on the same operands; ``tools/sala_bench.py`` prints the same
numbers.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.kernels.ssm_check import rel_l2  # noqa: F401 — also the tool's
from benchmark.reference import minicpm_sala as ref

NAMES = ("out", "dq", "dk", "dv")


def make_inputs(shape, dtype, seed: int):
    """Operands as a lightning layer hands them over, ``[B, T, H, D]``:
    ``q`` and ``k`` unit-RMS rows a head (after QK-norm), ``q`` over
    ``sqrt(D)``, ``v`` a projection's output; the last is the cotangent of
    the output."""
    ks = jax.random.split(jax.random.PRNGKey(seed % 2 ** 31), 4)
    f32 = jnp.float32

    def unit(k):
        x = jax.random.normal(k, shape, f32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))

    return ((unit(ks[0]) / shape[-1] ** 0.5).astype(dtype),
            unit(ks[1]).astype(dtype),
            jax.random.normal(ks[2], shape, f32).astype(dtype),
            jax.random.normal(ks[3], shape, f32).astype(dtype))


def sequential(q, k, v, lam):
    """The reference's recurrence over a batch, float32."""
    return jnp.stack([ref.recurrence(*(x.astype(jnp.float32) for x in one),
                                     lam) for one in zip(q, k, v)])


def sequential_out_and_gradients(inputs, lam):
    q, k, v, do = inputs

    @jax.jit
    def run(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: sequential(*a, lam), q, k, v)
        return (out,) + vjp(do)
    return jax.block_until_ready(run(*(x.astype(jnp.float32)
                                       for x in inputs)))


def against_sequential(kernels, inputs, lam, want=None) -> dict:
    """``{name: relative L2 distance}`` of ``kernels(q, k, v, log lam, do)
    -> (out, dq, dk, dv)`` (operands ``[B, T, H * D]``, results asked for in
    float32) from the sequential recurrence (``want``: its
    ``sequential_out_and_gradients``, where one has them)."""
    if want is None:
        want = sequential_out_and_gradients(inputs, lam)
    shape = inputs[0].shape
    flat = [x.reshape(*shape[:2], -1) for x in inputs]
    # The logarithm of the float32 decay itself, on the host: near 1 the
    # device's own would be 1e-5 of the slope off.
    log_lam = jnp.asarray(np.log(np.asarray(lam, np.float64)), jnp.float32)
    got = jax.block_until_ready(jax.jit(kernels)(
        *flat[:3], log_lam, flat[3]))
    return {n: rel_l2(g.reshape(shape), w)
            for n, g, w in zip(NAMES, got, want)}
