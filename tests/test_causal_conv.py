"""The depth-wise causal conv kernels before Jamba's scan
(``ops/pallas/causal_conv.py``; interpret mode: their own code) against the
``jax.numpy`` form: values and gradients, the rows a halo fills, float32 sums
under bf16 operands, the shapes refused and the cost the planner is told."""

import jax
import jax.numpy as jnp
import pytest

from kernel_checks import rel_l2
from tepdist_tpu.ops.pallas import causal_conv as conv


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


CONV_NAMES = ("c", "du", "dw", "db")


def conv_inputs(batch, T, Di, dtype, seed=0, K=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (batch, T, Di)).astype(dtype),
            (0.5 * jax.random.normal(ks[1], (K, Di))).astype(dtype),
            (0.1 * jax.random.normal(ks[2], (Di,))).astype(dtype),
            jax.random.normal(ks[3], (batch, T, Di)).astype(dtype))


def out_and_gradients(fn, u, w, b, dc):
    out, pull = jax.vjp(fn, u, w, b)
    return (out,) + pull(dc)


def hold_conv(got, want, limit, block_t=conv.STRIP):
    """``got`` (c, du, dw, db) to ``want``, and the rows a halo fills (the
    first of the sequence and of every later time block) on their own."""
    for name, g, w_ in zip(CONV_NAMES, got, want):
        assert g.shape == w_.shape and g.dtype == w_.dtype, name
        assert rel_l2(g, w_) < limit, name
    for name, g, w_ in zip(CONV_NAMES[:2], got, want):
        for at in range(0, g.shape[1] - 4, block_t):
            edge = slice(max(at - 4, 0), at + 4)
            assert rel_l2(g[:, edge], w_[:, edge]) < limit, (name, at)


S = conv.STRIP      # the least time block


# Two time blocks with the sequence ending inside the second; four blocks
# and two channel blocks, two sequences; one block longer than the sequence;
# a block of several strips; three taps.
@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 2e-6),
                                         (jnp.bfloat16, 4e-3)])
@pytest.mark.parametrize("batch,T,Di,K,block_t,block_d", [
    (1, S + 8, 128, 4, S, 128), (2, 3 * S + 36, 256, 4, S, 128),
    (1, 20, 128, 4, 512, 512), (1, 3 * S, 256, 4, 3 * S, 256),
    (1, S + 18, 128, 3, S, 128)])
def test_conv_kernels_match_the_jax_numpy_form(dtype, limit, batch, T, Di, K,
                                               block_t, block_d):
    """Values and the gradients of ``u``, ``w``, ``b``: zeros before the
    sequence, the rows before a block carried into it (forward) and the rows
    after it (backward), the padded rows past the end adding nothing to the
    sums."""
    args = conv_inputs(batch, T, Di, dtype, K=K)
    got = out_and_gradients(lambda *a: conv.causal_conv(
        *a, block_t=block_t, block_d=block_d), *args)
    hold_conv(got, out_and_gradients(conv.reference, *args), limit, block_t)


def test_conv_sums_are_float32_under_bf16_operands():
    """bf16 operands over 4096 rows: the taps' and the bias's gradients are
    sums of 4096 products each, within bf16's rounding of the float32 form's
    results (a bf16 accumulator would stand 1e-2 off)."""
    args = conv_inputs(1, 4096, 128, jnp.bfloat16, seed=4)
    got = out_and_gradients(conv.causal_conv, *args)
    want = out_and_gradients(
        conv.reference, *(a.astype(jnp.float32) for a in args))
    for name, g, w_ in zip(CONV_NAMES, got, want):
        assert g.dtype == jnp.bfloat16, name
        assert rel_l2(g, w_) < 4e-3, name


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_a_dropped_halo_fails_the_conv_comparison(which, monkeypatch):
    """The control of the comparison above: the same kernels with what one
    time block hands the next zeroed (the last rows of ``u`` going forward,
    the first rows of ``g`` going backward)."""
    name, carried = {"forward": ("_fwd_kernel", 4),     # the scratch's place
                     "backward": ("_bwd_kernel", 7)}[which]
    real = getattr(conv, name)

    def dropped(*refs, **how):
        refs[carried][...] = jnp.zeros(refs[carried].shape, jnp.float32)
        real(*refs, **how)

    monkeypatch.setattr(conv, name, dropped)
    u, w, b, dc = conv_inputs(1, 2 * S, 128, jnp.float32, seed=6)
    how = dict(block_t=S, block_d=128, interpret=True)
    # Not through the jitted entry points: their traces are cached.
    c = conv._fwd_call.__wrapped__(u, w, b, **how)
    du, dw, db = conv._bwd_call.__wrapped__(u, w, b, dc, **how)
    want = out_and_gradients(conv.reference, u, w, b, dc)
    with pytest.raises(AssertionError):
        hold_conv((c, du, dw, db), want, 2e-6)
    # What the fault does not touch is where it was.
    sound = (du, dw, db) if which == "forward" else (c,)
    for g, w_ in zip(sound, want[1:] if which == "forward" else want[:1]):
        assert rel_l2(g, w_) < 2e-6


def test_the_conv_refuses_shapes_it_cannot_tile():
    u, w, b, _ = conv_inputs(1, 16, 128, jnp.float32)
    with pytest.raises(ValueError):
        conv.causal_conv(u[..., :64], w[:, :64], b[:64])
    with pytest.raises(ValueError):
        conv.causal_conv(u, jnp.zeros((9, 128)), b)
    with pytest.raises(ValueError):
        conv.causal_conv(u, w, b[:64])


def test_the_conv_kernels_state_their_cost_to_the_planner():
    from tepdist_tpu.graph.cost import jaxpr_flops
    u, w, b, dc = conv_inputs(1, 32, 128, jnp.float32)
    fwd = jax.make_jaxpr(conv.causal_conv)(u, w, b)
    both = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(conv.causal_conv(*a) * dc), argnums=(0, 1, 2)))(
        u, w, b)
    assert jaxpr_flops(fwd.jaxpr) >= conv.FWD_FLOPS * u.size
    assert jaxpr_flops(both.jaxpr) >= (conv.FWD_FLOPS + conv.BWD_FLOPS) \
        * u.size
