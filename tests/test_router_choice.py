"""The expert layers' shared choice (``ops/pallas/router_choice.py:choose``)
held to ``jax.lax.top_k`` and ``take_along_axis``: the set and its order,
ties included, the chosen scores, their gradient, and what the traced
function is made of. Off a TPU the rounds run as XLA operations, which is
what the models' tests run; one case holds the interpreted kernel to them,
and its compile for a described v5e is
``tests/test_tpu_compile_qwen3_next.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tepdist_tpu.ops.pallas.router_choice import choose
from tepdist_tpu.telemetry import traced

CASES = [(64, 8), (128, 6), (128, 8), (256, 8), (512, 10)]
S = 40                      # short, and no whole number of lane blocks


def _scores(kind, E, k, seed=0):
    """[S, E] float32: ``random``; ``ties``: a few distinct values, so equal
    scores lie across every token's k-th place; ``equal``: rows of one
    value; ``minus_inf``: rows with fewer than k finite scores, one with
    none."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((S, E)).astype(np.float32)
    if kind == "ties":
        x = np.round(x * 1.5) / 2
        x[0, :k - 1], x[0, k - 1:] = 3.0, 1.0       # the k-th among equals
    elif kind == "equal":
        x[:] = rng.standard_normal((S, 1)).astype(np.float32)
        x[1] = 0.0
    elif kind == "minus_inf":
        x[::2, k // 2:] = -np.inf
        x[1, 3::2] = -np.inf
        x[2] = -np.inf
    return jnp.asarray(x)


@pytest.mark.parametrize("kind", ["random", "ties", "equal", "minus_inf"])
@pytest.mark.parametrize("E,k", CASES)
def test_the_set_and_its_order_are_top_ks(E, k, kind):
    x = _scores(kind, E, k)
    chosen, ids = jax.jit(lambda x: choose(x, k))(x)
    want, want_ids = jax.lax.top_k(x, k)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(chosen, want)
    assert ids.dtype == jnp.int32 and chosen.dtype == jnp.float32


@pytest.mark.parametrize("biased", [False, True])
@pytest.mark.parametrize("E,k", CASES)
def test_the_chosen_scores_are_take_along_axis(E, k, biased):
    """With ``select`` the choice is the biased scores' and what is read
    the unbiased ones', bit for bit."""
    x = _scores("ties", E, k, seed=1)
    select = x + jnp.asarray(np.random.default_rng(2).standard_normal(
        E).astype(np.float32)) if biased else None
    chosen, ids = jax.jit(lambda x, s: choose(x, k, select=s))(x, select)
    want_ids = jax.lax.top_k(x if select is None else select, k)[1]
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(
        chosen, jnp.take_along_axis(x, want_ids, axis=-1))


@pytest.mark.parametrize("biased", [False, True])
@pytest.mark.parametrize("E,k", CASES)
def test_the_gradient_is_autodiffs_through_take_along_axis(E, k, biased):
    """Of a router's use of the choice: the k scores normalised over their
    sum. No gradient reaches ``select``."""
    x = jax.nn.softmax(_scores("random", E, k, seed=3), axis=-1)
    bias = jnp.asarray(np.random.default_rng(4).standard_normal(
        E).astype(np.float32)) * 0.01
    g = jnp.asarray(np.random.default_rng(5).standard_normal(
        (S, k)).astype(np.float32))

    def weighed(chosen):
        return jnp.sum(chosen / chosen.sum(-1, keepdims=True) * g)

    def ours(x, bias):
        return weighed(choose(x, k, select=x + bias if biased else None)[0])

    def plain(x, bias):
        ids = jax.lax.top_k(
            jax.lax.stop_gradient(x + bias) if biased else x, k)[1]
        return weighed(jnp.take_along_axis(x, ids, axis=-1))

    got = jax.jit(jax.grad(ours, argnums=(0, 1)))(x, bias)
    want = jax.jit(jax.grad(plain, argnums=(0, 1)))(x, bias)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-7)
    assert not np.asarray(got[1]).any() and not np.asarray(want[1]).any()


def _primitives(jaxpr):
    """Every primitive's name in a jaxpr and in the jaxprs its equations
    hold (a ``pallas_call``'s kernel, a loop's body)."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("kernel", [None, True], ids=["rounds", "kernel"])
@pytest.mark.parametrize("E,k", CASES)
def test_no_sort_no_gather_no_scatter_is_traced(E, k, kernel):
    """Neither the function nor its gradient holds one, as XLA operations
    or as the kernel, its body included."""
    x = _scores("random", E, k)
    value = jax.make_jaxpr(lambda x: choose(x, k, interpret=kernel))(x)
    grad = jax.make_jaxpr(jax.grad(
        lambda x: choose(x, k, interpret=kernel)[0].sum()))(x)
    for closed in (value, grad):
        names = set(_primitives(closed.jaxpr))
        assert ("pallas_call" in names) == bool(kernel)
        assert not names & {"sort", "gather", "scatter", "scatter-add",
                            "scatter_add", "top_k"}, names


@pytest.mark.parametrize("kind", ["ties", "minus_inf"])
@pytest.mark.parametrize("E,k", CASES)
def test_the_interpreted_kernel_is_the_rounds(E, k, kind):
    """Blocks, padding and chunks of lanes round the same rounds: ids and
    scores bit for bit, with and without ``select``, at a token count that
    is no whole number of blocks."""
    x = _scores(kind, E, k, seed=6)
    select = x + jnp.asarray(np.random.default_rng(7).standard_normal(
        E).astype(np.float32))
    for s in (None, select):
        want = jax.jit(lambda x, s: choose(x, k, select=s))(x, s)
        got = jax.jit(lambda x, s: choose(x, k, select=s, interpret=True))(
            x, s)
        np.testing.assert_array_equal(got[1], want[1])
        np.testing.assert_array_equal(got[0], want[0])


def test_a_differentiated_choice_counts_once_a_layer():
    """``router_choice_calls``: one a differentiated call, times the layers
    its trace stands for; an undifferentiated call counts nothing."""
    x = _scores("random", 64, 8)
    traced.reset()
    jax.make_jaxpr(lambda x: choose(x, 8))(x)
    assert traced.values()["router_choice_calls"] == 0
    with traced.stands_for(3):
        jax.make_jaxpr(jax.grad(lambda x: choose(x, 8)[0].sum()))(x)
    assert traced.values()["router_choice_calls"] == 3


@pytest.mark.parametrize("shape,dtype,k", [
    ((8, 16), "bfloat16", 2), ((8, 16), "float32", 17),
    ((8, 16), "float32", 0), ((2, 8, 16), "float32", 2)])
def test_what_it_cannot_choose_from_is_refused(shape, dtype, k):
    with pytest.raises(ValueError, match="choose"):
        choose(jnp.zeros(shape, dtype), k)
