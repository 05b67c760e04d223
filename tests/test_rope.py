"""``models/layers.py:rope``: the rotary embedding that never splits a head's
lanes (full-width tables, rotate-half a product with a signed permutation,
the backward the rotation by minus the angle) against the slice-and-
concatenate form it replaced, which is kept here; what its jaxprs may not
hold; and the gauge that counts it in a traced step."""

import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tepdist_tpu.models import jamba, layers, mellum
from tepdist_tpu.models.layers import RopeTable, rope
from tepdist_tpu.optim import make_optimizer
from tepdist_tpu.parallel.sync_free import build_ga_step
from tepdist_tpu.telemetry import metrics

B, H, T, HD = 1, 2, 24, 16
THETA = 100.0


@functools.partial(jax.jit, static_argnums=0)
def plain_freqs(half):
    """The plain table's angles a position, as ``rope`` makes them."""
    return 1.0 / (THETA ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def sliced(x, table, start=0, rotary_dim=None):
    """``rope`` as it was before it stopped splitting a head: the two halves
    of the rotated channels as arrays ``half`` wide, joined again by
    ``concatenate``."""
    hd = x.shape[-1]
    rotary_dim = hd if rotary_dim is None else rotary_dim
    half = rotary_dim // 2
    plain = not isinstance(table, RopeTable)
    freqs = plain_freqs(half) if plain \
        else jnp.asarray(table.inv_freq, jnp.float32)
    positions = jnp.arange(x.shape[2], dtype=jnp.float32)
    if not (isinstance(start, int) and start == 0):
        positions = positions + jnp.asarray(start, jnp.float32)
    angles = positions[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, None, :, :]
    sin = jnp.sin(angles)[None, None, :, :]
    if not plain and table.scale != 1.0:
        cos, sin = cos * table.scale, sin * table.scale
    x1, x2 = x[..., :half].astype(jnp.float32), \
        x[..., half:rotary_dim].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1).astype(x.dtype)
    if rotary_dim < hd:
        out = jnp.concatenate([out, x[..., rotary_dim:]], axis=-1)
    return out


def scaled_table(half):
    return RopeTable(tuple(float(f) for f in np.asarray(
        1.0 / (7.0 ** (np.arange(half, dtype=np.float32) / half)))),
        scale=1.25, name="rope_scaled")


STARTS = {"zero": 0, "int": 5, "traced": jnp.int32(5)}
CASES = list(itertools.product(("bfloat16", "float32"), ("theta", "table"),
                               (None, HD // 2), STARTS))


def forms(x, g, table, minus, start, rotary_dim):
    """Value and vjp of ``rope``, of the sliced form, and ``rope`` of ``g``
    at minus the angle."""
    def both(f):
        out, pull = jax.vjp(lambda x: f(x, table, start, rotary_dim), x)
        return out, pull(g)[0]
    return both(rope), both(sliced), rope(g, minus, start, rotary_dim)


@functools.lru_cache(maxsize=None)
def compiled(dtype, kind, rotary_dim):
    """(x, g, table, minus, run): ``run(x, g)`` is :func:`forms` at each of
    the three starts, in one compiled program (they share no operation: a
    start that is traced is a parameter, the other two are constants)."""
    half = (rotary_dim or HD) // 2
    table = THETA if kind == "theta" else scaled_table(half)
    # The same angles with their sign turned: the plain table's as a
    # RopeTable of its own (negated) frequencies.
    minus = RopeTable(
        tuple(-float(f) for f in (plain_freqs(half) if kind == "theta"
                                  else table.inv_freq)),
        scale=1.0 if kind == "theta" else table.scale)
    x, g = (jnp.asarray(a, dtype) for a in np.random.default_rng(
        half).standard_normal((2, B, H, T, HD), np.float32))

    @jax.jit
    def run(x, g, traced):
        return {name: forms(x, g, table, minus,
                            traced if name == "traced" else start, rotary_dim)
                for name, start in STARTS.items()}

    return x, g, table, minus, lambda x, g: run(x, g, STARTS["traced"])


def roundings_apart(got32, want32, x32, scale):
    """float32 results that differ by a product's rounding and no more: by at
    most two units in the last place of the largest product a head's row can
    hold (a sum that cancels keeps its products' roundings, so its own last
    place is no measure)."""
    largest = scale * np.abs(np.asarray(x32)).max(axis=-1, keepdims=True)
    return np.all(np.abs(np.asarray(got32) - np.asarray(want32))
                  <= 2 * np.spacing(largest))


def same(got, want):
    return all(np.array_equal(a, b) for a, b in zip(got, want))


@pytest.mark.parametrize(
    "dtype,kind,rotary_dim,start_kind", CASES,
    ids=[f"{d}-{k}-{'whole' if r is None else 'half'}-{s}"
         for d, k, r, s in CASES])
def test_value_and_vjp_are_the_sliced_forms_bit_for_bit(dtype, kind,
                                                        rotary_dim,
                                                        start_kind):
    x, g, table, minus, run = compiled(dtype, kind, rotary_dim)
    got, want, turned_back = run(x, g)[start_kind]
    assert got[0].dtype == got[1].dtype == x.dtype
    if (dtype, kind, start_kind) == ("float32", "table", "int") \
            and rotary_dim is not None:
        # Operation by operation nothing is fused, so nothing is contracted:
        # the two forms' values and gradients are the same bits, and the
        # backward is the rotation by minus the angle (one case: it takes a
        # second and a half).
        eager = forms(x, g, table, minus, STARTS["int"], rotary_dim)
        assert same(eager[0], eager[1]) and same(eager[0][1:], eager[2:])
    if not (same(got, want) and same(got[1:], (turned_back,))):
        # Inside one compiled program the CPU compiler contracts ``a * b +
        # c * d`` into a fused multiply-add that rounds one of the products
        # and not the other, and it picks another one in ``x * cos + (x @ P)
        # * sin`` than in ``x1 * sin + x2 * cos`` (float32 shows it, bfloat16
        # rounds it away): the float32 values before the cast, which are the
        # forms' values on the float32 copies of x and g, then stand a
        # product's rounding apart. The chip has no fused multiply-add:
        # ``tools/rope_bench.py`` holds the compiled forms to each other bit
        # for bit there.
        assert jax.default_backend() == "cpu"
        x32, g32 = x.astype(jnp.float32), g.astype(jnp.float32)
        scale = 1.0 if kind == "theta" else table.scale
        if dtype != "float32":
            run32 = compiled("float32", kind, rotary_dim)[-1]
            got, want, turned_back = run32(x32, g32)[start_kind]
        for a, b, of in ((got[0], want[0], x32), (got[1], want[1], g32),
                         (got[1], turned_back, g32)):
            assert roundings_apart(a, b, of, scale)
        got = run(x, g)[start_kind][0]
    # The channels past the rotary width pass as they are, both ways.
    out, dx = got
    if rotary_dim is not None:
        np.testing.assert_array_equal(np.asarray(out[..., rotary_dim:]),
                                      np.asarray(x[..., rotary_dim:]))
        np.testing.assert_array_equal(np.asarray(dx[..., rotary_dim:]),
                                      np.asarray(g[..., rotary_dim:]))


def _every_equation(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _every_equation(sub)


@pytest.mark.parametrize("rotary_dim", [None, HD // 2],
                         ids=["whole", "half"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_no_jaxpr_splits_or_joins_a_heads_lanes(dtype, rotary_dim):
    """Neither ``rope`` nor its vjp holds a ``concatenate`` or an array whose
    last dimension is ``half``: a head stays ``hd`` lanes wide."""
    half = (rotary_dim or HD) // 2
    assert half not in (B, H, T, HD)
    x = jnp.zeros((B, H, T, HD), dtype)

    def value(x, start):
        return rope(x, mellum.CONFIGS["test"].global_rope
                    if rotary_dim is None else THETA, start, rotary_dim)

    def pulled(x, start):
        out, pull = jax.vjp(lambda x: value(x, start), x)
        return pull(out)

    for f in (value, pulled):
        closed = jax.make_jaxpr(f)(x, jnp.int32(3))
        seen = 0
        for eqn in _every_equation(closed.jaxpr):
            seen += 1
            assert eqn.primitive.name not in ("concatenate", "pad",
                                              "slice", "dynamic_slice"), eqn
            for var in (*eqn.invars, *eqn.outvars):
                shape = getattr(var.aval, "shape", ())
                assert not shape or shape[-1] != half, (eqn, shape)
        assert seen > 10


def _gauge_after_tracing(model, cfg, name):
    """The gauge after one gradient-accumulation step of ``model``'s small
    preset is traced (nothing compiles or runs)."""
    tx = make_optimizer({"name": "adamw_bf16", "learning_rate": 1e-3})

    def loss(p, t):
        return model.loss_fn(p, t, cfg)

    def apply_fn(p, s, g):
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    step = build_ga_step(lambda p, t: jax.value_and_grad(loss)(p, t),
                         apply_fn, 2, loss_fn=loss)
    params = jax.eval_shape(
        lambda: model.stacked_init_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(tx.init, params)
    tokens = jax.eval_shape(lambda: model.fake_batch(cfg, 2, 32))
    jax.make_jaxpr(step)(params, state, tokens)
    return metrics().gauge(name).value


def test_rope_calls_reads_twice_the_layers_that_rotate():
    """Mellum2's small preset walks three layers (window, global, window)
    whose two kinds are the branches of a ``lax.cond``: each branch rotates
    q and k and counts a half, so a layer counts 2 whatever its kind. The
    small Jamba's attention layer carries no position: 0."""
    cfg = dataclasses.replace(mellum.CONFIGS["test"], remat=True)
    assert _gauge_after_tracing(mellum, cfg, "rope_calls") \
        == 2 * cfg.num_hidden_layers == 6
    assert "rope_calls" in layers.traced.GROUP \
        and len(layers.traced.GROUP["rope_calls"]) > 20
    cfg = dataclasses.replace(jamba.CONFIGS["test"], remat=True)
    assert _gauge_after_tracing(jamba, cfg, "rope_calls") == 0
