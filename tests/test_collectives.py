"""Collective execution tests: the analogue of the reference's
dapple_all_reduce/all_gather/all_to_all integration tests
(tests/dapple_*_test.cc — real multi-device collectives asserting literals).
Here the collectives are XLA's, executed over the virtual 8-device mesh via
shard_map, asserting exact results."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@pytest.fixture()
def mesh(devices):
    return Mesh(np.array(devices), axis_names=("x",))


def test_psum_all_reduce(mesh):
    x = jnp.arange(8.0)

    def f(x):
        return jax.lax.psum(x, "x")

    out = shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P("x"))(x)
    # Each shard holds the sum of all shards: 0+1+...+7 = 28.
    np.testing.assert_array_equal(np.asarray(out), np.full((8,), 28.0))


def test_all_gather(mesh):
    x = jnp.arange(8.0).reshape(8, 1)

    def f(x):
        return jax.lax.all_gather(x, "x", axis=0, tiled=True)

    out = shard_map(f, mesh=mesh, in_specs=P("x", None),
                        out_specs=P("x", None))(x)
    assert out.shape == (64, 1)
    np.testing.assert_array_equal(np.asarray(out)[:8, 0], np.arange(8.0))


def test_all_to_all(mesh):
    # 8 devices, each with a row of 8 values; all_to_all transposes the
    # (device, position) layout.
    x = jnp.arange(64.0).reshape(8, 8)

    def f(x):  # local [1, 8] -> split columns across devices -> [8, 1]
        return jax.lax.all_to_all(x, "x", split_axis=1, concat_axis=0,
                                  tiled=True)

    out = shard_map(f, mesh=mesh, in_specs=P("x", None),
                        out_specs=P("x", None))(x)
    # Device d ends up holding column d: global (64, 1) stacking columns.
    assert out.shape == (64, 1)
    np.testing.assert_array_equal(
        np.asarray(out).reshape(8, 8),
        np.arange(64.0).reshape(8, 8).T)


def test_ppermute_ring(mesh):
    x = jnp.arange(8.0)

    def f(x):
        perm = [(i, (i + 1) % 8) for i in range(8)]
        return jax.lax.ppermute(x, "x", perm)

    out = shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P("x"))(x)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.roll(np.arange(8.0), 1))


def test_reduce_scatter(mesh):
    x = jnp.ones((8, 8))

    def f(x):  # [1, 8] per device
        return jax.lax.psum_scatter(x, "x", scatter_dimension=1, tiled=True)

    out = shard_map(f, mesh=mesh, in_specs=P("x", None),
                        out_specs=P("x", None))(x)
    np.testing.assert_array_equal(np.asarray(out), np.full((8, 1), 8.0))


def test_gspmd_inserts_allreduce_for_partial(mesh):
    """The planner's 'partial' contract: contraction-split dot under GSPMD
    produces the full result (XLA inserts the psum)."""
    a = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    asharded = jax.device_put(a, NamedSharding(mesh, P(None, "x")))
    bsharded = jax.device_put(b, NamedSharding(mesh, P("x", None)))
    out = jax.jit(jnp.dot,
                  out_shardings=NamedSharding(mesh, P()))(asharded, bsharded)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=1e-4)  # psum ordering vs local dot
    # The compiled module must contain a cross-device reduction.
    hlo = jax.jit(jnp.dot, out_shardings=NamedSharding(mesh, P())).lower(
        asharded, bsharded).compile().as_text()
    assert "all-reduce" in hlo or "reduce-scatter" in hlo
