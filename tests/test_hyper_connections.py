"""The residual path that is not an add (``models/layers.py:hyper_maps`` /
``hyper_read`` / ``hyper_write``, manifold-constrained hyper-connections)
against the plain reference's loop on ``[T, n, n]``, and the cross entropy
with a weight a position (``cross_entropy(weights=)``) against the dense
form on each of its three paths."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing as ref
from tepdist_tpu.models import layers
from tepdist_tpu.telemetry import metrics

# Level 1: at LLVM level 0 the clamped maps' two programs lie 1.5e-5 apart
# where 1e-5 is allowed (``test_the_clamp_holds_the_mixing_matrix_finite``).
pytestmark = pytest.mark.usefixtures("optimized_programs")

B, T, N, D = 2, 32, 4, 16            # 64 tokens, four lanes of 16
WIDE = N * N + 2 * N
KEY = jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def hyper(rounds):
    return ref.Hyper(qk_nope_head_dim=0, qk_rope_head_dim=0, v_head_dim=0,
                     kv_lora_rank=0, top_k=0, held=(0, 0), lanes=N,
                     sinkhorn_iters=rounds)


@functools.lru_cache(maxsize=None)
def operands(n=N):
    """A stream whose lanes differ and maps that differ from token to
    token: ``phi`` at a tenth, ``b`` normal(1) with 2 more on the mixing
    matrix' diagonal, ``alpha`` away from one."""
    ks = jax.random.split(KEY, 5)
    wide = n * n + 2 * n
    return (jax.random.normal(ks[0], (B, T, n * D)),
            0.1 * jax.random.normal(ks[1], (n * D, wide)),
            jax.random.normal(ks[2], (wide,)) + jnp.concatenate(
                [jnp.zeros((2 * n,)), 2.0 * jnp.eye(n).reshape(-1)]),
            jnp.asarray([0.7, 1.3, 0.9]),
            jax.random.normal(ks[3], (B, T, D)))


def program_maps(x, phi, b, alpha, rounds):
    return layers.hyper_maps(x, phi, b, alpha, rounds=rounds, eps=1e-6)


def reference_maps(x, phi, b, alpha, rounds):
    """The reference's three maps a sequence, side by side as the
    program's: H_pre | H_post | H_res row by row."""
    out = []
    for seq in x:
        pre, post, H = ref.hyper_maps(seq.reshape(T, -1, D), phi, b, alpha,
                                      hyper(rounds))
        out.append(jnp.concatenate([pre, post, H.reshape(T, -1)], -1))
    return jnp.stack(out)


def through(maps_fn, x, phi, b, alpha, f, rounds):
    """A sub-layer that multiplies its input by 3 and adds ``f``: maps,
    read, write, as a block strings them."""
    maps = maps_fn(x, phi, b, alpha, rounds)
    return maps, layers.hyper_read(x, maps), layers.hyper_write(
        x, maps, 3.0 * layers.hyper_read(x, maps) + f)


def reference_through(x, phi, b, alpha, f, rounds):
    ys, outs = [], []
    for seq, fs in zip(x, f):
        X = seq.reshape(T, N, D)
        pre, post, H = ref.hyper_maps(X, phi, b, alpha, hyper(rounds))
        y = jnp.einsum("ti,tid->td", pre, X)
        ys.append(y)
        outs.append((jnp.einsum("tij,tjd->tid", H, X) + post[:, :, None]
                     * (3.0 * y + fs)[:, None, :]).reshape(T, N * D))
    return jnp.stack(ys), jnp.stack(outs)


@pytest.mark.parametrize("rounds", [5, 20])
def test_the_maps_are_the_references_loop(rounds):
    x, phi, b, alpha, _ = operands()
    got = jax.jit(program_maps, static_argnums=4)(x, phi, b, alpha, rounds)
    want = reference_maps(x, phi, b, alpha, rounds)
    assert got.shape == (B, T, WIDE) and got.dtype == jnp.float32
    # float32 both; the program divides once a row or column and multiplies,
    # the reference divides every entry.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-6)
    assert float(jnp.std(got[..., 0])) > 0.05     # a token's own maps


@pytest.mark.parametrize("positions", [512, 13])
def test_the_planes_layout_gives_the_same_maps_and_gradients(positions):
    """The planes follow the count of tokens, not how they divide into
    sequences (``[8, count / 8]``; 26 tokens do not divide and stay
    ``[2, 13]``, a sequence of them ``[1, 13]``): two sequences at once are
    each sequence alone, maps and the gradients of all four operands, to
    float32's last places."""
    x, phi, b, alpha, _ = operands()
    x = jnp.tile(x, (1, 16, 1))[:, :positions]
    cot = jax.random.normal(jax.random.PRNGKey(7), (B, positions, WIDE))

    def loss(x, phi, b, alpha, cot):
        maps = program_maps(x, phi, b, alpha, 5)
        return jnp.sum(maps * cot), maps

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True))
    (dx, *both), maps = grad(x, phi, b, alpha, cot)
    halves = [grad(x[i:i + 1], phi, b, alpha, cot[i:i + 1]) for i in (0, 1)]
    for i, ((dxi, *_), mapsi) in enumerate(halves):
        np.testing.assert_allclose(np.asarray(maps[i:i + 1]),
                                   np.asarray(mapsi), rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(dx[i:i + 1]), np.asarray(dxi),
                                   rtol=1e-5, atol=1e-6)
    for g, g0, g1 in zip(both, halves[0][0][1:], halves[1][0][1:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(g0 + g1),
                                   rtol=1e-5, atol=1e-5)


def test_twenty_rounds_leave_rows_and_columns_summing_to_one():
    """Rows sum to one by the round's last division; columns come there at
    the rate the matrix allows, so the 1e-4 is held on maps a third as far
    from their diagonal start as the other tests' (whose columns stand
    4e-3 off after twenty rounds), and five rounds are further off."""
    x, phi, b, alpha, _ = operands()
    start = jnp.concatenate(
        [jnp.zeros((2 * N,)), 2.0 * jnp.eye(N).reshape(-1)])
    phi, b = 0.3 * phi, start + 0.3 * (b - start)
    H = program_maps(x, phi, b, alpha, 20)[..., 2 * N:].reshape(B, T, N, N)
    assert float(H.min()) > 0
    for axis in (-1, -2):
        np.testing.assert_allclose(np.asarray(H.sum(axis)), 1.0, rtol=0,
                                   atol=1e-4)
    H5 = program_maps(x, phi, b, alpha, 5)[..., 2 * N:].reshape(B, T, N, N)
    assert float(jnp.abs(H5.sum(-2) - 1).max()) > 10 * float(
        jnp.abs(H.sum(-2) - 1).max())


def test_the_clamp_holds_the_mixing_matrix_finite():
    x, phi, b, alpha, _ = operands()
    maps = program_maps(x, 40.0 * phi, b, alpha * 40.0, 5)
    assert bool(jnp.isfinite(maps).all())
    want = reference_maps(x, 40.0 * phi, b, alpha * 40.0, 5)
    np.testing.assert_allclose(np.asarray(maps), np.asarray(want), rtol=0,
                               atol=1e-5)


def test_read_and_write_are_the_references_sums_and_so_are_gradients():
    """One jit: a sub-layer through maps, read and write, and the gradient
    of a scalar of its results in the stream, ``phi``, ``b``, ``alpha`` and
    the sub-layer's addend, against autodiff of the reference's einsums."""
    x, phi, b, alpha, f = operands()
    w = jax.random.normal(jax.random.PRNGKey(9), (B, T, N * D))

    def scalar(fn):
        def of(x, phi, b, alpha, f):
            y, out = fn(x, phi, b, alpha, f)
            return jnp.sum(out * w) + jnp.sum(y * y)
        return of

    program = lambda *a: through(program_maps, *a, 5)[1:]      # noqa: E731
    reference = lambda *a: reference_through(*a, 5)            # noqa: E731
    for got, want in zip(jax.jit(program)(x, phi, b, alpha, f),
                         reference(x, phi, b, alpha, f)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-5)
    got = jax.jit(jax.grad(scalar(program), argnums=(0, 1, 2, 3, 4)))(
        x, phi, b, alpha, f)
    want = jax.jit(jax.grad(scalar(reference), argnums=(0, 1, 2, 3, 4)))(
        x, phi, b, alpha, f)
    for name, g, v in zip(("x", "phi", "b", "alpha", "f"), got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(v), rtol=0,
            atol=2e-5 * float(jnp.abs(v).max()), err_msg=name)


def test_one_lane_with_maps_of_one_is_the_plain_residual():
    """``n`` 1 and maps of one: read is the stream and write is ``x + f``,
    bit for bit. Leaves that pin the maps (``alpha`` 0; ``b_pre`` 40, whose
    sigmoid is 1 in float32; ``b_post`` 0, twice whose sigmoid is 1; ``b_res``
    at the clamp) give them within the forty reciprocals' roundings."""
    x, _, _, _, f = operands()
    x = x[..., :D]
    ones = jnp.ones((B, T, 3))
    np.testing.assert_array_equal(np.asarray(layers.hyper_read(x, ones)),
                                  np.asarray(x))
    np.testing.assert_array_equal(np.asarray(layers.hyper_write(x, ones, f)),
                                  np.asarray(x + f))
    maps = layers.hyper_maps(x, jnp.ones((D, 3)), jnp.asarray([40., 0., 30.]),
                             jnp.zeros((3,)), rounds=20, eps=1e-6)
    np.testing.assert_allclose(np.asarray(maps), 1.0, rtol=0, atol=2e-6)


def test_a_bf16_stream_is_mixed_in_float32():
    x, phi, b, alpha, f = operands()
    xb = x.astype(jnp.bfloat16)
    maps = program_maps(xb, phi.astype(jnp.bfloat16), b, alpha, 20)
    assert maps.dtype == jnp.float32
    out = layers.hyper_write(xb, maps, f.astype(jnp.bfloat16))
    assert out.dtype == jnp.bfloat16 and out.shape == x.shape
    # One rounding, at the end: the float32 sums of the bf16 stream.
    want = layers.hyper_write(xb.astype(jnp.float32), maps,
                              f.astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(want.astype(jnp.bfloat16), np.float32))
    with pytest.raises(ValueError, match="hyper maps"):
        layers.hyper_read(x, maps[..., :WIDE - 1])


# -- the cross entropy with a weight a position -------------------------------

V, HID = 96, 24


@functools.lru_cache(maxsize=None)
def loss_operands():
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (B, T, HID))
    head = 0.3 * jax.random.normal(ks[1], (V, HID))
    targets = jax.random.randint(ks[2], (B, T), 0, V)
    weights = jax.random.uniform(ks[3], (B, T)).at[:, -1].set(0.0)
    return x, head, targets, weights


def dense_form(x, head, targets, weights):
    logits = x @ head.T
    ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, targets[..., None], -1)[..., 0]
    return jnp.sum(ce * weights) / jnp.sum(weights)


# (chunk, differentiated): the dense path, the chunk loop alone (a call
# nobody differentiates), the loop that makes the gradients (custom_vjp);
# 24 does not divide the 64 tokens (a padded tail chunk).
@pytest.mark.parametrize("chunk,differentiated", [
    (0, True), (16, False), (16, True), (24, True)],
    ids=["dense", "chunked-loop", "chunked-vjp", "chunked-vjp-padded"])
def test_a_weight_a_position_is_the_dense_weighted_mean(chunk,
                                                        differentiated):
    x, head, targets, weights = loss_operands()
    want, (wx, wh) = jax.value_and_grad(dense_form, (0, 1))(
        x, head, targets, weights)
    loss = lambda x, head: layers.cross_entropy(             # noqa: E731
        x, head, targets, chunk, weights=weights)
    if not differentiated:
        assert float(jax.jit(loss)(x, head)) == pytest.approx(
            float(want), rel=1e-6)
        return
    got, (gx, gh) = jax.jit(jax.value_and_grad(loss, (0, 1)))(x, head)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(wx), rtol=0,
                               atol=1e-6 * float(jnp.abs(wx).max()) + 1e-9)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(wh), rtol=0,
                               atol=1e-5 * float(jnp.abs(wh).max()))
    # A position of weight 0 moves nothing: the last one's hidden state
    # has no gradient, and another target there is the same loss.
    np.testing.assert_array_equal(np.asarray(gx[:, -1]), 0.0)
    other = jax.jit(jax.value_and_grad(lambda x, head: layers.cross_entropy(
        x, head, targets.at[:, -1].add(1) % V, chunk, weights=weights),
        (0, 1)))(x, head)[0]
    assert float(other) == float(got)
    assert metrics().gauge("ce_weighted_positions").value >= B * T


@pytest.mark.parametrize("chunk", [0, 16, 24],
                         ids=["dense", "chunked", "chunked-padded"])
def test_no_weights_is_the_program_it_was(chunk):
    """``weights=None`` divides by the positions' count as before: the same
    value and gradients, bit for bit, as weights of one (whose sum is that
    count, exactly)."""
    x, head, targets, _ = loss_operands()
    plain = jax.jit(jax.value_and_grad(
        lambda x, head: layers.cross_entropy(x, head, targets, chunk),
        (0, 1)))
    ones = jax.jit(jax.value_and_grad(
        lambda x, head: layers.cross_entropy(
            x, head, targets, chunk, weights=jnp.ones((B, T))), (0, 1)))
    for got, want in zip(jax.tree_util.tree_leaves(plain(x, head)),
                         jax.tree_util.tree_leaves(ones(x, head))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if chunk == 0:          # today's dense form, written out
        logits = x @ head.T
        want = jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, targets[..., None], -1)[..., 0])
        assert float(plain(x, head)[0]) == pytest.approx(float(want),
                                                         rel=1e-6)
