"""The delta-rule kernels (``ops/pallas/kda_attention.py``; interpret mode:
their own code) against the chunked ``jax.numpy`` form beside them and the
token-by-token recurrence of ``benchmark/reference/kimi_linear.py``: values
and all five gradients, the custom VJP, a padded last chunk and a sequence
shorter than a chunk, decays at both ends of the initialisation's range and
at ``g`` = -5 a token, the rule's two limits, rows of a batch that do not
meet, the gauge and the shapes refused; what a differentiated forward hands
on (the states and each chunk's inverse), a grid step's two inverses side by
side against each alone, an odd head count, and the three cases of a
hand-over to a walk (``ops/pallas/flash_attention.py:KeptForward``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from kernel_checks import inverses_side_by_side, kernel_counts, rel_l2

from benchmark.reference import kimi_linear as ref
from tepdist_tpu.ops.pallas import flash_attention as fa
from tepdist_tpu.ops.pallas import kda_attention as kda
from tepdist_tpu.telemetry import metrics, traced
from tools.kda_bench import make_inputs

NAMES = ("out", "dq", "dk", "dv", "dg", "dbeta")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def inputs(B, T, H, K, seed=0, decay_scale=1.0, dtype=jnp.float32):
    """``make_inputs`` a row of the batch (a layer's operands: unit-norm
    ``q`` and ``k``, decays over the initialisation's range)."""
    rows = [make_inputs(T, H, K, dtype, seed + b, decay_scale)
            for b in range(B)]
    return tuple(jnp.concatenate(xs) for xs in zip(*rows))


def recurrence(q, k, v, g, beta):
    """The reference's recurrence over a batch ``[B, T, H * K]``."""
    H = beta.shape[2]

    def heads(x):
        return x.reshape(x.shape[0], H, -1)

    return jnp.stack([ref.recurrence(*map(heads, one[:4]), one[4]).reshape(
        one[0].shape) for one in zip(q, k, v, g, beta)])


def out_and_gradients(fn, operands):
    out, vjp = jax.vjp(fn, *operands[:5])
    return (out,) + vjp(operands[5])


def kernels(chunk, **how):
    def run(*x):
        return (kda.forward(*x[:5], chunk=chunk, **how),) \
            + kda.backward(*x, chunk=chunk, **how)
    return run


def distances(got, want):
    return {n: rel_l2(g, w) for n, g, w in zip(NAMES, got, want)}


# 40 positions in chunks of 16 (the last one padded), 64 in chunks of 32 and
# in one chunk, 5 positions in a chunk of 16 (shorter than a sub-block).
@pytest.mark.parametrize("T,chunk", [(40, 16), (64, 32), (64, 64), (5, 16)])
def test_kernels_match_the_recurrence_and_the_chunked_form(T, chunk):
    x = inputs(2, T, 4, 16)
    want = out_and_gradients(recurrence, x)
    plain = out_and_gradients(lambda *a: kda.chunked(*a, chunk=chunk), x)
    got = kernels(chunk)(*x)
    assert max(distances(plain, want).values()) < 3e-6
    assert max(distances(got, want).values()) < 3e-6, distances(got, want)
    assert max(distances(got, plain).values()) < 3e-6


def test_kernels_at_the_published_heads():
    """32 heads of 128 channels: a whole chunk of 64 and a padded one."""
    x = inputs(1, 80, 32, 128, seed=3)
    want = out_and_gradients(recurrence, x)
    got = kernels(64)(*x)
    assert got[4].shape == (1, 80, 4096) and got[5].shape == (1, 80, 32)
    assert max(distances(got, want).values()) < 3e-6, distances(got, want)


# The initialisation's range is about (-1.6, -0.001) a token and channel:
# every channel at its slow end, at its fast end, and past it at -5, where
# exp(-G) of a chunk of 64 is far outside float32.
@pytest.mark.parametrize("g", [-0.001, -1.6, -5.0])
def test_decays_at_both_ends_of_the_range_and_past_it(g):
    x = list(inputs(1, 70, 2, 128, seed=5))
    x[3] = jnp.full_like(x[3], g)
    want = out_and_gradients(recurrence, x)
    got = kernels(64)(*x)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in got)
    read = distances(got, want)
    # The faster the decay the less reaches the next token (0.7% of the
    # state at -5): dg is the difference of terms that nearly cancel.
    assert max(read[n] for n in NAMES if n != "dg") < 3e-6, read
    assert read["dg"] < {-0.001: 3e-6, -1.6: 3e-5, -5.0: 1e-3}[g], read


def test_beta_zero_only_decays_and_no_decay_is_the_plain_delta_rule():
    q, k, v, g, beta, _ = inputs(1, 48, 2, 16, seed=7)
    # beta = 0: nothing is written; from S_0 = 0 the output is zero.
    out = kda.forward(q, k, v, g, jnp.zeros_like(beta), chunk=16)
    assert not np.asarray(out).any()
    # alpha = 1, beta = 1: S_t = (I - k k^T) S_{t-1} + k v^T, by hand.
    one = kda.forward(q, k, v, jnp.zeros_like(g), jnp.ones_like(beta),
                      chunk=16)
    H, K = 2, 16
    S = np.zeros((H, K, K))
    qs, ks, vs = (np.asarray(t, np.float64).reshape(48, H, K)
                  for t in (q[0], k[0], v[0]))
    for t in range(48):
        for h in range(H):
            kk = ks[t, h]
            S[h] = S[h] - np.outer(kk, kk @ S[h]) + np.outer(kk, vs[t, h])
            np.testing.assert_allclose(
                np.asarray(one[0, t]).reshape(H, K)[h], S[h].T @ qs[t, h],
                rtol=2e-4, atol=2e-6)


def test_a_state_never_crosses_from_one_row_of_a_batch_to_the_next():
    x = inputs(2, 40, 2, 16, seed=11)
    both = kernels(16)(*x)
    for b in range(2):
        alone = kernels(16)(*(t[b:b + 1] for t in x))
        for got, want in zip(both, alone):
            np.testing.assert_array_equal(np.asarray(got[b:b + 1]),
                                          np.asarray(want))


def test_the_custom_vjp_is_the_kernels_backward_and_counts_its_calls():
    x = inputs(2, 40, 2, 16, seed=2)
    traced.reset()
    got = out_and_gradients(lambda *a: kda.kda_attention(*a, chunk=16), x)
    assert metrics().gauge("kda_calls").value == 1
    for a, w in zip(got, kernels(16)(*x)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))
    assert got[4].dtype == jnp.float32 and got[5].dtype == jnp.float32


def _equal(got, want):
    for a, w in zip(got, want, strict=True):
        assert a.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


# 40 positions in chunks of 16: the last chunk is padded.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_backward_from_a_handed_pair_is_the_backward_that_makes_it(dtype):
    x = inputs(2, 40, 2, 16, seed=6, dtype=dtype)
    o, states, inv = kda.forward(*x[:5], chunk=16, states=True)
    assert o.dtype == dtype and o.shape == x[0].shape
    assert states.shape == (2, 3, 2, 16, 16) and inv.shape == states.shape
    assert states.dtype == inv.dtype == jnp.float32
    _equal([o], [kda.forward(*x[:5], chunk=16)])
    _equal(kda.backward(*x, kept=(states, inv), chunk=16),
           kda.backward(*x, chunk=16))


def test_the_handed_inverse_is_the_inverse_of_the_chunks_system():
    """``A`` by its definition, a chunk and head at a time in float64: the
    third result is ``_inverse`` of it and inverts ``I + A``; a padded row
    (``beta`` = 0) is the identity's."""
    T, C, H, K = 40, 16, 2, 16
    q, k, v, g, beta, _ = inputs(1, T, H, K, seed=8)
    inv = np.asarray(kda.forward(q, k, v, g, beta, chunk=C, states=True)[2])

    def chunks(x):           # [1, T, H * n] -> [chunks, H, C, n] float64
        x = np.pad(np.asarray(x[0], np.float64), ((0, 3 * C - T), (0, 0)))
        return x.reshape(3, C, H, -1).transpose(0, 2, 1, 3)

    ks, G, b = chunks(k), np.cumsum(chunks(g), axis=2), chunks(beta)
    decay = np.exp(G[..., :, None, :] - G[..., None, :, :])  # [.., i, j, K]
    A = np.tril(np.einsum("nhic,nhjc,nhijc->nhij", ks, ks, decay) * b, -1)
    eye = np.eye(C)
    np.testing.assert_allclose(inv[0] @ (eye + A), np.broadcast_to(
        eye, A.shape), atol=2e-6)
    doubled = jax.vmap(jax.vmap(lambda a: kda._inverse(a, False)))(
        jnp.asarray(A, jnp.float32))
    np.testing.assert_allclose(inv[0], np.asarray(doubled), atol=2e-6)
    np.testing.assert_array_equal(inv[0, 2, :, T - 2 * C:],
                                  np.broadcast_to(eye[T - 2 * C:], (H, 8, C)))


def _systems(C, dtype):
    """Two heads' ``A`` [C, C] of one chunk of ``C`` tokens, by the
    definition, from operands of ``dtype``'s values."""
    _, k, _, g, beta, _ = inputs(1, C, 2, 16, seed=21, dtype=dtype)
    k = k[0].astype(jnp.float32).reshape(C, 2, 16).transpose(1, 0, 2)
    G = jnp.cumsum(g[0].reshape(C, 2, 16).transpose(1, 0, 2), axis=1)
    i, j = kda._ij(C)
    diff = jnp.where((j < i)[..., None], G[:, :, None] - G[:, None], -jnp.inf)
    A = jnp.einsum("hic,hjc,hijc->hij", k, k, jnp.exp(diff))
    return list(A * beta[0].T[..., None])


# Chunks of 16 and of the cell's 128 in float32, and the cell's as the chip
# runs it: operands of bf16 values, a product inside the inverse three bf16
# passes.
@pytest.mark.parametrize("C,narrow", [(16, False), (128, False), (128, True)])
def test_a_grid_steps_inverses_side_by_side_are_each_alone(C, narrow):
    inverses_side_by_side(
        kda._inverse, _systems(C, jnp.bfloat16 if narrow else jnp.float32),
        narrow)


# The forward's sweep takes the heads two a grid step; an odd count's one a
# step, as the backward's does.
@pytest.mark.parametrize("H", [1, 3])
def test_an_odd_head_count_runs_a_head_a_grid_step(H):
    x = inputs(1, 40, H, 16, seed=17)
    want = out_and_gradients(recurrence, x)
    got = kernels(16)(*x)
    assert max(distances(got, want).values()) < 3e-6, distances(got, want)
    o, states, inv = kda.forward(*x[:5], chunk=16, states=True)
    assert states.shape == inv.shape == (1, 3, H, 16, 16)
    _equal(kda.backward(*x, kept=(states, inv), chunk=16), got[1:])


def test_the_three_cases_of_a_hand_over_are_one_call():
    """Outside any walk, recording and replaying (``flash_attention.
    hand_over``): the same ``o`` and gradients bit for bit; the recording
    runs the forward kernel alone and keeps ``(o, states, inv)``, the replay
    runs the backward kernel and no other."""
    x = inputs(1, 40, 2, 16, seed=4)

    def attend(*a):
        return kda.kda_attention(*a, chunk=16)

    want = out_and_gradients(attend, x)
    traced.reset()
    with fa.KeptForward() as keep:
        recorded = attend(*x[:5])
    assert metrics().gauge("kda_calls").value == 1
    (kept,) = keep.kept
    assert [a.shape for a in kept] == [
        (1, 40, 32), (1, 3, 2, 16, 16), (1, 3, 2, 16, 16)]

    def replayed(*a):
        with fa.KeptForward(keep.kept):
            return attend(*a)

    _equal((recorded,) + out_and_gradients(replayed, x)[1:], want)
    assert metrics().gauge("kda_calls").value == 1
    assert kernel_counts(lambda *a: out_and_gradients(replayed, a),
                         *x) == {"tepdist_kda_bwd": 1}
    assert kernel_counts(lambda *a: out_and_gradients(attend, a), *x) == {
        "tepdist_kda_fwd": 1, "tepdist_kda_bwd": 1}


def test_bf16_operands_keep_a_float32_state():
    """bf16 in, the results asked for in float32: the distance from the
    recurrence on the same (rounded) operands is the two-part matmuls', a
    hundred times under one rounding of the state to bf16."""
    x = inputs(1, 96, 2, 128, seed=13, dtype=jnp.bfloat16)
    want = out_and_gradients(
        recurrence, tuple(t.astype(jnp.float32) for t in x))
    got = kernels(32, interpret=True, out_dtype=jnp.float32)(*x)
    sound = distances(got, want)
    assert max(sound.values()) < 2e-4, sound
    narrow = distances(kernels(32, out_dtype=jnp.float32,
                               state_dtype=jnp.bfloat16)(*x), want)
    assert narrow["out"] > 10 * sound["out"], (sound, narrow)


def test_shapes_that_are_refused():
    q, k, v, g, beta, _ = inputs(1, 32, 2, 16)
    with pytest.raises(ValueError, match="kda_attention"):
        kda.kda_attention(q, k, v[..., :16], g, beta)
    with pytest.raises(ValueError, match="kda_attention"):
        kda.kda_attention(q, k, v, g, beta[..., :1].repeat(3, -1))
    with pytest.raises(ValueError, match="kda_attention"):
        kda.kda_attention(q, k, v, g, beta, chunk=24)
