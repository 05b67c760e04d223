"""The scalar-decay delta-rule kernels (``ops/pallas/gdn_attention.py``;
interpret mode: their own code) against the chunked ``jax.numpy`` form beside
them and the token-by-token recurrence of
``benchmark/reference/qwen3_next.py``: values and all five gradients, the
custom VJP, a sequence of several chunks (the last one padded) and one
shorter than a chunk, decays at both ends of the initialisation's range and
at ``g`` = -20 a token, **equal to ``kda_attention`` with ``g`` broadcast and
``q, k`` repeated** (what says the two rules are one), the rule's two
limits, rows of a batch that do not meet, the gauge, the shapes refused, a
key head's two inverses side by side against each alone, and the three cases
of a hand-over to a walk
(``ops/pallas/flash_attention.py:KeptForward``). 2 key heads under 4 value
heads but for the one case at the published heads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from kernel_checks import inverses_side_by_side, kernel_counts, rel_l2

from benchmark.reference import qwen3_next as ref
from tepdist_tpu.ops.pallas import flash_attention as fa
from tepdist_tpu.ops.pallas import gdn_attention as gdn
from tepdist_tpu.ops.pallas import kda_attention as kda
from tepdist_tpu.telemetry import metrics, traced
from tools.gdn_bench import broadcast, make_inputs

NAMES = ("out", "dq", "dk", "dv", "dg", "dbeta")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def inputs(B, T, Hk, Hv, K, seed=0, decay_scale=1.0, dtype=jnp.float32):
    """``make_inputs`` a row of the batch (a layer's operands: unit-norm
    ``q`` and ``k``, decays over the initialisation's range)."""
    rows = [make_inputs(T, Hk, Hv, K, dtype, seed + b, decay_scale)
            for b in range(B)]
    return tuple(jnp.concatenate(xs) for xs in zip(*rows))


def recurrence(q, k, v, g, beta):
    """The reference's recurrence over a batch."""
    Hv = beta.shape[2]
    K = v.shape[2] // Hv

    def heads(x):
        return x.reshape(x.shape[0], -1, K)

    return jnp.stack([ref.recurrence(heads(q1), heads(k1), heads(v1), g1,
                                     b1).reshape(v1.shape)
                      for q1, k1, v1, g1, b1 in zip(q, k, v, g, beta)])


def out_and_gradients(fn, operands):
    out, vjp = jax.vjp(fn, *operands[:5])
    return (out,) + vjp(operands[5])


def kernels(chunk, **how):
    def run(*x):
        return (gdn.forward(*x[:5], chunk=chunk, **how),) \
            + gdn.backward(*x, chunk=chunk, **how)
    return run


def distances(got, want):
    return {n: rel_l2(g, w) for n, g, w in zip(NAMES, got, want)}


# 40 positions in chunks of 16 (three chunks, the last one padded) and 5
# positions in a chunk of 16 (shorter than a chunk).
@pytest.mark.parametrize("T,chunk", [(40, 16), (5, 16)])
def test_kernels_match_the_recurrence_and_the_chunked_form(T, chunk):
    x = inputs(2, T, 2, 4, 16)
    want = out_and_gradients(recurrence, x)
    plain = out_and_gradients(lambda *a: gdn.chunked(*a, chunk=chunk), x)
    got = kernels(chunk)(*x)
    assert got[1].shape == got[2].shape == x[0].shape
    assert got[4].shape == got[5].shape == x[4].shape
    assert max(distances(plain, want).values()) < 3e-6
    assert max(distances(got, want).values()) < 3e-6, distances(got, want)
    assert max(distances(got, plain).values()) < 3e-6


def test_kernels_at_the_published_heads():
    """16 key heads under 32 value heads of 128 channels: a whole chunk of
    64 and a padded one."""
    x = inputs(1, 80, 16, 32, 128, seed=3)
    want = out_and_gradients(recurrence, x)
    got = kernels(64)(*x)
    assert got[0].shape == (1, 80, 4096) and got[1].shape == (1, 80, 2048)
    assert got[4].shape == (1, 80, 32) and got[5].shape == (1, 80, 32)
    assert max(distances(got, want).values()) < 3e-6, distances(got, want)


# The initialisation's range is about (-1.6, -0.001) a token and head: every
# head at its slow end, at its fast end, and far past it at -20, where a
# chunk's exp(-G) is far outside float32 and every factor the kernel makes
# is at most 1 all the same.
@pytest.mark.parametrize("g", [-0.001, -1.6, -20.0])
def test_decays_at_both_ends_of_the_range_and_far_past_it(g):
    x = list(inputs(1, 70, 2, 4, 128, seed=5))
    x[3] = jnp.full_like(x[3], g)
    want = out_and_gradients(recurrence, x)
    got = kernels(64)(*x)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in got)
    read = distances(got, want)
    # The faster the decay the less reaches the next token (2e-9 of the
    # state at -20): dg is the difference of terms that nearly cancel, and
    # at -20 it is itself 1e-9 of dbeta, under the rounding of its terms
    # (the chunked jax.numpy form sits as far from the recurrence): there
    # its distance is held in the terms' units.
    assert max(read[n] for n in NAMES if n != "dg") < 3e-6, read
    if g == -20.0:
        assert float(jnp.linalg.norm(got[4] - want[4])) \
            < 1e-7 * float(jnp.linalg.norm(want[5])), read
    else:
        assert read["dg"] < {-0.001: 3e-6, -1.6: 3e-5}[g], read


def test_the_broadcast_call_of_the_per_channel_kernels_is_the_same_rule():
    """``kda_attention`` with ``g`` spread over a head's channels and ``q,
    k`` repeated to the value heads: the same output and, summed back over
    what was repeated, the same five gradients."""
    x = inputs(2, 40, 2, 4, 16, seed=9)
    wide = out_and_gradients(broadcast(kda.kda_attention, 2, 16), x)
    got = out_and_gradients(lambda *a: gdn.gdn_attention(*a, chunk=16), x)
    assert [a.shape for a in got] == [a.shape for a in wide]
    read = distances(got, wide)
    assert max(read.values()) < 3e-6, read


def test_beta_zero_only_decays_and_no_decay_is_the_plain_delta_rule():
    q, k, v, g, beta, _ = inputs(1, 48, 2, 4, 16, seed=7)
    # beta = 0: nothing is written; from S_0 = 0 the output is zero.
    out = gdn.forward(q, k, v, g, jnp.zeros_like(beta), chunk=16)
    assert not np.asarray(out).any()
    # alpha = 1, beta = 1: S_t = (I - k k^T) S_{t-1} + k v^T, by hand, value
    # head h on key head h // 2.
    one = gdn.forward(q, k, v, jnp.zeros_like(g), jnp.ones_like(beta),
                      chunk=16)
    Hv, K = 4, 16
    S = np.zeros((Hv, K, K))
    qs, ks = (np.asarray(t, np.float64).reshape(48, 2, K)
              for t in (q[0], k[0]))
    vs = np.asarray(v[0], np.float64).reshape(48, Hv, K)
    for t in range(48):
        for h in range(Hv):
            kk = ks[t, h // 2]
            S[h] = S[h] - np.outer(kk, kk @ S[h]) + np.outer(kk, vs[t, h])
            np.testing.assert_allclose(
                np.asarray(one[0, t]).reshape(Hv, K)[h],
                S[h].T @ qs[t, h // 2], rtol=2e-4, atol=2e-6)


def test_a_state_never_crosses_from_one_row_of_a_batch_to_the_next():
    x = inputs(2, 40, 2, 4, 16, seed=11)
    both = kernels(16)(*x)
    for b in range(2):
        alone = kernels(16)(*(t[b:b + 1] for t in x))
        for got, want in zip(both, alone):
            np.testing.assert_array_equal(np.asarray(got[b:b + 1]),
                                          np.asarray(want))


def _equal(got, want):
    for a, w in zip(got, want, strict=True):
        assert a.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


def test_the_custom_vjp_is_the_kernels_backward_and_counts_its_calls():
    x = inputs(2, 40, 2, 4, 16, seed=2)
    traced.reset()
    got = out_and_gradients(lambda *a: gdn.gdn_attention(*a, chunk=16), x)
    assert metrics().gauge("gdn_calls").value == 1
    _equal(got, kernels(16)(*x))
    assert got[4].dtype == jnp.float32 and got[5].dtype == jnp.float32
    # What a differentiated forward hands on: the state before every chunk
    # and each chunk's inverse, a value head each.
    o, states, inv = gdn.forward(*x[:5], chunk=16, states=True)
    assert states.shape == (2, 3, 4, 16, 16) and inv.shape == states.shape
    assert states.dtype == inv.dtype == jnp.float32
    _equal(gdn.backward(*x, kept=(states, inv), chunk=16), got[1:])


def _systems(C, dtype):
    """A key head's two value heads' ``A`` [C, C] of one chunk of ``C``
    tokens, by the definition, from operands of ``dtype``'s values."""
    _, k, _, g, beta, _ = inputs(1, C, 1, 2, 16, seed=21, dtype=dtype)
    k = k[0].astype(jnp.float32)
    G = jnp.cumsum(g[0].T, axis=1)                          # [2, C]
    i, j = gdn._ij(C)
    D = jnp.exp(jnp.where(j < i, G[:, :, None] - G[:, None], -jnp.inf))
    return list((k @ k.T) * D * beta[0].T[..., None])


# Chunks of 16 and of the cell's 128 in float32, and the cell's as the chip
# runs it: operands of bf16 values, a product inside the inverse three bf16
# passes.
@pytest.mark.parametrize("C,narrow", [(16, False), (128, False), (128, True)])
def test_a_key_heads_inverses_side_by_side_are_each_alone(C, narrow):
    inverses_side_by_side(
        gdn._inverse, _systems(C, jnp.bfloat16 if narrow else jnp.float32),
        narrow)


def test_the_three_cases_of_a_hand_over_are_one_call():
    """Outside any walk, recording and replaying (``flash_attention.
    hand_over``): the same ``o`` and gradients bit for bit; the recording
    runs the forward kernel alone and keeps ``(o, states, inv)``, the replay
    runs the backward kernel and no other."""
    x = inputs(1, 40, 2, 4, 16, seed=4)

    def attend(*a):
        return gdn.gdn_attention(*a, chunk=16)

    want = out_and_gradients(attend, x)
    traced.reset()
    with fa.KeptForward() as keep:
        recorded = attend(*x[:5])
    assert metrics().gauge("gdn_calls").value == 1
    (kept,) = keep.kept
    assert [a.shape for a in kept] == [
        (1, 40, 64), (1, 3, 4, 16, 16), (1, 3, 4, 16, 16)]

    def replayed(*a):
        with fa.KeptForward(keep.kept):
            return attend(*a)

    _equal((recorded,) + out_and_gradients(replayed, x)[1:], want)
    assert metrics().gauge("gdn_calls").value == 1
    assert kernel_counts(lambda *a: out_and_gradients(replayed, a),
                         *x) == {"tepdist_gdn_bwd": 1}
    assert kernel_counts(lambda *a: out_and_gradients(attend, a), *x) == {
        "tepdist_gdn_fwd": 1, "tepdist_gdn_bwd": 1}


def test_bf16_operands_keep_a_float32_state():
    """bf16 in, the results asked for in float32: the distance from the
    recurrence on the same (rounded) operands is the two-part matmuls' and
    the bf16 products' of ``q k^T`` and ``k k^T`` (exact: the operands are
    bf16 values), far under one rounding of the state to bf16."""
    x = inputs(1, 96, 2, 4, 128, seed=13, dtype=jnp.bfloat16)
    want = out_and_gradients(
        recurrence, tuple(t.astype(jnp.float32) for t in x))
    got = kernels(32, interpret=True, out_dtype=jnp.float32)(*x)
    sound = distances(got, want)
    assert max(sound.values()) < 2e-4, sound
    narrow = distances(kernels(32, out_dtype=jnp.float32,
                               state_dtype=jnp.bfloat16)(*x), want)
    assert narrow["out"] > 10 * sound["out"], (sound, narrow)


def test_shapes_that_are_refused():
    q, k, v, g, beta, _ = inputs(1, 32, 2, 4, 16)
    with pytest.raises(ValueError, match="gdn_attention"):
        gdn.gdn_attention(q, k, v[..., :48], g, beta)
    with pytest.raises(ValueError, match="gdn_attention"):
        gdn.gdn_attention(q, k[..., :16], v, g, beta)
    with pytest.raises(ValueError, match="gdn_attention"):
        gdn.gdn_attention(q, k, v, g[..., :2], beta)
    with pytest.raises(ValueError, match="gdn_attention"):
        gdn.gdn_attention(q, k, v, g, beta, chunk=12)
