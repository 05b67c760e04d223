"""The state-space-dual kernels (``ops/pallas/ssd_attention.py``; interpret
mode: their own code) against the chunked ``jax.numpy`` form beside them and
the token-by-token recurrence of ``benchmark/reference/nemotron_h.py``:
values and all six gradients, the custom VJP, a sequence of several chunks
(the last one padded) and one shorter than a chunk, a group of heads sharing
``B``/``C``, heads two and eight a lane block and a head a block, steps at
both ends of the initialisation's range and far past it, the rule's limits by
hand, rows of a batch that do not meet, the gauge, the shapes refused, a
state through bf16 caught, and what the two delta rules and this rule share
(``_delta_rule.py``). 4 heads of 16 over 2 groups of 8 states but for the one
case at the published heads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from kernel_checks import kernel_counts, rel_l2

from benchmark.reference import nemotron_h as ref
from tepdist_tpu.ops.pallas import _delta_rule, gdn_attention
from tepdist_tpu.ops.pallas import ssd_attention as ssd
from tepdist_tpu.telemetry import metrics, traced
from tools.ssd_bench import make_inputs, recurrence

NAMES = ("out", "du", "dB", "dC", "dDelta", "dA", "dD")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def inputs(B, T, H, P, G, N, seed=0, step_scale=1.0, dtype=jnp.float32):
    """``make_inputs`` a row of the batch (a layer's operands: what a conv's
    silu leaves, steps over the initialisation's range), ``A`` and ``D``
    the first row's."""
    rows = [make_inputs(T, H, P, G, N, dtype, seed + b, step_scale)
            for b in range(B)]
    u, Bm, Cm, delta, _, _, dy = (jnp.concatenate(xs) for xs in zip(*rows))
    return u, Bm, Cm, delta, rows[0][4], rows[0][5], dy


def out_and_gradients(fn, operands):
    out, vjp = jax.vjp(fn, *operands[:6])
    return (out,) + vjp(operands[6])


def kernels(groups, chunk, **how):
    def run(*x):
        return (ssd.forward(*x[:6], groups=groups, chunk=chunk, **how),) \
            + ssd.backward(*x, groups=groups, chunk=chunk, **how)
    return run


def distances(got, want):
    return {n: rel_l2(g, w) for n, g, w in zip(NAMES, got, want)}


# 40 positions in chunks of 16 (three chunks, the last one padded) and 5
# positions in a chunk of 16 (shorter than a chunk); two heads a group.
@pytest.mark.parametrize("T,chunk", [(40, 16), (5, 16)])
def test_kernels_match_the_recurrence_and_the_chunked_form(T, chunk):
    x = inputs(2, T, 4, 16, 2, 8)
    want = out_and_gradients(recurrence(ref, 2), x)
    plain = out_and_gradients(
        lambda *a: ssd.chunked(*a, groups=2, chunk=chunk), x)
    got = kernels(2, chunk)(*x)
    assert [a.shape for a in got] == [a.shape for a in want]
    assert max(distances(plain, want).values()) < 3e-6
    assert max(distances(got, want).values()) < 3e-6, distances(got, want)
    assert max(distances(got, plain).values()) < 3e-6


# Heads a lane block: 8 heads of 16 in one group (eight a block), 2 heads of
# 128 (a head a block, no mask of lanes), 4 heads of 64 in groups of 2 (two a
# block, the published pairing) and a group of one head.
@pytest.mark.parametrize("H,P,G", [(8, 16, 1), (2, 128, 1), (4, 64, 2),
                                   (2, 16, 2)])
def test_heads_share_a_lane_block_and_a_groups_b_and_c(H, P, G):
    assert ssd.heads_a_block(P, H // G) == min(H // G, max(1, 128 // P))
    x = inputs(1, 40, H, P, G, 8, seed=3)
    want = out_and_gradients(recurrence(ref, G), x)
    got = kernels(G, 16)(*x)
    assert max(distances(got, want).values()) < 3e-6, distances(got, want)


def test_kernels_at_the_published_heads():
    """64 heads of 64 over 8 groups of 128 states: a whole chunk of 64 and a
    padded one."""
    x = inputs(1, 80, 64, 64, 8, 128, seed=3)
    want = out_and_gradients(recurrence(ref, 8), x)
    got = kernels(8, 64)(*x)
    assert got[0].shape == (1, 80, 4096) and got[2].shape == (1, 80, 1024)
    assert got[4].shape == (1, 80, 64) and got[5].shape == (64,)
    assert max(distances(got, want).values()) < 3e-6, distances(got, want)


# Delta over the initialisation's range is 0.001 to 0.1 a token and A -1 to
# -H: log decays of -0.001 (weak: a state that stands nearly still) to -6.4
# (strong), and at a step of 5 down to -20, where a chunk's exp(-Gc) is far
# outside float32 and every factor the kernel makes is at most 1 all the
# same.
@pytest.mark.parametrize("step", [0.001, 0.1, 5.0])
def test_weak_and_strong_decays(step):
    x = list(inputs(1, 70, 4, 16, 2, 8, seed=5))
    x[3] = jnp.full_like(x[3], step)
    want = out_and_gradients(recurrence(ref, 2), x)
    got = kernels(2, 16)(*x)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in got)
    read = distances(got, want)
    # At a step of 5 nothing reaches the next token but exp(-5 h) of the
    # state: dA is the sum of terms that are nearly nothing beside dDelta's,
    # held in their units.
    assert max(read[n] for n in NAMES if n != "dA") < 3e-6, read
    if step == 5.0:
        assert float(jnp.linalg.norm(got[5] - want[5])) \
            < 2e-5 * float(jnp.linalg.norm(want[4])), read
    else:
        assert read["dA"] < 1e-5, read


def test_the_rules_limits_by_hand():
    u, Bm, Cm, delta, A, D, _ = inputs(1, 48, 4, 16, 2, 8, seed=7)
    # No step: nothing is written and the state stands at zero; y = D u.
    out = ssd.forward(u, Bm, Cm, jnp.zeros_like(delta), A, D, groups=2,
                      chunk=16)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(u * jnp.repeat(D, 16)), rtol=1e-6)
    # A = 0 (no decay), D = 0: y_t = sum_{s <= t} Delta_s (C_t . B_s) u_s, by
    # hand, head h on group h // 2.
    one = ssd.forward(u, Bm, Cm, delta, jnp.zeros_like(A), jnp.zeros_like(D),
                      groups=2, chunk=16)
    us = np.asarray(u[0], np.float64).reshape(48, 4, 16)
    Bs, Cs = (np.asarray(t[0], np.float64).reshape(48, 2, 8)
              for t in (Bm, Cm))
    dl = np.asarray(delta[0], np.float64)
    S = np.zeros((4, 16, 8))
    for t in range(48):
        for h in range(4):
            S[h] += dl[t, h] * np.outer(us[t, h], Bs[t, h // 2])
            np.testing.assert_allclose(
                np.asarray(one[0, t]).reshape(4, 16)[h], S[h] @ Cs[t, h // 2],
                rtol=2e-4, atol=2e-6)


def test_a_state_never_crosses_from_one_row_of_a_batch_to_the_next():
    x = inputs(2, 40, 4, 16, 2, 8, seed=11)
    both = kernels(2, 16)(*x)
    for b in range(2):
        alone = kernels(2, 16)(*(t[b:b + 1] if t.ndim == 3 else t
                                 for t in x))
        for got, want in zip(both[:5], alone[:5]):
            np.testing.assert_array_equal(np.asarray(got[b:b + 1]),
                                          np.asarray(want))


def test_the_custom_vjp_is_the_kernels_backward_and_counts_its_calls():
    x = inputs(2, 40, 4, 16, 2, 8, seed=2)
    traced.reset()
    got = out_and_gradients(
        lambda *a: ssd.ssd_attention(*a, groups=2, chunk=16), x)
    # The primal's trace and the forward rule's: a forward that is
    # differentiated is one kernel call.
    assert metrics().gauge("ssd_calls").value == 1
    for a, w in zip(got, kernels(2, 16)(*x), strict=True):
        assert a.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))
    assert got[4].dtype == got[5].dtype == got[6].dtype == jnp.float32
    # What a differentiated forward hands on: the state before every chunk,
    # a lane block (here the group's two heads) each.
    y, states = ssd.forward(*x[:6], groups=2, chunk=16, states=True)
    assert states.shape == (2, 3, 2, 32, 8) and states.dtype == jnp.float32
    assert not np.asarray(states[:, 0]).any()
    for a, w in zip(ssd.backward(*x, groups=2, kept=states, chunk=16),
                    got[1:], strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))
    fn = lambda *a: out_and_gradients(                       # noqa: E731
        lambda *b: ssd.ssd_attention(*b, groups=2, chunk=16), a)
    assert kernel_counts(fn, *x) == {"tepdist_ssd_fwd__g2": 1,
                                     "tepdist_ssd_bwd__g2": 1}


def test_bf16_operands_keep_a_float32_state():
    """bf16 in, the results asked for in float32: the distance from the
    recurrence on the same (rounded) operands is the two-part matmuls', and
    a state carried through bf16 (the control) is a hundred times off."""
    x = inputs(1, 96, 4, 16, 2, 8, seed=13, dtype=jnp.bfloat16)
    want = out_and_gradients(recurrence(ref, 2),
                             tuple(t.astype(jnp.float32) for t in x))
    got = kernels(2, 16, out_dtype=jnp.float32)(*x)
    read = distances(got, want)
    assert max(read.values()) < 5e-5, read
    narrow = distances(kernels(2, 16, out_dtype=jnp.float32,
                               state_dtype=jnp.bfloat16)(*x), want)
    assert narrow["out"] > 50 * read["out"], (narrow, read)


@pytest.mark.parametrize("bad", [
    dict(u=(1, 40, 62)),                 # no whole number of heads
    dict(Bm=(1, 40, 24)),                # B and C of unequal width
    dict(delta=(1, 39, 4)),              # another length
    dict(A=(3,)),                        # not one a head
    dict(groups=3),                      # does not divide the heads
    dict(chunk=12),                      # not whole sublane tiles
])
def test_shapes_that_are_refused(bad):
    shapes = dict(u=(1, 40, 64), Bm=(1, 40, 16), Cm=(1, 40, 16),
                  delta=(1, 40, 4), A=(4,), D=(4,))
    how = dict(groups=bad.pop("groups", 2), chunk=bad.pop("chunk", 16))
    shapes.update(bad)
    with pytest.raises(ValueError, match="ssd_attention"):
        ssd.ssd_attention(*(jnp.zeros(s) for s in shapes.values()), **how)


def test_what_the_rules_share_is_one_copy():
    """The running sum, a head's column, a column as a row and the sweep are
    ``_delta_rule.py``'s, for the delta rules and this rule alike."""
    for name in ("_prefix", "_column", "_row", "_col", "sweep"):
        assert getattr(ssd, name) is getattr(_delta_rule, name)
    assert gdn_attention._row is _delta_rule._row
    col = jnp.arange(8.0)[:, None] * 0.37
    np.testing.assert_array_equal(np.asarray(_delta_rule._row(col)),
                                  np.asarray(col.T))
    np.testing.assert_array_equal(np.asarray(_delta_rule._col(col.T)),
                                  np.asarray(col))
