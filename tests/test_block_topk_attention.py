"""The block top-k attention of MiniCPM-SALA's sparse layers
(``ops/pallas/block_topk_attention.py``; interpret mode: its own code): the
choice against the reference's sets, the chosen blocks' kernels against the
reference's explicit mask, attention from a saved forward, top-k's ties and
the sequence the resident form refuses. The model's test preset is the
geometry (2 key/value groups, blocks of 8, top 4, window 16)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import minicpm_sala as ref
from kernel_checks import kernel_counts, rel_l2
from kernel_checks import sala_hyper as hyper
from tepdist_tpu.models import minicpm_sala as sala
from tepdist_tpu.ops.pallas import block_topk_attention as bt

CFG = sala.CONFIGS["test"]
GEO = CFG.sparse


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def sparse_inputs(T, seed=0, dtype=jnp.float32):
    H, G, D = 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, do = (jax.random.normal(k, (2, T, H, D)).astype(dtype)
             for k in (ks[0], ks[3]))
    k, v = (jax.random.normal(k, (2, T, G, D)).astype(dtype)
            for k in (ks[1], ks[2]))
    return q, k, v, do


def reference_sets(q, k, hp):
    """bool [B, G, T, blocks] by the reference's own choice."""
    T, H, D = q.shape[1:]
    G = k.shape[2]
    return jnp.stack([ref.chosen_blocks(qs.reshape(T, G, H // G, D), ks, hp)
                      for qs, ks in zip(q, k)])


def as_sets(idx, n_blocks):
    B, G, T, _ = idx.shape
    return jnp.zeros((B, G, T, n_blocks), bool).at[
        jnp.arange(B)[:, None, None, None], jnp.arange(G)[None, :, None, None],
        jnp.arange(T)[None, None, :, None], idx].set(True)


def test_the_chosen_sets_are_the_references():
    """Scores drawn well apart (continuous random scores: no tie but among
    the forced blocks, which are all chosen): the same set for every query
    and group; sorted, the query's own block last of the valid entries and
    repeated after them."""
    q, k, _, _ = sparse_inputs(128)
    idx = bt.select_blocks(q, k, GEO)
    assert idx.shape == (2, 2, 128, 4) and idx.dtype == jnp.int32
    sets = as_sets(idx, 16)
    np.testing.assert_array_equal(np.asarray(sets),
                                  np.asarray(reference_sets(q, k, hyper(CFG))))
    idx, t = np.asarray(idx), np.arange(128)
    assert (np.diff(idx, axis=-1) >= 0).all()
    valid = np.minimum(4, t // 8 + 1)
    own = np.take_along_axis(idx, np.broadcast_to(
        (valid - 1)[None, None, :, None], idx.shape[:3] + (1,)), -1)[..., 0]
    assert (own == t // 8).all() and (idx[..., -1] == t // 8).all()
    # Block 0 and the window's blocks are in every set that has room.
    late = t >= 32
    assert sets[:, :, late, 0].all()
    assert all(sets[:, :, i, (i - 15) // 8:i // 8 + 1].all()
               for i in t[late])
    assert np.asarray(sets.sum(-1))[:, :, late].min() == 4


def test_the_keys_a_query_visits_are_the_geometrys():
    t = np.arange(128)
    sets = np.asarray(as_sets(
        bt.select_blocks(*sparse_inputs(128)[:2], GEO), 16))
    seen = np.repeat(sets, 8, axis=-1) & (t[None, :] <= t[:, None])
    np.testing.assert_array_equal(
        seen.sum(-1)[0, 0], np.asarray(bt.visible_keys(jnp.asarray(t), GEO)))
    assert bt.mean_keys_per_query(128, GEO) == pytest.approx(
        seen.sum(-1).mean())
    assert bt.mean_keys_per_query(32768, bt.BlockGeometry()) == 3812.5


# 24 positions: 3 blocks, under top 4. 16 key slots a trip: a set walked in
# two trips (the online softmax across trips), where the default takes the
# whole set in one.
@pytest.mark.parametrize("T,keys_a_trip", [(128, None), (24, None),
                                           (128, 16), (128, 8)])
def test_the_chosen_blocks_kernels_match_the_masked_reference(
        T, keys_a_trip, monkeypatch):
    """Handed the reference's sets, forward and all three gradients against
    explicit scores under an explicit mask."""
    if keys_a_trip:
        monkeypatch.setattr(bt, "KEYS_A_TRIP", keys_a_trip)
    q, k, v, do = sparse_inputs(T, seed=1)
    hp = hyper(CFG)
    sets = reference_sets(q, k, hp)
    K = min(4, T // 8)
    # The reference's sets as the kernels take them: sorted, then the own
    # block repeated.
    own = (jnp.arange(T) // 8)[None, None, :, None]
    idx = jnp.sort(jnp.where(sets, jnp.arange(T // 8), T // 8),
                   axis=-1)[..., :K]
    idx = jnp.where(idx < T // 8, idx, own).astype(jnp.int32)

    def masked(q, k, v):
        return jnp.stack([ref.masked_attention(
            qs.reshape(T, 2, 2, 16), ks, vs, chosen, hp).reshape(T, 4, 16)
            for qs, ks, vs, chosen in zip(q, k, v, sets)])

    out, vjp = jax.vjp(lambda *a: bt.topk_attention(*a, idx, GEO), q, k, v)
    want, ref_vjp = jax.vjp(masked, q, k, v)
    for name, g, w in zip(("out", "dq", "dk", "dv"), (out,) + vjp(do),
                          (want,) + ref_vjp(do)):
        assert rel_l2(g, w) < 2e-6, name


def test_the_chosen_blocks_kernels_in_bf16_stay_near_float32():
    q, k, v, do = sparse_inputs(128, seed=2, dtype=jnp.bfloat16)
    idx = bt.select_blocks(q, k, GEO)
    f32 = lambda x: x.astype(jnp.float32)                    # noqa: E731
    out, vjp = jax.vjp(lambda *a: bt.topk_attention(*a, idx, GEO), q, k, v)
    want, ref_vjp = jax.vjp(lambda *a: bt.topk_attention(*a, idx, GEO),
                            f32(q), f32(k), f32(v))
    for g, w in zip((out,) + vjp(do), (want,) + ref_vjp(f32(do))):
        assert g.dtype == jnp.bfloat16 and rel_l2(f32(g), w) < 1e-2


def test_the_choice_carries_no_gradient():
    """The sets are data to the attention: the gradient with the choice
    inside the differentiated function is the gradient with the sets handed
    in, and wrong shapes are refused."""
    q, k, v, _ = sparse_inputs(128)
    fixed = bt.select_blocks(q, k, GEO)
    inside = jax.grad(lambda q, k: bt.topk_attention(
        q, k, v, bt.select_blocks(q, k, GEO), GEO).sum(), argnums=(0, 1))(q, k)
    outside = jax.grad(lambda q, k: bt.topk_attention(
        q, k, v, fixed, GEO).sum(), argnums=(0, 1))(q, k)
    for a, b in zip(inside, outside):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="topk_attention"):
        bt.topk_attention(q, k, k, jnp.zeros((2, 2, 64, 4), jnp.int32), GEO)
    with pytest.raises(ValueError, match="select_blocks"):
        bt.select_blocks(q[:, :100], k[:, :100], GEO)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_attention_from_a_saved_forward_is_the_kernels_own(dtype):
    """What a walk's recomputation runs (``_attend_from``: the forward
    kernel's ``(o, lse)`` handed in) has ``_attend``'s output and VJP bit
    for bit, runs the backward kernel alone, and takes no gradient into the
    saved pair or the sets."""
    q, k, v, do = sparse_inputs(128, seed=3, dtype=dtype)
    idx = bt.select_blocks(q, k, GEO)
    bs = GEO.block_size
    want, want_pull = jax.vjp(
        lambda *a: bt._attend(*a, idx, bs, True), q, k, v)
    o, lse = bt.forward(q, k, v, idx, block_size=bs, interpret=True)
    assert lse.shape == (2, 2, 2, 128) and lse.dtype == jnp.float32
    from_saved = lambda *a: bt._attend_from(*a, idx, o, lse, bs, True)  # noqa: E731,E501
    got, pull = jax.vjp(from_saved, q, k, v)
    for a, b in zip((got,) + pull(do), (want,) + want_pull(do), strict=True):
        assert a.dtype == b.dtype == dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert kernel_counts(from_saved, q, k, v) == {}
    assert kernel_counts(
        jax.grad(lambda *a: from_saved(*a).astype(jnp.float32).sum(),
                 argnums=(0, 1, 2)), q, k, v) == {"tepdist_topk_attn_bwd": 1}


# Scores apart, scores on a grid of four values (ties inside and at the
# K-th place), all equal, and infinities of both signs among them.
@pytest.mark.parametrize("levels,K", [(0, 4), (4, 4), (4, 7), (1, 5),
                                      (0, 16)])
def test_the_highest_entries_are_top_ks_ties_to_the_lower_index(levels, K):
    rng = np.random.default_rng(levels + K)
    score = rng.random((3, 40, 16)).astype(np.float32)
    if levels:
        score = np.round(score * (levels - 1)) / max(levels - 1, 1)
    score[:, ::3, :2] = np.inf
    score[:, 1::4, -5:] = -np.inf
    score[0, 0] = -0.0
    got = np.asarray(bt.highest(jnp.asarray(score), K))
    _, idx = jax.lax.top_k(jnp.asarray(score), K)
    want = np.zeros(score.shape, bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    np.testing.assert_array_equal(got, want)


def test_a_sequence_the_resident_form_cannot_hold_is_refused():
    big = jax.ShapeDtypeStruct((1, 2 ** 20, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2 ** 20, 2, 128), jnp.bfloat16)
    idx = jax.ShapeDtypeStruct((1, 2, 2 ** 20, 64), jnp.int32)
    with pytest.raises(ValueError, match="VMEM"):
        jax.eval_shape(lambda q, k, v, i: bt.topk_attention(
            q, k, v, i, bt.BlockGeometry(), interpret=False), big, kv, kv,
            idx)
