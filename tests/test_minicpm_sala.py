"""MiniCPM-SALA (openbmb): the program against the plain float32 reference
at a tiny preset with the published structure (2 key/value groups, blocks of
8, top 4, window 16, ``dense_len`` 32, 128 positions, runs of 1, 2, 1 and 1
layers), the linear-attention kernels (interpret mode: their own code)
against the sequential recurrence, the sparse layer's choice and its kernels
against the reference's sets and mask, the block's token-wise parts in
chunks, and the walks under gradient accumulation."""

import collections
import dataclasses
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.kernels import lightning_check
from benchmark.reference import minicpm_sala as ref
from tepdist_tpu.models import minicpm_sala as sala
from tepdist_tpu.ops.pallas import block_topk_attention as bt
from tepdist_tpu.ops.pallas import flash_attention as fa
from tepdist_tpu.ops.pallas import lightning_attention as la
from tepdist_tpu.optim import make_optimizer
from tepdist_tpu.parallel.sync_free import build_ga_step
from tepdist_tpu.telemetry import metrics

CFG = sala.CONFIGS["test"]
GEO = CFG.sparse
KEY = jax.random.PRNGKey(0)
OPT = {"name": "adamw_bf16", "learning_rate": 1e-3}
loss_and_grads = jax.jit(jax.value_and_grad(sala.loss_fn), static_argnums=2)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def hyper(cfg):
    return ref.Hyper(
        n_head=cfg.num_attention_heads, n_kv_head=cfg.num_key_value_heads,
        lightning_heads=cfg.lightning_nh, mixer_types=cfg.mixer_types,
        first_layer=cfg.first_layer, published_layers=cfg.published_layers,
        scale_emb=cfg.scale_emb, scale_depth=cfg.scale_depth,
        dim_model_base=cfg.dim_model_base, rope_theta=cfg.rope_theta,
        eps=cfg.rms_norm_eps, **cfg.sparse._asdict())


def to_reference(params, cfg):
    """The reference's view of either layout of the program's parameters."""
    if "l0" not in params:
        return params
    out = {k: params[k] for k in ("tok_emb", "lm_head", "norm_f")}
    out["layers"] = [params[f"l{i}"] for i in range(cfg.num_hidden_layers)]
    return out


def uneven_gains(params):
    """Norm gains off 1, so that a gain applied in the wrong place shows."""
    def off(path, a):
        name = jax.tree_util.keystr(path)
        if a.dtype != jnp.float32 or "norm" not in name and "_ln" not in name:
            return a
        return a * (1 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(len(name) + a.size), a.shape))
    return jax.tree_util.tree_map_with_path(off, params)


def rel_l2(got, want) -> float:
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def leaves_close(got, want, limit):
    want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        assert np.linalg.norm(np.asarray(want[path], np.float64)) > 0, path
        assert rel_l2(g, want[path]) < limit, jax.tree_util.keystr(path)


# -- the program against the reference ---------------------------------------

# 128 positions are past ``dense_len`` (the sparse layers choose blocks), 32
# are not (plain causal attention). 2e-5: float32 sums in another order
# through five layers; bf16 anywhere (2**-9 a rounding) reads 1e-3 or more.
@pytest.mark.parametrize("stacked,remat,T", [
    (False, False, 128), (True, False, 128), (True, True, 128),
    (True, True, 32)])
def test_logits_loss_and_every_gradient_match_the_reference(stacked, remat,
                                                            T):
    cfg = dataclasses.replace(CFG, remat=remat, loss_chunk=48 * remat)
    init = sala.stacked_init_params if stacked else sala.init_params
    params = uneven_gains(init(cfg, KEY, std=0.05))
    tokens = sala.fake_batch(cfg, 2, T, seed=1)
    as_ref, hp = to_reference(params, cfg), hyper(cfg)
    logits = ref.logits(as_ref, tokens[:, :-1], hp)
    np.testing.assert_allclose(
        np.asarray(sala.forward(params, tokens[:, :-1], cfg)),
        np.asarray(logits), rtol=0, atol=2e-5 * float(jnp.abs(logits).max()))
    loss, grads = loss_and_grads(params, tokens, cfg)
    want_loss, want = jax.value_and_grad(ref.loss)(as_ref, tokens, hp)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    leaves_close(to_reference(grads, cfg), want, 2e-5)


def test_the_dense_len_switch_counts_its_side():
    """At or under ``dense_len`` the sparse layers are plain causal
    attention (the flash kernels), past it the chosen blocks' kernels."""
    params = sala.stacked_init_params(CFG, KEY)
    gauge = lambda n: metrics().gauge(n).value or 0         # noqa: E731
    for T, dense, chosen in ((32, 2, 0), (40, 0, 2)):
        for name in ("topk_attn_dense_calls", "topk_attn_calls",
                     "lin_attn_calls"):
            metrics().gauge(name).set(0)
        jax.make_jaxpr(lambda p, t: sala.loss_fn(p, t, CFG))(
            params, sala.fake_batch(CFG, 1, T))
        assert (gauge("topk_attn_dense_calls"), gauge("topk_attn_calls")) \
            == (dense, chosen), T
        assert gauge("lin_attn_calls") == 3


def test_bf16_program_stays_near_the_float32_reference():
    """bf16 weights and activations, float32 inside the linear attention.
    The distance is bf16's rounding of every activation through five layers
    and the sets it flips: a per cent, not the float32 test's 1e-5."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, remat=True,
                              loss_chunk=48)
    params = sala.stacked_init_params(cfg, KEY)
    tokens = sala.fake_batch(cfg, 2, 128, seed=1)
    loss, grads = loss_and_grads(params, tokens, cfg)
    want_loss, want = jax.value_and_grad(ref.loss)(params, tokens, hyper(cfg))
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-3)
    for name in ("tok_emb", "lm_head", "norm_f"):
        assert rel_l2(grads[name], want[name]) < 0.05, name
    assert grads["vec1"]["o_norm"].dtype == jnp.float32
    assert grads["run1"]["wq"].dtype == jnp.bfloat16


def test_a_doubled_micro_batch_shows():
    """What the cell's check compares: a batch that repeats a sequence is
    the reference's weighted loss, and no other weighting."""
    params = sala.stacked_init_params(CFG, KEY)
    unique = sala.fake_batch(CFG, 2, 64, seed=3)
    batch = unique[jnp.asarray([0, 1, 1, 1])]
    want = ref.loss(params, unique, hyper(CFG), ref.identity,
                    jnp.asarray([0.25, 0.75]))
    assert float(sala.loss_fn(params, batch, CFG)) \
        == pytest.approx(float(want), rel=1e-5)
    even = ref.loss(params, unique, hyper(CFG))
    assert abs(float(even) - float(want)) > 1e-4


def test_the_blocks_token_wise_parts_in_chunks_change_nothing(monkeypatch):
    """The chunk is chosen from the shapes; forced small, eight chunks of 16
    positions give the whole sequence's loss and gradients (the rotary
    embedding at each chunk's own positions), and no chunk is as wide as the
    sequence."""
    cfg = dataclasses.replace(CFG, remat=True)
    params = uneven_gains(sala.stacked_init_params(cfg, KEY, std=0.05))
    tokens = sala.fake_batch(cfg, 2, 128, seed=4)
    whole = jax.value_and_grad(sala.loss_fn)(params, tokens, cfg)
    monkeypatch.setattr(sala, "_CHUNK_ELEMENTS",
                        2 * 16 * cfg.intermediate_size)
    assert sala.tokens_a_chunk(2, 128, cfg.intermediate_size) == 16
    loss, grads = jax.value_and_grad(sala.loss_fn)(params, tokens, cfg)
    assert float(loss) == pytest.approx(float(whole[0]), rel=1e-6)
    leaves_close(grads, whole[1], 1e-5)
    text = str(jax.make_jaxpr(lambda p, t: sala.loss_fn(p, t, cfg))(
        params, tokens))
    assert f"f32[2,128,{cfg.intermediate_size}]" not in text
    assert f"f32[2,16,{cfg.intermediate_size}]" in text


def test_the_chunk_follows_the_shapes():
    assert sala.tokens_a_chunk(1, 32768, 16384) == 2048     # the cell's
    assert sala.tokens_a_chunk(1, 8192, 8192) == 4096
    assert sala.tokens_a_chunk(2, 128, 96) == 128           # one chunk
    assert sala.tokens_a_chunk(1, 3 * 1031, 2 ** 14) == 1031


# -- the linear-attention kernels against the sequential recurrence -----------

def lightning_inputs(B, T, H, D, dtype=jnp.float32, seed=0):
    return lightning_check.make_inputs((B, T, H, D), dtype, seed)


def kernels(chunk, **how):
    def run(q, k, v, log_decay, do):
        return (la.forward(q, k, v, log_decay, chunk=chunk, **how),) \
            + la.backward(q, k, v, log_decay, do, chunk=chunk, **how)
    return run


LAM = jnp.exp(jnp.asarray(sala.log_decays(CFG, 1)))          # 0.44 to 0.92


# 40 positions in chunks of 16 (the last one padded), 64 in chunks of 16 and
# in one chunk, one position alone.
@pytest.mark.parametrize("T,chunk", [(40, 16), (64, 16), (64, 64), (1, 8)])
def test_kernels_match_the_sequential_recurrence(T, chunk):
    inputs = lightning_inputs(2, T, 4, 16)
    read = lightning_check.against_sequential(kernels(chunk), inputs, LAM)
    assert max(read.values()) < 2e-6, read


def test_the_custom_vjp_is_the_kernels_backward():
    q, k, v, do = (x.reshape(2, 40, 64)
                   for x in lightning_inputs(2, 40, 4, 16, seed=2))
    ld = jnp.log(LAM)
    out, vjp = jax.vjp(lambda *a: la.lightning_attention(*a, ld, chunk=16),
                       q, k, v)
    want = kernels(16)(q, k, v, ld, do)
    for got, w in zip((out,) + vjp(do), want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
    # The decay is data: it takes a gradient of zeros, not an error.
    grad = jax.grad(lambda ld: la.lightning_attention(q, k, v, ld,
                                                      chunk=16).sum())(ld)
    assert not np.asarray(grad).any()


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_padded():
    q, k, v, _ = (x.reshape(1, 37, 64)
                  for x in lightning_inputs(1, 37, 4, 16, seed=3))
    ld = jnp.log(LAM)
    got = la.lightning_attention(q, k, v, ld, chunk=16)
    more = la.lightning_attention(
        *(jnp.pad(x, ((0, 0), (0, 11), (0, 0))) for x in (q, k, v)), ld,
        chunk=16)
    assert got.shape == (1, 37, 64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(more[:, :37]))


def test_state_and_accumulation_are_float32_under_bf16_operands():
    """bf16 operands, results asked for in float32: the kernels stand 1e-5
    from the float32 recurrence on the same (bf16-valued) operands, where
    one rounding of a float32 factor to bf16 would read 2e-3."""
    inputs = lightning_inputs(1, 256, 4, 16, jnp.bfloat16, seed=5)
    read = lightning_check.against_sequential(
        kernels(64, out_dtype=jnp.float32), inputs, LAM)
    assert max(read.values()) < 2e-5, read


def test_a_bfloat16_state_would_fail_the_kernels_comparison():
    """The control of ``kernels/lightning_check.py``: the same kernels with
    the carried state through bf16 read a hundred times the sound ones."""
    inputs = lightning_inputs(1, 256, 4, 16, jnp.bfloat16, seed=5)
    read = lightning_check.against_sequential(
        kernels(64, out_dtype=jnp.float32, state_dtype=jnp.bfloat16),
        inputs, LAM)
    assert min(read.values()) > 5e-4, read


def test_the_kernels_refuse_shapes_they_cannot_tile():
    q = jnp.zeros((1, 16, 64))
    with pytest.raises(ValueError, match="lightning_attention"):
        la.lightning_attention(q, q, q, jnp.zeros((3,)))     # 64 % 3
    with pytest.raises(ValueError, match="lightning_attention"):
        la.lightning_attention(q, q[:, :8], q, jnp.zeros((4,)))
    with pytest.raises(ValueError, match="lightning_attention"):
        la.lightning_attention(q, q, q, jnp.zeros((4,)), chunk=12)


def test_the_decay_slopes_are_the_published_layers():
    """Lightning Attention-2's slopes with MiniMax-01's per-layer factor at
    the layer's published index, whatever is held."""
    cfg = dataclasses.replace(sala.CONFIGS["9b"], first_layer=4,
                              mixer_types=(sala.LIGHTNING,) * 2)
    got = sala.log_decays(cfg, 1)                            # published 5
    h = np.arange(1, 33)
    np.testing.assert_allclose(
        got, -(2.0 ** (-8 * h / 32)) * (1 - 5 / 31 + 1e-5), rtol=1e-6)
    np.testing.assert_allclose(
        np.exp(got), np.asarray(ref.decays(hyper(cfg), 5)), rtol=1e-6)


# -- the sparse layer: the choice and the chosen blocks' kernels --------------

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


# -- the structure and the walks ----------------------------------------------

def test_the_published_order_and_its_runs():
    full = sala.CONFIGS["9b"]
    assert full.num_hidden_layers == 32
    assert [i for i, k in enumerate(full.mixer_types) if k == sala.SPARSE] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    assert full.runs[:3] == ((sala.SPARSE, 0, 1), (sala.LIGHTNING, 1, 8),
                             (sala.SPARSE, 9, 1))
    assert full.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert CFG.runs == ((sala.SPARSE, 0, 1), (sala.LIGHTNING, 1, 2),
                        (sala.SPARSE, 3, 1), (sala.LIGHTNING, 4, 1))


def test_the_stacks_follow_the_runs():
    params = sala.stacked_init_params(CFG, KEY)
    flat = sala.init_params(CFG, KEY)
    assert set(params) == {"tok_emb", "lm_head", "norm_f"} | {
        f"{g}{r}" for g in ("run", "vec") for r in range(4)}
    assert params["run1"]["wq"].shape == (2, 64, 64)
    assert params["run0"]["wk"].shape == (1, 64, 32)         # 2 of 4 heads
    assert set(params["vec1"]) == {"input_ln", "ff_ln", "q_norm", "k_norm",
                                   "o_norm"}
    assert "o_norm" not in params["vec0"]
    np.testing.assert_array_equal(np.asarray(params["run1"]["wo"][1]),
                                  np.asarray(flat["l2"]["wo"]))
    np.testing.assert_array_equal(
        np.asarray(sala.run_blocks(params, 2)["w_up"][0]),
        np.asarray(flat["l3"]["w_up"]))


def test_the_reference_refuses_weights_out_of_order():
    params = to_reference(sala.init_params(CFG, KEY), CFG)
    params["layers"][0], params["layers"][1] = \
        params["layers"][1], params["layers"][0]
    with pytest.raises(ValueError, match="mixer_types says"):
        ref.hidden(params, jnp.zeros((16,), jnp.int32), hyper(CFG))


def ga_step(cfg, micro, **more):
    tx = make_optimizer(OPT)

    def loss(p, t):
        return sala.loss_fn(p, t, cfg)

    def apply_fn(p, s, g):
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    return build_ga_step(lambda p, t: jax.value_and_grad(loss)(p, t),
                         apply_fn, micro, **more, loss_fn=loss), tx


def nbytes(tree):
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


def test_every_walk_accumulates_in_the_layer_loop_and_counts_its_kernels():
    """Two micro batches: all four stacks' leaves are found by the sink
    (embedding, head and final norm are outside the blocks), the linear
    attention's forward runs twice a layer (the walk and its recomputation)
    and the block top-k attention's once: each sparse layer hands the walk
    its sets and its forward kernel's ``(o, lse)``."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=48)
    params = sala.stacked_init_params(cfg, KEY)
    step, tx = ga_step(cfg, 2)
    tokens = sala.fake_batch(cfg, 2, 128)
    jax.make_jaxpr(step)(params, tx.init(params), tokens)
    gauge = lambda n: metrics().gauge(n).value          # noqa: E731
    stacks = {k: v for k, v in params.items()
              if k not in ("tok_emb", "lm_head", "norm_f")}
    assert gauge("ga_fused_bytes") == nbytes(stacks)
    assert gauge("ga_unfused_bytes") == nbytes(params) - nbytes(stacks)
    assert gauge("lin_attn_calls") == 2 * 3
    assert gauge("topk_attn_calls") == 2 * 1
    assert gauge("topk_attn_dense_calls") == 0
    assert gauge("topk_attn_keys_per_query") == pytest.approx(
        bt.mean_keys_per_query(128, GEO))
    assert gauge("ssm_scan_calls") == 0
    # A micro batch of one sequence, two sparse layers, two hand-overs each:
    # o float32 [128, 4, 16] with lse [2, 2, 128], and the sets [2, 128, 4].
    assert gauge("attn_kept_calls") == 2 * 2
    assert gauge("attn_kept_bytes") == 2 * 4 * 128 * (4 * 16 + 4 + 2 * 4)


def equations(fn, *args):
    """Every equation of ``fn``'s jaxpr, nested jaxprs included (a loop's
    body once)."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for v in eqn.params.values():
                for j in v if isinstance(v, (list, tuple)) else (v,):
                    j = getattr(j, "jaxpr", j)
                    if hasattr(j, "eqns"):
                        yield from walk(j)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def kernel_counts(fn, *args):
    return dict(collections.Counter(
        e.params["name"] for e in equations(fn, *args)
        if e.primitive.name == "pallas_call"))


def choices(fn, *args):
    """How often ``select_blocks`` is in ``fn``'s program: the one place of
    the model that reads a float's bits as an integer
    (``block_topk_attention._ordered_bits``)."""
    return sum(e.primitive.name == "bitcast_convert_type"
               for e in equations(fn, *args))


def whole_remat(monkeypatch):
    """Every block under ``nothing_kept``: all of it rematerialised, as
    before a walk kept anything."""
    block = sala.block

    def whole(*args, **kwargs):
        with fa.nothing_kept():
            return block(*args, **kwargs)
    monkeypatch.setattr(sala, "block", whole)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_walked_sparse_layer_keeps_its_forward_and_its_choice(dtype,
                                                                monkeypatch):
    """Two micro batches over the stacked model: each sparse layer's forward
    kernel and choice are in the step once (the walk; its recomputation
    takes them back) where a block rematerialised whole holds them twice,
    the other kernels as often as there, and two steps leave loss,
    parameters and optimizer state bit for bit the same."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=48, dtype=dtype)
    params = sala.stacked_init_params(cfg, KEY, std=0.05)
    tokens = sala.fake_batch(cfg, 2, 128, seed=3)
    step, tx = ga_step(cfg, 2)
    state = tx.init(params)
    kept = kernel_counts(step, params, state, tokens)
    kept_choices = choices(step, params, state, tokens)
    got = (params, state)
    for _ in range(2):
        loss_got, *got = jax.jit(step)(*got, tokens)

    whole_remat(monkeypatch)
    step, _ = ga_step(cfg, 2)
    whole = kernel_counts(step, params, state, tokens)
    assert metrics().gauge("attn_kept_calls").value == 0
    assert metrics().gauge("topk_attn_calls").value == 2 * 2
    want = (params, state)
    for _ in range(2):
        loss_want, *want = jax.jit(step)(*want, tokens)

    sparse_layers = cfg.mixer_types.count(sala.SPARSE)
    assert kept.pop("tepdist_topk_attn_fwd") == sparse_layers
    assert whole.pop("tepdist_topk_attn_fwd") == 2 * sparse_layers
    assert kept == whole and kept["tepdist_topk_attn_bwd"] == sparse_layers
    assert kept_choices == sparse_layers
    assert choices(step, params, state, tokens) == 2 * sparse_layers
    assert float(loss_got) == float(loss_want)
    assert np.isfinite(float(loss_got))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("stacked,remat,micro", [
    (True, False, 2),       # a plain scan, nothing rematerialised
    (False, True, 2),       # l0.. blocks, each under jax.checkpoint
    (True, True, 1)])       # one micro batch: scan_blocks' plain scan
def test_outside_a_walk_nothing_is_handed_over(stacked, remat, micro,
                                               monkeypatch):
    """Where no walk keeps anything the step is the program of a model with
    no hand-over in it, equation for equation, and the gauges read 0."""
    cfg = dataclasses.replace(CFG, remat=remat, loss_chunk=48)
    init = sala.stacked_init_params if stacked else sala.init_params
    params = init(cfg, KEY)
    tokens = sala.fake_batch(cfg, 2, 128)
    step, tx = ga_step(cfg, micro)
    state = tx.init(params)
    here = [str(e.primitive) for e in equations(step, params, state, tokens)]
    assert metrics().gauge("attn_kept_calls").value == 0
    assert metrics().gauge("attn_kept_bytes").value == 0
    # Two sparse layers; a block under ``jax.checkpoint`` holds its forward
    # kernel again in the backward pass.
    assert kernel_counts(step, params, state, tokens)[
        "tepdist_topk_attn_fwd"] == 2 * (2 if remat else 1)
    for module in (bt, fa):
        monkeypatch.setattr(module, "hand_over",
                            lambda attend: attend(None))
    step, _ = ga_step(cfg, micro)
    assert [str(e.primitive) for e in equations(
        step, params, state, tokens)] == here
    assert "optimization_barrier" not in here


@pytest.mark.parametrize("stacked", [False, True])
def test_two_micro_batches_leave_the_state_one_batch_of_two_leaves(stacked):
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=48)
    init = sala.stacked_init_params if stacked else sala.init_params
    params = init(cfg, KEY)
    tokens = sala.fake_batch(cfg, 2, 128, seed=2)
    results = []
    for micro in (1, 2):
        step, tx = ga_step(cfg, micro)
        state = (params, tx.init(params))
        for _ in range(2):
            loss, *state = jax.jit(step)(*state, tokens)
        results.append((float(loss), state))
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-5)
    leaves_close(results[1][1][0], results[0][1][0], 1e-4)
    # The moments are bf16: where the two sums' float32 noise crosses a
    # rounding boundary an element moves by 2**-8 of itself.
    leaves_close(results[1][1][1], results[0][1][1], 1e-3)


def test_the_mixers_parts_carry_their_scopes():
    cfg = dataclasses.replace(CFG, remat=True)
    params = sala.stacked_init_params(cfg, KEY)
    tokens = sala.fake_batch(cfg, 1, 128)
    text = jax.jit(sala.loss_fn, static_argnums=2).lower(
        params, tokens, cfg).as_text(debug_info=True)
    for scope in ("mixer_in", "lin_attn", "topk_select", "topk_attend",
                  "mixer_out_mlp", "rope_plain", "tepdist_lightning_fwd",
                  "tepdist_topk_attn_fwd"):
        assert scope in text, scope


def test_a_planned_step_with_two_micro_batches_runs(devices):
    """``plan_training`` with the other models' entry point: 2 micro batches
    accumulated in one program, the five kernels inside the accumulation
    scan and the layer walks."""
    from tepdist_tpu.train import plan_training
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=48)
    params = sala.stacked_init_params(cfg, KEY)
    tokens = sala.fake_batch(cfg, 2, 128)
    plan = plan_training(lambda p, t: sala.loss_fn(p, t, cfg),
                         make_optimizer(OPT), params, tokens,
                         devices=devices[:1], explore=False,
                         num_micro_batches=2)
    want = float(sala.loss_fn(params, tokens, cfg))
    assert plan.step(tokens) == pytest.approx(want, rel=1e-5)
    text = plan.compiled_step_text()
    assert "tepdist_train_step" in text
    assert np.isfinite(plan.step(tokens))
