"""Ring attention + Ulysses tests: sharded sequence-parallel attention must
match full attention exactly (LSE merging correctness), causal and full."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tepdist_tpu.ops.ring_attention import reference_attention, ring_attention
from tepdist_tpu.ops.ulysses import ulysses_attention


@pytest.fixture()
def seq_mesh(devices):
    return Mesh(np.array(devices[:4]), axis_names=("seq",))


def _qkv(B=2, H=4, T=64, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, T, D))
    k = jax.random.normal(ks[1], (B, H, T, D))
    v = jax.random.normal(ks[2], (B, H, T, D))
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(seq_mesh, causal):
    q, k, v = _qkv()
    sh = NamedSharding(seq_mesh, P(None, None, "seq", None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, seq_mesh, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # Output keeps the sequence sharding. Older jax trims trailing
    # replicated dims from the spec, so compare padded tuples.
    spec = tuple(out.sharding.spec)
    assert spec + (None,) * (4 - len(spec)) == (None, None, "seq", None)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_reference(seq_mesh, causal):
    q, k, v = _qkv()
    sh = NamedSharding(seq_mesh, P(None, None, "seq", None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    out = ulysses_attention(qs, ks, vs, seq_mesh, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grad_flows(seq_mesh):
    q, k, v = _qkv(T=32)
    sh = NamedSharding(seq_mesh, P(None, None, "seq", None))

    def loss_sharded(q, k, v):
        return ring_attention(
            jax.device_put(q, sh), jax.device_put(k, sh),
            jax.device_put(v, sh), seq_mesh).astype(jnp.float32).sum()

    def loss_ref(q, k, v):
        return reference_attention(q, k, v).astype(jnp.float32).sum()

    # Each side one jit: bare, the ring's scan inside the shard_map is
    # dispatched and compiled a primitive at a time.
    g1 = jax.jit(jax.grad(loss_sharded))(q, k, v)
    g2 = jax.jit(jax.grad(loss_ref))(q, k, v)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-4)


def test_gpt2_with_ring_attention(devices):
    """GPT-2 forward with ring-attention inner must match einsum attention."""
    from tepdist_tpu.models import gpt2

    cfg = gpt2.CONFIGS["test"]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, 2, 32)
    mesh = Mesh(np.array(devices[:4]), axis_names=("seq",))

    def attn_impl(q, k, v):
        return ring_attention(q, k, v, mesh, causal=True)

    ref = jax.jit(lambda p: gpt2.loss_fn(p, tokens, cfg))(params)
    got = jax.jit(lambda p: gpt2.loss_fn(
        p, tokens, cfg, attn_impl=attn_impl))(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4)


def test_ulysses_head_divisibility(seq_mesh):
    q, k, v = _qkv(H=3)
    with pytest.raises(ValueError):
        ulysses_attention(q, k, v, seq_mesh)


def test_flash_attention_kernel_matches_reference():
    from tepdist_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(B=1, H=2, T=64, D=16)
    for causal in (True, False):
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                              interpret=True)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_ulysses_with_flash_inner(seq_mesh):
    from tepdist_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(T=64)
    sh = NamedSharding(seq_mesh, P(None, None, "seq", None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    out = ulysses_attention(
        qs, ks, vs, seq_mesh, causal=True,
        inner=lambda a, b, c: flash_attention(a, b, c, causal=True,
                                              block_q=16, block_k=16,
                                              interpret=True))
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gpt2_training_with_ring_attention_matches_dense(devices):
    """Full GPT-2 training steps with seq-parallel ring attention must track
    dense-attention training exactly."""
    import optax
    from tepdist_tpu.models import gpt2

    cfg = gpt2.CONFIGS["test"]
    mesh = Mesh(np.array(devices[:4]), axis_names=("seq",))

    def attn_impl(q, k, v):
        return ring_attention(q, k, v, mesh, causal=True)

    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, 2, 32)
    tx = optax.sgd(0.05)

    def make_step(impl):
        def step(p, o, t):
            l, g = jax.value_and_grad(
                lambda p: gpt2.loss_fn(p, t, cfg, attn_impl=impl))(p)
            u, o = tx.update(g, o, p)
            return l, optax.apply_updates(p, u), o
        return jax.jit(step)

    ring_step = make_step(attn_impl)
    dense_step = make_step(None)
    p1, o1 = params, tx.init(params)
    p2, o2 = params, tx.init(params)
    for _ in range(3):
        l1, p1, o1 = ring_step(p1, o1, tokens)
        l2, p2, o2 = dense_step(p2, o2, tokens)
        np.testing.assert_allclose(float(l1), float(l2), rtol=2e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5),
        jax.device_get(p1), jax.device_get(p2))


def test_device_prefetcher():
    from tepdist_tpu.data import DevicePrefetcher, fake_input_iterator

    def batch_fn(i):
        return {"x": np.full((4, 4), float(i), np.float32)}

    it = fake_input_iterator(batch_fn, reuse_first=False)
    pf = DevicePrefetcher(it, depth=2)
    got = [next(pf) for _ in range(3)]
    for i, b in enumerate(got):
        assert isinstance(b["x"], jax.Array)
        np.testing.assert_array_equal(np.asarray(b["x"]),
                                      np.full((4, 4), float(i)))

    # Finite iterator terminates cleanly.
    pf2 = DevicePrefetcher(iter([{"x": np.zeros((2,), np.float32)}]))
    assert next(pf2) is not None
    with pytest.raises(StopIteration):
        next(pf2)


def test_flash_attention_backward_matches_reference():
    """custom_vjp backward (dq/dk/dv via blockwise recompute from saved
    LSE) equals autodiff through the einsum reference."""
    import math

    from tepdist_tpu.ops.pallas.flash_attention import flash_attention

    def ref(q, k, v, causal):
        T = q.shape[2]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
        s = s / math.sqrt(q.shape[-1])
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e9)
        p = jax.nn.softmax(s, -1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    key = jax.random.PRNGKey(3)
    for causal in (True, False):
        q, k, v, do = (jax.random.normal(jax.random.fold_in(key, i),
                                         (2, 3, 128, 32), jnp.float32)
                       for i in range(4))
        g = jax.grad(lambda q, k, v: jnp.vdot(
            flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                            interpret=True), do), (0, 1, 2))(q, k, v)
        r = jax.grad(lambda q, k, v: jnp.vdot(ref(q, k, v, causal), do),
                     (0, 1, 2))(q, k, v)
        for a, b in zip(g, r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-4, rtol=1e-3)


def _dense_f32_attention(q, k, v, causal, window=None):
    """(o, lse) of plain attention in float32, whatever the inputs' dtype;
    k and v may have fewer heads than q (each read by a group of q's), and
    under ``window`` key j is visible to query i iff ``0 <= i - j <
    window``."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = (jnp.repeat(x, q.shape[1] // x.shape[1], axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest")
    s = s / np.sqrt(q.shape[-1])
    if causal:
        ahead = np.arange(q.shape[2])[:, None] - np.arange(q.shape[2])
        seen = (ahead >= 0) if window is None else \
            (ahead >= 0) & (ahead < window)
        s = jnp.where(seen, s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest"), lse


def _gpt2_einsum_attention(q, k, v, causal):
    """models/gpt2.py's own plain attention (its `attention`, after the
    head split), which rounds the logits and the probabilities to the
    activations' dtype."""
    T = q.shape[2]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    logits = logits.astype(jnp.float32)
    if causal:
        logits = jnp.where(jnp.tril(jnp.ones((T, T), bool)), logits, -1e9)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _rel_l2(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# (T, block_q, block_k): one block, several blocks, block_q != block_k
# either way round; two and eight blocks (the backward kernel's dQ^T
# accumulator is zeroed at a head's first key block and written at its last:
# one step, neighbours, far apart).
_FLASH_TILINGS = [(64, 64, 64), (128, 32, 32), (128, 64, 32), (128, 32, 64),
                  (128, 64, 64), (256, 32, 32)]


def _bf16_case(T, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v, do = (jax.random.normal(kk, (2, 3, T, 32), jnp.float32)
                   .astype(jnp.bfloat16) for kk in ks[:4])
    dlse = jax.random.normal(ks[4], (2, 3, T), jnp.float32)
    return q, k, v, do, dlse


@pytest.mark.parametrize("tiling", _FLASH_TILINGS,
                         ids=lambda t: "T%d-bq%d-bk%d" % t)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_bf16_no_worse_than_einsum(causal, tiling):
    """On bf16 inputs the kernels feed the MXU bf16 operands (P and dS
    rounded for the second matmul of each pair, float32 everything else):
    the forward and the three gradients stay as close to float32 dense
    attention of the same inputs as the model's own bf16 einsum attention
    and its autodiff do, within a factor of 1.5."""
    from tepdist_tpu.ops.pallas.flash_attention import flash_attention

    T, bq, bk = tiling
    q, k, v, do, _ = _bf16_case(T)

    def outputs(attend, *args):
        o, vjp = jax.vjp(attend, *args)
        return (o,) + vjp(do.astype(o.dtype))

    want = outputs(lambda q, k, v: _dense_f32_attention(q, k, v, causal)[0],
                   *(x.astype(jnp.float32) for x in (q, k, v)))
    einsum = outputs(lambda q, k, v: _gpt2_einsum_attention(q, k, v, causal),
                     q, k, v)
    flash = outputs(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True),
        q, k, v)
    for name, f, e, w in zip(("o", "dq", "dk", "dv"), flash, einsum, want):
        assert f.dtype == jnp.bfloat16, name
        assert _rel_l2(f, w) <= 1.5 * _rel_l2(e, w), name


@pytest.mark.parametrize("tiling", _FLASH_TILINGS,
                         ids=lambda t: "T%d-bq%d-bk%d" % t)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_with_lse_bf16(causal, tiling):
    """The log-sum-exp stays float32 on bf16 inputs, keeps its [B, H, T]
    shape, and its cotangent folds into the backward kernels' delta."""
    from tepdist_tpu.ops.pallas.flash_attention import (
        flash_attention_with_lse)

    T, bq, bk = tiling
    q, k, v, do, dlse = _bf16_case(T)
    (o, lse), vjp = jax.vjp(lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True),
        q, k, v)
    got = vjp((do, dlse))
    (ro, rlse), rvjp = jax.vjp(
        lambda q, k, v: _dense_f32_attention(q, k, v, causal),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    want = rvjp((do.astype(jnp.float32), dlse))
    assert lse.dtype == jnp.float32 and lse.shape == q.shape[:3]
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse),
                               rtol=1e-5, atol=1e-5)
    assert _rel_l2(o, ro) < 5e-3
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        assert _rel_l2(g, w) < 1e-2


# (T, block_q, block_k, window, query heads, key/value heads, causal): a
# window the tiles divide (far edge and diagonal at fixed places) under 8
# query heads a key/value head, one they do not divide, unequal tiles under a
# window, 20 heads over 1 without one over eight key blocks, no causal mask
# under grouped heads, and one and two key blocks.
_FUSED_BACKWARD_CASES = [
    (256, 64, 64, 128, 8, 1, True),
    (256, 64, 64, 100, 4, 2, True),
    (256, 32, 64, 96, 4, 2, True),
    (256, 64, 32, 64, 2, 2, True),
    (256, 32, 32, None, 20, 1, True),
    (128, 32, 32, None, 8, 1, False),
    (64, 64, 64, None, 4, 2, True),
    (128, 64, 64, 64, 4, 1, True),
]


@pytest.mark.parametrize(
    "case", _FUSED_BACKWARD_CASES,
    ids=lambda c: "T%d-bq%d-bk%d-w%s-h%dkv%d-c%d" % c)
def test_flash_attention_one_backward_kernel_bf16(case):
    """dq, dk and dv of the one backward kernel (each (key block, query
    block) pair's P^T and dS^T made once and used for all three) against
    dense float32 attention of the same bf16 inputs, under the bound the
    log-sum-exp test holds its gradients to; the program of the gradient
    holds the forward and that one kernel."""
    from tepdist_tpu.ops.pallas.flash_attention import flash_attention

    T, bq, bk, window, H, Hkv, causal = case
    ks = jax.random.split(jax.random.PRNGKey(T + H), 4)
    q, do = (jax.random.normal(kk, (1, H, T, 32), jnp.float32)
             .astype(jnp.bfloat16) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (1, Hkv, T, 32), jnp.float32)
            .astype(jnp.bfloat16) for kk in ks[2:])

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=bq,
                               block_k=bk, window=window, interpret=True)

    o, vjp = jax.vjp(flash, q, k, v)
    got = vjp(do)
    ro, rvjp = jax.vjp(
        lambda q, k, v: _dense_f32_attention(q, k, v, causal, window)[0],
        *(x.astype(jnp.float32) for x in (q, k, v)))
    assert _rel_l2(o, ro) < 5e-3
    for name, g, w, x in zip(("dq", "dk", "dv"), got,
                             rvjp(do.astype(jnp.float32)), (q, k, v)):
        assert g.dtype == jnp.bfloat16 and g.shape == x.shape, name
        assert _rel_l2(g, w) < 1e-2, name
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(flash, *a)[1](do))(q, k, v))
    assert text.count("tepdist_flash_dkv__") == 1
    assert "tepdist_flash_dq__" not in text


def test_gpt2_flash_config_trains_like_einsum():
    """GPT2Config(attn='flash', remat=True) end-to-end loss/grad parity
    with the einsum model (the benched big-model path)."""
    import dataclasses

    from tepdist_tpu.models import gpt2

    cfg = gpt2.CONFIGS["test"]
    cfgf = dataclasses.replace(cfg, attn="flash", remat=True)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    toks = gpt2.fake_batch(cfg, 4, 32)
    l1, g1 = jax.jit(jax.value_and_grad(
        lambda p: gpt2.loss_fn(p, toks, cfg)))(params)
    l2, g2 = jax.jit(jax.value_and_grad(
        lambda p: gpt2.loss_fn(p, toks, cfgf)))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-3)


def test_gpt2_stacked_scan_matches_unrolled():
    """Scan-over-layers stacked-param form == per-layer unrolled form."""
    from tepdist_tpu.models import gpt2

    cfg = gpt2.CONFIGS["test"]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(1))
    stacked = {k: params[k] for k in ("wte", "wpe", "ln_f_g", "ln_f_b")}
    stacked["blocks"] = gpt2.stack_block_params(params, cfg)
    toks = gpt2.fake_batch(cfg, 2, 16)
    l1 = jax.jit(lambda p: gpt2.loss_fn(p, toks, cfg))(params)
    l2 = jax.jit(lambda p: gpt2.loss_fn_stacked(p, toks, cfg))(stacked)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
