"""Jamba (AI21): the program against the plain float32 reference at a tiny
preset, the selective-scan kernel (interpret mode: its own code) against the
sequential scan, the order of the layers from the period rule, the walks of
unequal shape under gradient accumulation, and the scan's gauges and scopes."""

import dataclasses
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import jamba as ref
from tepdist_tpu.models import jamba
from tepdist_tpu.ops.pallas import causal_conv as conv
from tepdist_tpu.ops.pallas import selective_scan as ssm
from tepdist_tpu.optim import make_optimizer
from tepdist_tpu.parallel.sync_free import build_ga_step
from tepdist_tpu.telemetry import metrics

CFG = jamba.CONFIGS["test"]          # Mamba x 2, attention, Mamba x 2;
#                                      128 channels of 8 states, chunks of 16
KEY = jax.random.PRNGKey(0)
OPT = {"name": "adamw_bf16", "learning_rate": 1e-3}
loss_and_grads = jax.jit(jax.value_and_grad(jamba.loss_fn), static_argnums=2)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def hyper(cfg):
    return ref.Hyper(
        n_head=cfg.num_attention_heads, n_kv_head=cfg.num_key_value_heads,
        attn_layer_period=cfg.attn_layer_period,
        attn_layer_offset=cfg.attn_layer_offset, d_state=cfg.mamba_d_state,
        dt_rank=cfg.mamba_dt_rank, eps=cfg.rms_norm_eps)


def to_reference(params, cfg):
    """The reference's view of either layout of the program's parameters."""
    if "l0" not in params:
        return params
    out = {k: params[k] for k in ("tok_emb", "norm_f")}
    out["layers"] = [params[f"l{i}"] for i in range(cfg.num_hidden_layers)]
    return out


def rel_l2(got, want) -> float:
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def leaves_close(got, want, limit):
    want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        assert np.linalg.norm(np.asarray(want[path], np.float64)) > 0, path
        assert rel_l2(g, want[path]) < limit, jax.tree_util.keystr(path)


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("stacked,remat", [(False, False), (True, False),
                                           (True, True)])
def test_logits_loss_and_every_gradient_match_the_reference(stacked, remat):
    cfg = dataclasses.replace(CFG, remat=remat, loss_chunk=16 * remat)
    init = jamba.stacked_init_params if stacked else jamba.init_params
    params = init(cfg, KEY)
    tokens = jamba.fake_batch(cfg, 2, 40, seed=1)      # 2.5 chunks of 16
    as_ref, hp = to_reference(params, cfg), hyper(cfg)
    logits = ref.logits(as_ref, tokens[:, :-1], hp)
    np.testing.assert_allclose(
        np.asarray(jamba.forward(params, tokens[:, :-1], cfg)),
        np.asarray(logits), rtol=0, atol=2e-5 * float(jnp.abs(logits).max()))
    loss, grads = loss_and_grads(params, tokens, cfg)
    want_loss, want = jax.value_and_grad(ref.loss)(as_ref, tokens, hp)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    leaves_close(to_reference(grads, cfg), want, 2e-5)


def test_bf16_program_stays_near_the_float32_reference():
    """bf16 weights and activations, float32 inside the scan. The distance
    is bf16's rounding of every activation (2**-9 a rounding) through five
    layers, so a per cent, not the float32 test's 1e-5; a scan that lost
    its float32 state would not be told from it here, nor by the cell's
    step check on the chip (PERF.md section 2): the kernel's own tests
    below hold it."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, remat=True,
                              loss_chunk=16)
    params = jamba.stacked_init_params(cfg, KEY)
    tokens = jamba.fake_batch(cfg, 2, 40, seed=1)
    loss, grads = loss_and_grads(params, tokens, cfg)
    want_loss, want = jax.value_and_grad(ref.loss)(params, tokens, hyper(cfg))
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-3)
    for name in ("tok_emb", "norm_f"):
        assert rel_l2(grads[name], want[name]) < 0.03, name
    assert grads["decay0"]["A_log"].dtype == jnp.float32
    assert grads["run0"]["in_proj"].dtype == jnp.bfloat16


def test_a_doubled_micro_batch_shows():
    """What the cell's check compares: a batch that repeats a sequence is
    the reference's weighted loss, and no other weighting."""
    params = jamba.stacked_init_params(CFG, KEY)
    unique = jamba.fake_batch(CFG, 2, 32, seed=3)
    batch = unique[jnp.asarray([0, 1, 1, 1])]
    want = ref.loss(params, unique, hyper(CFG), ref.identity,
                    jnp.asarray([0.25, 0.75]))
    assert float(jamba.loss_fn(params, batch, CFG)) \
        == pytest.approx(float(want), rel=1e-5)
    even = ref.loss(params, unique, hyper(CFG))
    assert abs(float(even) - float(want)) > 1e-4


# -- the kernel against the sequential scan ----------------------------------

def scan_inputs(batch, T, Di, N, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    shape = (batch, T, Di)
    delta = jax.nn.softplus(jax.random.normal(ks[1], shape) - 2.0)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (Di, N)))
    return (jax.random.normal(ks[0], shape).astype(dtype), delta, A,
            jax.random.normal(ks[3], (batch, T, N)).astype(dtype),
            jax.random.normal(ks[4], (batch, T, N)).astype(dtype),
            jax.random.normal(ks[5], (Di,)),
            jax.random.normal(ks[6], shape).astype(dtype)), \
        jax.random.normal(ks[7], shape)


def sequential(c, delta, A, B, C, D, z):
    f32 = jnp.float32
    c, delta, B, C, z = (x.astype(f32) for x in (c, delta, B, C, z))
    y = jnp.stack([ref.recurrence(c[i], delta[i], A, B[i], C[i])
                   for i in range(c.shape[0])])
    return (y + D * c) * jax.nn.silu(z)


NAMES = ("c", "delta", "A", "B", "C", "D", "z")


# Several whole chunks; a length the chunk does not divide (the last chunk
# is padded with steps that leave the state alone); two channel blocks of
# two lane tiles each; one chunk longer than the sequence.
@pytest.mark.parametrize("T,Di,N,chunk,block_d", [
    (48, 256, 16, 16, 128), (37, 128, 16, 16, 128), (40, 512, 8, 8, 256),
    (12, 128, 8, 16, 128)])
def test_kernel_matches_the_sequential_scan(T, Di, N, chunk, block_d):
    args, w = scan_inputs(2, T, Di, N)

    def through(scan):
        return jax.value_and_grad(
            lambda *a: jnp.sum(scan(*a) * w), argnums=tuple(range(7)))(*args)

    got_out = ssm.selective_scan(*args, chunk=chunk, block_d=block_d)
    want_out = sequential(*args)
    assert rel_l2(got_out, want_out) < 1e-6
    (_, got), (_, want) = through(lambda *a: ssm.selective_scan(
        *a, chunk=chunk, block_d=block_d)), through(sequential)
    for name, g, w_ in zip(NAMES, got, want):
        assert g.shape == w_.shape and g.dtype == w_.dtype, name
        assert rel_l2(g, w_) < 2e-6, name


def test_state_is_carried_across_chunks_bit_for_bit():
    """The same float32 sequence in chunks of 8, 16 and 64 steps (3, 2 and
    1 chunks with padding): the steps are the same steps in the same order
    whatever the chunk, so the output and every gradient but ``A``'s and
    ``D``'s agree bit for bit; those two are sums over the sequence taken a
    chunk at a time, and regroup."""
    args, w = scan_inputs(1, 24, 128, 8, seed=2)

    def run(chunk):
        out, pull = jax.vjp(lambda *a: ssm.selective_scan(
            *a, chunk=chunk, block_d=128), *args)
        return (out,) + pull(w)

    first = run(8)
    assert float(jnp.abs(first[0]).max()) > 0.1
    for chunk in (16, 64):
        for name, a, b in zip(("out",) + NAMES, first, run(chunk)):
            if name in ("A", "D"):
                assert rel_l2(b, a) < 1e-6, name
            else:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=name)


def test_state_and_accumulation_are_float32_under_bf16_operands():
    """bf16 ``c``, ``z``, ``B``, ``C``: against the sequential float32 scan
    of the same (rounded) operands the kernel differs by the rounding of its
    bf16 results alone, a sequence of 96 steps long."""
    args, w = scan_inputs(1, 96, 128, 16, seed=3, dtype=jnp.bfloat16)
    out, pull = jax.vjp(lambda *a: ssm.selective_scan(*a, chunk=16), *args)
    want, want_pull = jax.vjp(sequential, *args)
    assert out.dtype == jnp.bfloat16
    assert rel_l2(out, want) < 4e-3
    for name, g, w_ in zip(NAMES, pull(w.astype(jnp.bfloat16)),
                           want_pull(w.astype(jnp.bfloat16)
                                     .astype(jnp.float32))):
        assert g.dtype == w_.dtype, name
        assert rel_l2(g, w_) < (4e-3 if g.dtype == jnp.bfloat16 else 1e-5), \
            name


def test_a_bfloat16_state_would_fail_the_kernels_comparison():
    """The control of the tests above: the sequential scan with its state
    rounded to bfloat16 after every step, one precision below the float32
    the configuration states, in the kernel's place. At Mamba-1's step sizes
    (``delta`` from 1e-3 to 1e-1 against ``A`` = -1..-16, so a state sums a
    thousand steps) it stands thousands of times further from the float32
    scan than the 2e-6 the kernel is held to, in the output and in every
    gradient the state reaches."""
    T, Di, N = 256, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    c, z = (jax.random.normal(k, (T, Di)) for k in ks[:2])
    B, C = (jax.random.normal(k, (T, N)) for k in ks[2:4])
    delta = jnp.exp(jax.random.uniform(
        ks[4], (T, Di), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    A = -jnp.broadcast_to(jnp.arange(1.0, N + 1), (Di, N))
    w = jax.random.normal(ks[5], (T, Di))

    def rounded(c, delta, A, B, C):
        def step(h, x):
            c_t, d_t, B_t, C_t = x
            h = jnp.exp(d_t[None] * A.T) * h + B_t[:, None] * (d_t * c_t)[None]
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
            return h, jnp.sum(h * C_t[:, None], axis=0)
        return jax.lax.scan(step, jnp.zeros((N, Di)), (c, delta, B, C))[1]

    def through(scan):
        return jax.value_and_grad(lambda *a: jnp.sum(scan(*a) * w),
                                  argnums=(0, 1, 2, 3, 4))(c, delta, A, B, C)

    (_, want), (_, low) = through(ref.recurrence), through(rounded)
    assert rel_l2(rounded(c, delta, A, B, C),
                  ref.recurrence(c, delta, A, B, C)) > 1e-3
    for name, g, w_ in zip(NAMES, low, want):
        assert rel_l2(g, w_) > 1e-3, name
    # And the kernel, on the same inputs, is where the tests above hold it.
    args = (c[None], delta[None], A, B[None], C[None], jnp.ones((Di,)),
            z[None])
    assert rel_l2(ssm.selective_scan(*args, chunk=64),
                  sequential(*args)) < 2e-6


def test_the_kernel_refuses_shapes_it_cannot_tile():
    args, _ = scan_inputs(1, 16, 128, 8)
    with pytest.raises(ValueError):
        ssm.selective_scan(*args, chunk=12)
    with pytest.raises(ValueError):
        ssm.selective_scan(args[0][..., :64], args[1][..., :64],
                           args[2][:64], *args[3:5], args[5][:64],
                           args[6][..., :64])


def test_the_kernels_state_their_cost_to_the_planner():
    """``graph/cost.py`` prices a ``pallas_call`` by its ``cost_estimate``:
    the scan is not read as free."""
    from tepdist_tpu.graph.cost import jaxpr_flops
    args, w = scan_inputs(1, 32, 128, 8)
    fwd = jax.make_jaxpr(lambda *a: ssm.selective_scan(*a, chunk=16))(*args)
    both = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssm.selective_scan(*a, chunk=16) * w)))(*args)
    elements = 32 * 128 * 8
    assert jaxpr_flops(fwd.jaxpr) >= ssm.FWD_FLOPS * elements
    assert jaxpr_flops(both.jaxpr) >= (ssm.FWD_FLOPS + ssm.BWD_FLOPS) \
        * elements


# -- the conv kernels against the jax.numpy form ------------------------------

CONV_NAMES = ("c", "du", "dw", "db")


def conv_inputs(batch, T, Di, dtype, seed=0, K=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (batch, T, Di)).astype(dtype),
            (0.5 * jax.random.normal(ks[1], (K, Di))).astype(dtype),
            (0.1 * jax.random.normal(ks[2], (Di,))).astype(dtype),
            jax.random.normal(ks[3], (batch, T, Di)).astype(dtype))


def out_and_gradients(fn, u, w, b, dc):
    out, pull = jax.vjp(fn, u, w, b)
    return (out,) + pull(dc)


def hold_conv(got, want, limit, block_t=conv.STRIP):
    """``got`` (c, du, dw, db) to ``want``, and the rows a halo fills (the
    first of the sequence and of every later time block) on their own."""
    for name, g, w_ in zip(CONV_NAMES, got, want):
        assert g.shape == w_.shape and g.dtype == w_.dtype, name
        assert rel_l2(g, w_) < limit, name
    for name, g, w_ in zip(CONV_NAMES[:2], got, want):
        for at in range(0, g.shape[1] - 4, block_t):
            edge = slice(max(at - 4, 0), at + 4)
            assert rel_l2(g[:, edge], w_[:, edge]) < limit, (name, at)


S = conv.STRIP      # the least time block


# Two time blocks with the sequence ending inside the second; four blocks
# and two channel blocks, two sequences; one block longer than the sequence;
# a block of several strips; three taps.
@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 2e-6),
                                         (jnp.bfloat16, 4e-3)])
@pytest.mark.parametrize("batch,T,Di,K,block_t,block_d", [
    (1, S + 8, 128, 4, S, 128), (2, 3 * S + 36, 256, 4, S, 128),
    (1, 20, 128, 4, 512, 512), (1, 3 * S, 256, 4, 3 * S, 256),
    (1, S + 18, 128, 3, S, 128)])
def test_conv_kernels_match_the_jax_numpy_form(dtype, limit, batch, T, Di, K,
                                               block_t, block_d):
    """Values and the gradients of ``u``, ``w``, ``b``: zeros before the
    sequence, the rows before a block carried into it (forward) and the rows
    after it (backward), the padded rows past the end adding nothing to the
    sums."""
    args = conv_inputs(batch, T, Di, dtype, K=K)
    got = out_and_gradients(lambda *a: conv.causal_conv(
        *a, block_t=block_t, block_d=block_d), *args)
    hold_conv(got, out_and_gradients(conv.reference, *args), limit, block_t)


def test_conv_sums_are_float32_under_bf16_operands():
    """bf16 operands over 4096 rows: the taps' and the bias's gradients are
    sums of 4096 products each, within bf16's rounding of the float32 form's
    results (a bf16 accumulator would stand 1e-2 off)."""
    args = conv_inputs(1, 4096, 128, jnp.bfloat16, seed=4)
    got = out_and_gradients(conv.causal_conv, *args)
    want = out_and_gradients(
        conv.reference, *(a.astype(jnp.float32) for a in args))
    for name, g, w_ in zip(CONV_NAMES, got, want):
        assert g.dtype == jnp.bfloat16, name
        assert rel_l2(g, w_) < 4e-3, name


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_a_dropped_halo_fails_the_conv_comparison(which, monkeypatch):
    """The control of the comparison above: the same kernels with what one
    time block hands the next zeroed (the last rows of ``u`` going forward,
    the first rows of ``g`` going backward)."""
    name, carried = {"forward": ("_fwd_kernel", 4),     # the scratch's place
                     "backward": ("_bwd_kernel", 7)}[which]
    real = getattr(conv, name)

    def dropped(*refs, **how):
        refs[carried][...] = jnp.zeros(refs[carried].shape, jnp.float32)
        real(*refs, **how)

    monkeypatch.setattr(conv, name, dropped)
    u, w, b, dc = conv_inputs(1, 2 * S, 128, jnp.float32, seed=6)
    how = dict(block_t=S, block_d=128, interpret=True)
    # Not through the jitted entry points: their traces are cached.
    c = conv._fwd_call.__wrapped__(u, w, b, **how)
    du, dw, db = conv._bwd_call.__wrapped__(u, w, b, dc, **how)
    want = out_and_gradients(conv.reference, u, w, b, dc)
    with pytest.raises(AssertionError):
        hold_conv((c, du, dw, db), want, 2e-6)
    # What the fault does not touch is where it was.
    sound = (du, dw, db) if which == "forward" else (c,)
    for g, w_ in zip(sound, want[1:] if which == "forward" else want[:1]):
        assert rel_l2(g, w_) < 2e-6


def test_the_conv_refuses_shapes_it_cannot_tile():
    u, w, b, _ = conv_inputs(1, 16, 128, jnp.float32)
    with pytest.raises(ValueError):
        conv.causal_conv(u[..., :64], w[:, :64], b[:64])
    with pytest.raises(ValueError):
        conv.causal_conv(u, jnp.zeros((9, 128)), b)
    with pytest.raises(ValueError):
        conv.causal_conv(u, w, b[:64])


def test_the_conv_kernels_state_their_cost_to_the_planner():
    from tepdist_tpu.graph.cost import jaxpr_flops
    u, w, b, dc = conv_inputs(1, 32, 128, jnp.float32)
    fwd = jax.make_jaxpr(conv.causal_conv)(u, w, b)
    both = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(conv.causal_conv(*a) * dc), argnums=(0, 1, 2)))(
        u, w, b)
    assert jaxpr_flops(fwd.jaxpr) >= conv.FWD_FLOPS * u.size
    assert jaxpr_flops(both.jaxpr) >= (conv.FWD_FLOPS + conv.BWD_FLOPS) \
        * u.size


# -- the order of the layers -------------------------------------------------

def test_28_layers_run_in_the_period_rules_order():
    cfg = jamba.CONFIGS["2-3b"]
    kinds = cfg.layer_kinds
    assert [i for i, k in enumerate(kinds) if k == jamba.ATTENTION] == [7, 21]
    assert cfg.runs == ((jamba.MAMBA, 0, 7), (jamba.ATTENTION, 7, 1),
                        (jamba.MAMBA, 8, 13), (jamba.ATTENTION, 21, 1),
                        (jamba.MAMBA, 22, 6))
    one = dataclasses.replace(cfg, num_hidden_layers=14)
    assert [(k, n) for k, _, n in one.runs] == [
        (jamba.MAMBA, 7), (jamba.ATTENTION, 1), (jamba.MAMBA, 6)]
    assert (cfg.d_inner, cfg.head_dim) == (5120, 128)


def test_the_stacks_follow_the_runs():
    params = jamba.stacked_init_params(CFG, KEY)
    # A run's leaves by group: matrices, per-channel leaves, ``A_log``.
    assert sorted(params) == ["decay0", "decay2", "norm_f", "run0", "run1",
                              "run2", "tok_emb", "vec0", "vec1", "vec2"]
    assert params["run0"]["in_proj"].shape == (2, 64, 256)
    assert params["run1"]["wk"].shape == (1, 64, 16)       # one key/value head
    assert "in_proj" not in params["run1"]
    assert sorted(params["vec1"]) == ["ff_ln", "input_ln"]  # no q/k norm
    assert sorted(params["vec0"]) == [
        "D", "b_norm", "c_norm", "conv_b", "conv_w", "dt_bias", "dt_norm",
        "ff_ln", "input_ln"]
    assert all(v.ndim == 3 for v in params["run2"].values())
    assert list(params["decay2"]) == ["A_log"]
    assert params["decay2"]["A_log"].shape == (2, 128, 8)
    assert sorted(jamba.run_blocks(params, 2)) \
        == sorted(jamba.init_params(CFG, KEY)["l4"])
    layered = jamba.init_params(CFG, KEY)
    np.testing.assert_array_equal(np.asarray(params["run2"]["out_proj"][1]),
                                  np.asarray(layered["l4"]["out_proj"]))
    # Mamba-1's initialisation: A = -(1..N), D = 1, dt in [1e-3, 1e-1].
    np.testing.assert_allclose(np.asarray(jnp.exp(layered["l0"]["A_log"][5])),
                               np.arange(1, 9), rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(layered["l0"]["dt_bias"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001


def test_the_reference_refuses_weights_out_of_the_rules_order():
    params = jamba.stacked_init_params(CFG, KEY)
    swapped = {**params, "run0": params["run1"], "run1": params["run0"]}
    del swapped["decay0"]
    with pytest.raises(ValueError, match="period rule"):
        ref.hidden(swapped, jnp.zeros((8,), jnp.int32), hyper(CFG))


# -- gradient accumulation over walks of unequal shape -----------------------

def ga_step(cfg, micro, **more):
    tx = make_optimizer(OPT)

    def loss(p, t):
        return jamba.loss_fn(p, t, cfg)

    def apply_fn(p, s, g):
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    return build_ga_step(lambda p, t: jax.value_and_grad(loss)(p, t),
                         apply_fn, micro, **more, loss_fn=loss), tx


def nbytes(tree):
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


def test_every_walk_accumulates_in_the_layer_loop_and_attention_is_kept():
    """Four micro batches: all three stacks' leaves are found by the sink
    (the embedding and the final norm are outside the blocks), the attention
    layer's flash forward is kept, and the scan's and the conv's forward run
    twice a Mamba layer (the walk and its recomputation)."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params = jamba.stacked_init_params(cfg, KEY)
    step, tx = ga_step(cfg, 4)
    tokens = jamba.fake_batch(cfg, 4, 32)
    jax.make_jaxpr(step)(params, tx.init(params), tokens)
    gauge = lambda n: metrics().gauge(n).value          # noqa: E731
    stacks = {k: v for k, v in params.items()
              if k not in ("tok_emb", "norm_f")}
    assert gauge("ga_fused_bytes") == nbytes(stacks)
    assert gauge("ga_unfused_bytes") == nbytes(params) - nbytes(stacks)
    assert gauge("attn_kept_calls") == 1
    assert gauge("ssm_scan_calls") == 2 * 4            # 4 Mamba layers
    assert gauge("ssm_conv_calls") == 2 * 4
    assert gauge("ssm_boundary_bytes") == ssm.boundary_bytes(
        1, 32, cfg.d_inner, cfg.mamba_d_state, cfg.ssm_chunk) \
        == 2 * 8 * 128 * 4


@pytest.mark.parametrize("stacked", [False, True])
def test_two_steps_do_not_depend_on_the_accumulation_split(stacked):
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    init = jamba.stacked_init_params if stacked else jamba.init_params
    params = init(cfg, KEY)
    tokens = jamba.fake_batch(cfg, 4, 32, seed=2)
    results = []
    for micro in (1, 4):
        step, tx = ga_step(cfg, micro)
        state = (params, tx.init(params))
        for _ in range(2):
            loss, *state = jax.jit(step)(*state, tokens)
        results.append((float(loss), state[0]))
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-5)
    leaves_close(results[1][1], results[0][1], 1e-4)


def test_the_accumulating_walks_give_the_tree_wide_adds_step():
    """The step that adds gradients inside the three walks against the one
    that differentiates each micro batch whole and adds the trees: the same
    parameters after two steps."""
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params = jamba.stacked_init_params(cfg, KEY)
    tokens = jamba.fake_batch(cfg, 4, 32, seed=5)
    tx = make_optimizer(OPT)

    def loss(p, t):
        return jamba.loss_fn(p, t, cfg)

    def apply_fn(p, s, g):
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    grad_fn = lambda p, t: jax.value_and_grad(loss)(p, t)   # noqa: E731
    ends = []
    for more in ({"loss_fn": loss}, {}):
        step = jax.jit(build_ga_step(grad_fn, apply_fn, 4, **more))
        state = (params, tx.init(params))
        for _ in range(2):
            _, *state = step(*state, tokens)
        ends.append(state[0])
    leaves_close(ends[0], ends[1], 1e-5)


# -- scopes, and the step on other devices -----------------------------------

def test_the_mixers_parts_carry_their_scopes():
    cfg = dataclasses.replace(CFG, remat=True)
    params = jamba.stacked_init_params(cfg, KEY)
    tokens = jamba.fake_batch(cfg, 1, 32)
    text = jax.jit(jamba.loss_fn, static_argnums=2).lower(
        params, tokens, cfg).as_text(debug_info=True)
    for scope in ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_out_proj",
                  "tepdist_ssm_fwd", "tepdist_conv_fwd"):
        assert scope in text, scope


def test_a_planned_step_binds_the_scan_kernels_for_four_devices(devices):
    """``plan_training`` over four virtual devices: the scan kernels, inside
    the accumulation scan and the layer walks, are bound replicated under
    ``shard_map`` by ``parallel/spmd_transform.py`` as every kernel is, and
    the step runs."""
    from tepdist_tpu.train import plan_training
    cfg = dataclasses.replace(CFG, remat=True, loss_chunk=16)
    params = jamba.stacked_init_params(cfg, KEY)
    tokens = jamba.fake_batch(cfg, 4, 32)
    plan = plan_training(lambda p, t: jamba.loss_fn(p, t, cfg),
                         make_optimizer(OPT), params, tokens,
                         devices=devices[:4], explore=False,
                         num_micro_batches=2)
    want = float(jamba.loss_fn(params, tokens, cfg))
    assert plan.step(tokens) == pytest.approx(want, rel=1e-5)
    text = plan.compiled_step_text()
    assert len(re.findall(r"num_partitions=4", text)) >= 1
    assert np.isfinite(plan.step(tokens))


@pytest.fixture(scope="module")
def v5e_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def test_a_narrow_jambas_step_compiles_for_a_described_v5e(v5e_chip,
                                                           monkeypatch):
    """Kernels not interpreted, at a narrow model's shapes (512 channels of
    16 states, 1024 tokens, attention of one key/value head under four): the
    compiled step holds the scan's forward kernel in the walk and in its
    recomputation, its backward kernel, the flash kernels, and its peak is
    read. The cell's own step is compiled in ``tests/test_tpu_compile.py``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        CFG, hidden_size=256, intermediate_size=512, num_attention_heads=2,
        mamba_d_state=16, mamba_dt_rank=16, dtype=jnp.bfloat16, remat=True,
        loss_chunk=512, ssm_chunk=64, ssm_block_d=256, flash_block_q=512,
        flash_block_k=512)
    step, tx = ga_step(cfg, 2)
    params = jax.eval_shape(
        lambda: jamba.stacked_init_params(cfg, jax.random.PRNGKey(0)))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
        (params, jax.eval_shape(tx.init, params),
         jax.ShapeDtypeStruct((2, 1025), jnp.int32)))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    calls = [line.split(" = ", 1)[0].strip() for line in
             compiled.as_text().splitlines() if " custom-call(" in line]
    # Two Mamba walks, each: the walk's forward, the recomputation's, the
    # backward.
    assert sum("tepdist_ssm_fwd" in c for c in calls) == 4, calls
    assert sum("tepdist_ssm_bwd" in c for c in calls) == 2, calls
    assert sum("tepdist_conv_fwd" in c for c in calls) == 4, calls
    assert sum("tepdist_conv_bwd" in c for c in calls) == 2, calls
    assert sum("tepdist_flash_fwd" in c for c in calls) == 1, calls
    assert compiled.memory_analysis().peak_memory_in_bytes > 0
