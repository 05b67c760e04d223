"""What a walk over rematerialised blocks keeps of its attention
(``models/layers.py:scan_blocks`` under ``parallel/sync_free.py:
build_ga_step``, ``ops/pallas/flash_attention.py:KeptForward``): the forward
flash kernel's ``(o, lse)`` a layer, so that the backward pass's
recomputation of a block runs no forward kernel. The step is bit for bit the
step that rematerialises everything, each forward kernel is in the program
once a kind and a walk where it was twice, and the gauges ``attn_kept_calls``
/ ``attn_kept_bytes`` say what is held. The hand-over carries whatever tuple
a call gives: the block top-k attention's pair and its sets beside flash's
pairs (``tests/test_minicpm_sala.py`` holds that layer's walked step).
"""

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tepdist_tpu.models import afmoe, gpt2, mellum, minicpm_sala, olmoe
from tepdist_tpu.ops import grouped_matmul as gm
from tepdist_tpu.ops.pallas import flash_attention as fa
from tepdist_tpu.parallel.sync_free import build_ga_step
from tepdist_tpu.telemetry import metrics

# Level 1: at LLVM level 0 the walked block's flash forward and the plain
# one round apart (``test_walked_blocks_keep_their_flash_forward[mellum]``).
pytestmark = pytest.mark.usefixtures("optimized_programs")

MICRO, BATCH, SEQ = 4, 8, 32
BF16 = jnp.bfloat16


def _gpt2(policy):
    cfg = dataclasses.replace(
        gpt2.CONFIGS["test"], n_layer=3, remat=True, dtype=BF16,
        attn="flash", remat_policy=policy)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(BF16) if a.ndim > 1 else a,
        gpt2.stacked_init_params(cfg, jax.random.PRNGKey(0)))
    return (lambda p, t: gpt2.loss_fn_stacked(p, t, cfg), params,
            gpt2.fake_batch(cfg, BATCH, SEQ), cfg.n_head, cfg.head_dim)


def _expert_model(module, **changes):
    cfg = dataclasses.replace(module.CONFIGS["test"], remat=True, dtype=BF16,
                              **changes)
    params = module.stacked_init_params(cfg, jax.random.PRNGKey(0), std=0.1)
    return (lambda p, t: module.loss_fn(p, t, cfg), params,
            module.fake_batch(cfg, BATCH, SEQ, seed=1),
            cfg.num_attention_heads, cfg.head_dim)


# name -> (model, micro batches, flash calls kept a micro batch, forward
# kernels in the step's program: one a kind and a walk)
CASES = {
    "olmoe": (lambda: _expert_model(olmoe), MICRO, 2, 1),
    # Two stacks: a dense window layer; a global and a window layer whose
    # kernels are the two branches of one ``lax.cond``.
    "afmoe": (lambda: _expert_model(afmoe), MICRO, 3, 3),
    # One stack of window, global (YaRN's table), window.
    "mellum": (lambda: _expert_model(mellum, loss_chunk=16), MICRO, 3, 2),
    "gpt2-save-attn": (lambda: _gpt2("save_attn"), MICRO, 3, 1),
    # Paths that keep nothing: the recipe that pins full rematerialisation,
    # and one micro batch (the plain scan under jax.checkpoint).
    "gpt2-full": (lambda: _gpt2("full"), MICRO, 0, 2),
    "olmoe-one-micro-batch": (lambda: _expert_model(olmoe), 1, 0, 2),
}


def _steps(loss, micro):
    """(the step as ``plan_training`` builds it, the step of the tree-wide
    add: every block under ``jax.checkpoint``, all of it rematerialised)."""
    opt = optax.adamw(1e-2)

    def apply_fn(p, s, g):
        updates, s = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s

    def build(**more):
        return jax.jit(build_ga_step(
            lambda p, *b: jax.value_and_grad(loss)(p, *b), apply_fn, micro,
            **more))
    return build(loss_fn=loss), build(), opt


def _gauges(*names):
    return tuple(metrics().gauge(n).value for n in names)


def _kernels(fn, *args):
    """How often each kernel is in ``fn``'s program: the names of the
    ``pallas_call`` equations of its jaxpr, nested jaxprs included (a loop's
    body counts once)."""
    def sub_jaxprs(eqn):
        for v in eqn.params.values():
            for j in v if isinstance(v, (list, tuple)) else (v,):
                if hasattr(j, "eqns"):
                    yield j
                elif hasattr(j, "jaxpr") and hasattr(j.jaxpr, "eqns"):
                    yield j.jaxpr

    def names(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in sub_jaxprs(eqn):
                yield from names(sub)

    return collections.Counter(names(jax.make_jaxpr(fn)(*args).jaxpr))


def _flash_kernels(fn, *args):
    """:func:`_kernels`, the flash kernels alone (``fwd`` / ``dkv``)."""
    return collections.Counter({n: c for n, c in _kernels(fn, *args).items()
                                if n.startswith("tepdist_flash_")})


def _count(kernels, which):
    return sum(n for name, n in kernels.items()
               if name.startswith(f"tepdist_flash_{which}__"))


@pytest.fixture
def one_forward_everywhere(monkeypatch):
    """An expert layer's first half runs, where nothing differentiates it,
    what a differentiated pass runs (``ops/grouped_matmul.py:activation``:
    the walk's first forward has the activation as a kernel's epilogue,
    which rounds ``up`` once less than the plain scan's only forward, so
    the two steps differ by that rounding). Held to each other bit for bit
    they have to run one forward; the backward rule is the layer's own."""
    composed = jax.custom_vjp(lambda *a: gm._activation_fwd(*a)[0],
                              nondiff_argnums=(6,))
    composed.defvjp(gm._activation_fwd, gm._activation_bwd)
    monkeypatch.setattr(gm, "activation", composed)
    # No trace from before: ``jax.jit`` keeps its traces by the function it
    # wraps, so a new ``jit`` of ``routed_experts_at`` itself would find
    # what an earlier test file of this process traced at these shapes
    # (``tests/test_stack_in_place.py``'s steps, with the epilogue).
    def branch(*args, **static):
        return gm.routed_experts_at(*args, **static)

    monkeypatch.setattr(gm, "_branch", jax.jit(
        branch, inline=True, static_argnames="tile_m"))


@pytest.mark.parametrize("case", list(CASES))
def test_walked_blocks_keep_their_flash_forward(case, one_forward_everywhere):
    model, micro, kept_calls, fwd_kernels = CASES[case]
    loss, params, batch, n_head, head_dim = model()
    metrics().gauge("attn_kept_calls").set(-1)
    step, whole_remat, opt = _steps(loss, micro)
    state = opt.init(params)

    kernels = _flash_kernels(step, params, state, batch)
    # (c) what the shapes say: o [B, H, T, D] in bf16 and a float32 lse
    # [B, H, T] a call.
    assert _gauges("attn_kept_calls", "attn_kept_bytes") == (
        kept_calls, kept_calls * (BATCH // micro) * n_head * SEQ
        * (head_dim * 2 + 4))
    fused = _gauges("ga_fused_bytes", "ga_unfused_bytes", "ce_fused_chunks")

    # (b) one forward kernel a kind and a walk; where nothing is kept the
    # recomputation holds the second. The backward kernels as they were.
    old_kernels = _flash_kernels(whole_remat, params, state, batch)
    assert _gauges("attn_kept_calls", "attn_kept_bytes") == (0, 0)
    assert _count(kernels, "fwd") == fwd_kernels
    assert _count(old_kernels, "fwd") == 2 * _count(old_kernels, "dkv")
    # One backward kernel a kind (PR 46): no dQ kernel on either path.
    assert _count(kernels, "dq") == _count(old_kernels, "dq") == 0
    assert _count(kernels, "dkv") == _count(old_kernels, "dkv") \
        == _count(old_kernels, "fwd") // 2
    assert {n for n in kernels if "_fwd__" not in n} \
        == {n for n in old_kernels if "_fwd__" not in n}

    # (d) the accumulation in the layer loop and the loss's fused chunks
    # are what they are without the keeping: every stack's bytes, and the
    # chunks of the loss that the other step traced.
    def nbytes(tree):
        return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))

    stacks = [v for v in params.values() if isinstance(v, dict)]
    assert fused[:2] == ((nbytes(stacks), nbytes(params) - nbytes(stacks))
                         if micro > 1 else (0, 0))
    assert fused[2] == _gauges("ce_fused_chunks")[0]

    # (a) two steps, bit for bit the steps that rematerialise everything.
    got, want = (params, state), (params, state)
    for _ in range(2):
        loss_got, *got = step(*got, batch)
        loss_want, *want = whole_remat(*want, batch)
        assert float(loss_got) == float(loss_want)
        assert np.isfinite(float(loss_got))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _pallas_names(fn, *args):
    return sorted(n.split("__")[0] for n in _kernels(fn, *args).elements())


@pytest.mark.parametrize("dtype", [jnp.float32, BF16])
def test_a_hand_over_carries_any_tuple_and_replays_in_order(dtype):
    """One block with four hand-overs of three lengths: a sparse layer at or
    under ``dense_len`` (a flash call's pair), a call of the test's own that
    gives three arrays, and a sparse layer past ``dense_len`` (the sets
    alone, then the top-k kernel's pair). Replaying, each call gets its own
    tuple back in the recording's order, no forward kernel and no choice is
    traced, and output and gradients are those outside any walk."""
    cfg = dataclasses.replace(minicpm_sala.CONFIGS["test"], dtype=dtype)
    H, G, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (1, 128, H, D)).astype(dtype)
    k, v = (jax.random.normal(key, (1, 128, G, D)).astype(dtype)
            for key in ks[1:])
    taken = []

    def three(saved):
        if saved:
            taken.append(saved)
            return saved[0]
        a = q[:, :32].reshape(1, 32, H * D) * 2
        return a if saved is None else (a, a + 1, a + 2)

    def block(q, k, v):
        short = [x[:, :cfg.sparse.dense_len] for x in (q, k, v)]
        return (minicpm_sala.sparse_attention(*short, cfg)
                + fa.hand_over(three),
                minicpm_sala.sparse_attention(q, k, v, cfg))

    def total(q, k, v):
        return sum(o.astype(jnp.float32).sum() for o in block(q, k, v))

    want = block(q, k, v)
    want_grads = jax.grad(total, argnums=(0, 1, 2))(q, k, v)
    with fa.KeptForward() as keep:
        recorded = block(q, k, v)
    assert [len(t) for t in keep.kept] == [2, 3, 1, 2]
    assert keep.kept[2][0].dtype == jnp.int32
    assert keep.kept[3][0].shape == q.shape

    def replayed(q, k, v):
        with fa.KeptForward(keep.kept):
            return block(q, k, v)

    def replayed_total(q, k, v):
        return sum(o.astype(jnp.float32).sum() for o in replayed(q, k, v))

    got = replayed(q, k, v)
    assert len(taken) == 1 and all(
        a is b for a, b in zip(taken[0], keep.kept[1], strict=True))
    grads = jax.grad(replayed_total, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip((*recorded, *got, *grads), (*want, *want, *want_grads),
                    strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert _pallas_names(replayed, q, k, v) == []
    assert "bitcast_convert_type" not in str(jax.make_jaxpr(replayed)(q, k, v))
    assert "bitcast_convert_type" in str(jax.make_jaxpr(block)(q, k, v))
    assert _pallas_names(jax.grad(replayed_total, argnums=(0, 1, 2)),
                         q, k, v) == [
        "tepdist_flash_dkv", "tepdist_topk_attn_bwd"]
