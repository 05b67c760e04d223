"""Expert stacks read and summed where they lie: a walk through
``models/decoder.py:walk_layers`` that accumulates gradients hands the
grouped-matmul kernels the ``[L, E, K, N]`` stack with the layer's index
(``ops/pallas/grouped_matmul.py:ExpertStack``) and makes no copy of a
layer's experts. Against the same walk on slices: the step bit for bit, no
``[E, K, N]`` slice or update left in its program, and the gauge
``moe_stack_in_place_calls``. sarvam's expert layer runs inside
``over_sequence``'s chunks, whose backward is written out and carries the
stacks' accumulators from chunk to chunk (``models/layers.py:
_chunks_carrying``): one chunk is the sliced walk bit for bit, four chunks
are it but for the one rounding a chunk of the expert leaves' sums; the loop
alone is held to ``jax.grad`` of the unchunked function in float32. OLMoE
(its own ``scan_blocks`` call) stays on slices.

A step is traced once a (model, micro batches, form, chunks): its result,
its lowered text, its kernel calls and the gauge all come from that trace
(``_built``), whichever test asks first."""

import dataclasses
import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from kernel_checks import equations_of

from tepdist_tpu.models import afmoe, layers, mellum, olmoe, sarvam_mla, zaya
from tepdist_tpu.models.decoder import EXPERT_LEAVES
from tepdist_tpu.ops.pallas.grouped_matmul import ExpertStack, grouped_matmul
from tepdist_tpu.optim import make_optimizer
from tepdist_tpu.parallel.sync_free import build_ga_step
from tepdist_tpu.telemetry import metrics

OPT = {"name": "adamw_bf16_router_bias", "learning_rate": 1e-3,
       "bias_rate": 0.001}
# model -> (module, its tiny configuration in bf16, expert layers)
MODELS = {
    "zaya": (zaya, zaya.CONFIGS["test-bf16"], 3),
    "afmoe": (afmoe, afmoe.CONFIGS["test"], 2),
    "mellum": (mellum, mellum.CONFIGS["test"], 3),
    "olmoe": (olmoe, olmoe.CONFIGS["test"], 0),
    "sarvam": (sarvam_mla, sarvam_mla.CONFIGS["test"], 2),
}
IN_PLACE = ("zaya", "afmoe", "mellum", "sarvam")
T, CHUNKS = 32, 4           # a sequence, and sarvam's chunks where forced


class Built(NamedTuple):
    """One traced step: what it returns, the gauge its trace set, the
    moves of a layer's experts in its lowered text (``_expert_leaf_moves``),
    the rank of every grouped-matmul call's weight and the routers'
    differentiated choices its trace counted (``router_choice_calls``)."""
    out: Any
    gauge: float
    moves: Tuple[str, ...]
    ranks: Tuple[int, ...]
    choices: float


def _built(name, micro, sliced=False, chunks=1) -> Built:
    """The step of ``micro`` micro batches of two sequences, traced once.
    ``sliced``: every walk as PR 48 made it, no leaf stays whole.
    ``chunks``: of a block's token-wise parts (``over_sequence``)."""
    return _trace(name, micro, sliced, chunks)


@functools.lru_cache(maxsize=None)
def _trace(name, micro, sliced, chunks) -> Built:
    model, cfg, _ = MODELS[name]
    cfg = dataclasses.replace(cfg, remat=True, dtype=jnp.bfloat16)
    tx = make_optimizer(dict(OPT))
    loss = lambda p, t: model.loss_fn(p, t, cfg)            # noqa: E731

    def apply_fn(p, s, g):
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    params = model.stacked_init_params(cfg, jax.random.PRNGKey(0))
    args = (params, tx.init(params),
            model.fake_batch(cfg, 2 * micro, T, seed=9))
    step = jax.jit(build_ga_step(
        lambda p, t: jax.value_and_grad(loss)(p, t), apply_fn, micro,
        loss_fn=loss))
    with pytest.MonkeyPatch.context() as patch:
        if sliced:
            walk = layers._walk_accumulating
            patch.setattr(
                layers, "_walk_accumulating",
                lambda body, x, blocks, acc, kinds, in_place=():
                walk(body, x, blocks, acc, kinds))
        if chunks > 1:
            patch.setattr(layers, "_CHUNK_ELEMENTS",
                          2 * (T // chunks) * model._widest(cfg))
        traced = step.trace(*args)
    gauge = metrics().gauge("moe_stack_in_place_calls").value
    choices = metrics().gauge("router_choice_calls").value
    lowered = traced.lower()
    return Built(
        lowered.compile()(*args), gauge,
        _expert_leaf_moves(lowered.as_text(), params["blocks"]),
        tuple(max(v.aval.ndim for v in (*e.invars, *e.outvars))
              for e in equations_of(traced.jaxpr.jaxpr)
              if e.primitive.name == "pallas_call"
              and e.params["name"].startswith("tepdist_gmm_")),
        choices)


def _expert_leaf_moves(text, blocks):
    """The slices out of and the updates into a stacked expert leaf in the
    lowered step: ``dynamic_slice`` results and ``dynamic_update_slice``
    updates of one layer ``[1, E, K, N]`` (a kernel's interpreted blocks
    are ``[1, 1, K, n]`` at most)."""
    layer = {"x".join(map(str, (1,) + a.shape[1:]))
             for k, a in blocks.items() if k in EXPERT_LEAVES}
    assert all(one.count("x") == 3 for one in layer), layer
    found = []
    for line in text.splitlines():
        if "stablehlo.dynamic_slice" in line and any(
                f"-> tensor<{one}x" in line for one in layer):
            found.append("dynamic_slice")
        if "stablehlo.dynamic_update_slice" in line and any(
                f">, tensor<{one}x" in line for one in layer):
            found.append("dynamic_update_slice")
    return tuple(found)


def _stack_calls(built: Built):
    """The grouped-matmul kernel calls of the step's program by the rank of
    their weight operand or result: (over a stack, over a slice)."""
    assert set(built.ranks) <= {3, 4}
    return built.ranks.count(4), built.ranks.count(3)


def _leaves(built: Built):
    """(path, float32 array) of everything a step returns."""
    return [(jax.tree_util.keystr(path), np.asarray(a, np.float32))
            for path, a in jax.tree_util.tree_leaves_with_path(built.out)]


@pytest.mark.parametrize("name,micro", [
    (name, micro) for name in IN_PLACE for micro in (2, 3)
    if (name, micro) != ("sarvam", 3)])     # its 3: the chunked case below
def test_the_step_is_the_sliced_walks_bit_for_bit(name, micro):
    """Loss, every parameter and the optimizer's state after one step of
    2 and of 3 micro batches: the walk that hands the kernels the stack
    against the walk that hands them slices (sarvam: a layer's sequence one
    chunk, so each accumulator gets one cotangent a layer)."""
    got, want = _built(name, micro), _built(name, micro, sliced=True)
    assert got.gauge == 12 * MODELS[name][2] and want.gauge == 0
    got, want = _leaves(got), _leaves(want)
    assert len(got) == len(want)
    for (path, a), (_, b) in zip(got, want):
        np.testing.assert_array_equal(a, b, err_msg=path)


@pytest.mark.parametrize("micro", [2, 3])
def test_chunks_add_into_the_accumulator_one_rounding_a_chunk(micro):
    """sarvam with a layer's token-wise parts in four chunks: the expert
    leaves' gradients are added chunk by chunk into the walk's accumulator
    (the sliced walk adds them to a zeroed carry and the total to the
    accumulator), so Adam's first moment, a tenth of the gradient, lies
    within a bf16 addition's rounding a chunk and one more of the sliced
    walk's; every other leaf, the loss and the router's bias bit for bit.
    The gauge counts a layer's twelve calls once, whatever its chunks, and
    so does the routers' (the written-out chunk loop differentiates a chunk
    after the walk's body has returned, and still for its layers)."""
    got = _built("sarvam", micro, chunks=CHUNKS)
    want = _built("sarvam", micro, sliced=True, chunks=CHUNKS)
    assert got.gauge == 12 * 2 and want.gauge == 0
    assert got.choices == want.choices == 2
    experts = moved = 0
    for (path, a), (_, b) in zip(_leaves(got), _leaves(want)):
        if not any(f"['blocks']['{k}']" in path for k in EXPERT_LEAVES):
            np.testing.assert_array_equal(a, b, err_msg=path)
        elif ".mu[" in path:
            experts += 1
            moved += int((a != b).sum())
            np.testing.assert_allclose(
                a, b, rtol=0, atol=(CHUNKS + 1) * 2.0 ** -8 * np.abs(b).max(),
                err_msg=path)
        elif ".nu[" not in path:
            # Adam's first step is the rate times the gradient's sign: one
            # that rounds across zero moves its weight by twice the rate.
            np.testing.assert_allclose(
                a, b, rtol=0, atol=2 * OPT["learning_rate"] + 2.0 ** -8
                * np.abs(b).max(), err_msg=path)
    assert experts == 3 and moved      # the two orders do differ


@pytest.mark.parametrize("name", IN_PLACE)
def test_no_layers_experts_are_sliced_out_or_updated_in(name):
    """The step's program holds no ``dynamic_slice`` whose result and no
    ``dynamic_update_slice`` whose update is one layer of an expert leaf,
    and every grouped-matmul call in it takes a stack; on slices it holds
    nine and three a trace of the walk's body and more. sarvam in four
    chunks: the written-out chunk loop moves no layer either."""
    chunks = CHUNKS if name == "sarvam" else 1
    built = _built(name, 2, chunks=chunks)
    assert built.moves == ()
    over_stack, over_slice = _stack_calls(built)
    assert over_stack and not over_slice
    sliced = _built(name, 2, sliced=True, chunks=chunks)
    moves = sliced.moves
    assert moves.count("dynamic_slice") >= 9 \
        and moves.count("dynamic_update_slice") >= 3, moves
    assert _stack_calls(sliced)[0] == 0


def test_the_gauge_counts_every_grouped_matmul_call_of_the_zaya_walk():
    """One trace of the walk's body stands for its three layers: the
    kernels' calls in the step's program (forward loop, recomputation, the
    two gradients) times the layers are what the gauge reads."""
    built = _built("zaya", 2)
    assert _stack_calls(built) == (12, 0)
    assert built.gauge == 3 * 12


def test_the_walk_that_stays_on_slices_holds_no_stack_form():
    """OLMoE calls ``scan_blocks`` itself: no kernel of its step takes a
    stack and the gauge reads 0."""
    built = _built("olmoe", 2)
    over_stack, over_slice = _stack_calls(built)
    assert over_stack == 0 and over_slice > 0
    assert built.gauge == 0


# -- ``over_sequence``'s written-out backward alone --------------------------

L, E, K, N, TILE = 3, 2, 16, 8, 8       # a stack of three layers' experts
LAYER = 1


def _toy():
    """Two sequences of 32 positions through two dense leaves and one
    layer's experts, a row's expert chosen by its position: what a chunk of
    8 positions computes of them (``chunk``) and the whole sequence's loss
    at once, plain (``whole``)."""
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    d = 12
    x = jax.random.normal(keys[0], (2, T, d))
    dense = {"w_in": 0.3 * jax.random.normal(keys[1], (d, K)),
             "gain": 1 + 0.1 * jax.random.normal(keys[2], (K,))}
    stack = 0.3 * jax.random.normal(keys[3], (L, E, K, N))
    into = jax.random.normal(keys[4], (L, E, K, N))
    cot = jax.random.normal(keys[5], (2, T, N))

    def chunk(w, start, xc):
        B, c, _ = xc.shape              # a tile a sequence: c == TILE
        h = (xc @ w["w_in"] * w["gain"]).reshape(B * c, K)
        groups = (start // c + jnp.arange(B, dtype=jnp.int32)) % E
        y = grouped_matmul(h, w["experts"], groups,
                           jnp.full((1,), B, jnp.int32), TILE)
        return jnp.sin(y).reshape(B, c, N) + start

    def whole(x, dense, experts):
        h = x @ dense["w_in"] * dense["gain"]
        of = (jnp.arange(T)[None, :] // TILE + jnp.arange(2)[:, None]) % E
        y = jnp.einsum("btk,btkn->btn", h, experts[of])
        starts = jnp.arange(T, dtype=jnp.float32) // TILE * TILE
        return jnp.sum((jnp.sin(y) + starts[None, :, None]) * cot)

    def chunked(fn, x, weights=None):
        # Four chunks of 8 positions, by the width chunks are sized with.
        widest = layers._CHUNK_ELEMENTS // (2 * TILE)
        return jnp.sum(layers.over_sequence(fn, widest, x, weights=weights)
                       * cot)

    return x, dense, stack, into, chunk, whole, chunked


def test_the_written_out_chunk_loop_is_the_unchunked_functions_gradient():
    """Float32, four chunks: the loss, the input's and the dense leaves'
    gradients are ``jax.grad``'s of the unchunked function, and the stack's
    accumulator comes back once, with the layer's gradient added into its
    slice and every other slice as it was."""
    x, dense, stack, into, chunk, whole, chunked = _toy()
    layer = jnp.full((1,), LAYER, jnp.int32)

    def loss(x, dense, into):
        return chunked(chunk, x, {
            **dense, "experts": ExpertStack(stack, layer, into)})

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=2))(x, dense, into))
    assert text.count("tepdist_gmm_dw") == 1        # in one loop's body
    assert "add_any" not in text                    # nothing sums stacks
    got, (dx, d_dense, d_into) = jax.value_and_grad(
        loss, argnums=(0, 1, 2))(x, dense, into)
    want, (want_dx, want_dense, experts) = jax.value_and_grad(
        whole, argnums=(0, 1, 2))(x, dense, stack[LAYER])
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for name, g, w in (("x", dx, want_dx),
                       ("w_in", d_dense["w_in"], want_dense["w_in"]),
                       ("gain", d_dense["gain"], want_dense["gain"]),
                       ("experts", d_into[LAYER], into[LAYER] + experts)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=0,
            atol=2e-6 * float(jnp.abs(w).max()), err_msg=name)
    for other in (0, 2):
        np.testing.assert_array_equal(np.asarray(d_into[other]),
                                      np.asarray(into[other]))


def test_plain_weights_keep_the_mapped_checkpointed_chunk():
    """No stack among the weights, handed in or closed over: the ``lax.map``
    of a checkpointed chunk as it always was (no ``custom_vjp`` in the
    program), the same gradients either way bit for bit, and the unchunked
    function's."""
    x, dense, stack, _, chunk, whole, chunked = _toy()

    def grads(handed):
        def loss(x, dense, experts):
            weights = {**dense, "experts": experts}
            if handed:
                return chunked(chunk, x, weights)
            return chunked(lambda *xs: chunk(weights, *xs), x)

        grad = jax.grad(loss, argnums=(0, 1, 2))
        text = str(jax.make_jaxpr(grad)(x, dense, stack[LAYER]))
        # The rematerialised chunk inside the transposed map, and nothing
        # written out: the only ``custom_vjp`` is the kernel's own, which
        # its differentiation has already taken apart.
        assert "remat2[" in text and "custom_vjp_call" not in text
        return jax.tree_util.tree_leaves(grad(x, dense, stack[LAYER]))

    want = jax.tree_util.tree_leaves(jax.grad(whole, argnums=(0, 1, 2))(
        x, dense, stack[LAYER]))
    for a, b, w in zip(grads(True), grads(False), want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=0,
                                   atol=2e-6 * float(jnp.abs(w).max()))


# -- ``over_sequence`` over several sequences ---------------------------------

D = 6                                   # channels of the chunk loop's toy
WIDEST = 8                              # ... and what sizes its chunks


def _turned(w, start, xc):
    """Token-wise given ``start``: a token's channels through ``w``, turned
    by cos of 0.3 its position (as rotary angles are); two results of
    unequal width."""
    positions = (start + jnp.arange(xc.shape[1])).astype(jnp.float32)
    turn = jnp.cos(0.3 * positions)[None, :, None]
    return jnp.tanh(xc @ w) * turn, (xc * turn).sum(-1)


@pytest.mark.parametrize("B", [1, 2, 3])
def test_chunks_of_several_sequences_give_the_unchunked_function(
        B, monkeypatch):
    """Float32, ``B`` sequences of 32 positions in four chunks of 8 of each:
    both results and ``jax.grad``'s gradients are the unchunked function's,
    the position-dependent turn at each chunk's own ``start``."""
    monkeypatch.setattr(layers, "_CHUNK_ELEMENTS", B * 8 * WIDEST)
    keys = jax.random.split(jax.random.PRNGKey(B), 4)
    x = jax.random.normal(keys[0], (B, T, D))
    w = 0.5 * jax.random.normal(keys[1], (D, D))
    cots = (jax.random.normal(keys[2], (B, T, D)),
            jax.random.normal(keys[3], (B, T)))

    def loss(chunked, x, w):
        out = (layers.over_sequence(_turned, WIDEST, x, weights=w)
               if chunked else _turned(w, 0, x))
        return sum(jnp.sum(o * c) for o, c in zip(out, cots))

    text = str(jax.make_jaxpr(functools.partial(loss, True))(x, w))
    assert f":f32[4,{B},8,{D}] " in text        # the chunk loop's results
    for got, want in zip(
            jax.tree_util.tree_leaves(jax.value_and_grad(
                functools.partial(loss, True), argnums=(0, 1))(x, w)),
            jax.tree_util.tree_leaves(jax.value_and_grad(
                functools.partial(loss, False), argnums=(0, 1))(x, w))):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0,
            atol=2e-6 * float(jnp.abs(want).max()))
