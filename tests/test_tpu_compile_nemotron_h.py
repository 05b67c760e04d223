"""Compile the Nemotron-3-Nano cell's kernels and its whole step for a
DESCRIBED v5e (no chip): ``tests/test_tpu_compile.py``'s cases for
``ops/pallas/ssd_attention.py``, the two-stack expert's grouped matmuls at
2688 x 1856 and ``nemotron-3-nano-30b-a3b.train.s8192``, in a file of their
own so that a worker other than that file's takes them (the suite is dealt
out a file at a time). A compile that passes is NOT a chip run: nothing
executes here."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding
from test_tpu_compile import v5e_devices  # noqa: F401 — the fixture


def _custom_calls(text):
    return [line.split(" = ", 1)[0].strip() for line in text.splitlines()
            if " custom-call(" in line]


@pytest.mark.parametrize("H,G", [(64, 8), (16, 1)],
                         ids=["whole-8-groups", "a-ranks-16-heads-1-group"])
def test_ssd_kernels_compile_for_v5e(v5e_devices, H, G):
    """Forward and backward of the state-space-dual kernels at the cell's
    ``[1, 8192, 64 x 64]`` over 8 groups of 128 states, and at a divided
    mixer's ``[1, 8192, 16 x 64]`` over one group (the Granite 4.0-H cell's:
    a grid step its 16 heads), chunks of 128 and of
    256, not interpreted: two kernels under their names, heads of 64 lanes
    two a lane block, nothing held but the states before every chunk (a
    ``[128, 128]`` block a pair of heads), and ``Delta`` and its gradient as
    ``[T, H]`` float32."""
    from tepdist_tpu.ops.pallas.ssd_attention import ssd_attention
    one_chip = SingleDeviceSharding(v5e_devices[0])
    T, P, N = 8192, 64, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide, key = sds((1, T, H * P), jnp.bfloat16), \
        sds((1, T, G * N), jnp.bfloat16)
    head, one = sds((1, T, H), jnp.float32), sds((H,), jnp.float32)
    for chunk in (128, 256):
        def both(u, B, C, delta, A, D, dy, chunk=chunk):
            out, vjp = jax.vjp(lambda *a: ssd_attention(
                *a, groups=G, chunk=chunk, interpret=False),
                u, B, C, delta, A, D)
            return (out,) + vjp(dy)

        compiled = jax.jit(both).lower(wide, key, key, head, one, one,
                                       wide).compile()
        text = compiled.as_text()
        names = _custom_calls(text)
        for kernel in (f"tepdist_ssd_fwd__g{G}", f"tepdist_ssd_bwd__g{G}"):
            assert sum(kernel in n for n in names) == 1, names
        assert f"f32[1,{T // chunk},{H // 2},{2 * P},{N}]" in text
        states = T // chunk * H * P * N * 4
        assert compiled.memory_analysis().temp_size_in_bytes \
            < states + 2 ** 22


def test_the_two_stack_experts_grouped_matmuls_compile_for_v5e(v5e_devices):
    """The expert layer without a gate matrix at the cell's widths (hidden
    2688, experts of 1856, 16 of 128 held, 6 a token, 8192 tokens),
    forward and backward: the grouped-matmul kernels on two stacks and no
    third."""
    from tepdist_tpu.models.decoder import held_weights
    from tepdist_tpu.ops.grouped_matmul import routed_experts
    one_chip = SingleDeviceSharding(v5e_devices[0])
    S, d, f, E, G, k = 8192, 2688, 1856, 128, 16, 6

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(h, weights, experts, w_up, w_down):
        def run(h, weights, w_up, w_down):
            return routed_experts(
                h, held_weights(weights, experts, (0, G), E), experts, None,
                w_up, w_down, E, 128, held=(0, G))
        out, vjp = jax.vjp(run, h, weights, w_up, w_down)
        return (out,) + vjp(out)

    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:                                    # kernels as on the chip
        names = _custom_calls(jax.jit(layer).lower(
            sds((S, d), jnp.bfloat16), sds((S, k), jnp.float32),
            sds((S, k), jnp.int32), sds((G, d, f), jnp.bfloat16),
            sds((G, f, d), jnp.bfloat16)).compile().as_text())
    finally:
        jax.default_backend = backend
    # Two sizes of the layout, each with two matmuls forward, their inputs'
    # and their weights' gradients.
    for kernel, n in (("tepdist_gmm_fwd", 4), ("tepdist_gmm_dx", 4),
                      ("tepdist_gmm_dw", 4)):
        assert sum(kernel in c for c in names) == n, (kernel, names)


def test_the_nemotron_cells_step_compiles_for_v5e(v5e_devices):
    """``nemotron-3-nano-30b-a3b.train.s8192``'s step from the cell's own
    files (8 micro batches of one 8,192-token sequence; nine layers in three
    walks of units; ``adamw_bf16_router_bias``), kernels not interpreted:
    every walk's leaves accumulate inside its backward layer loop, the
    state-space forward runs twice a Mamba-2 layer and micro batch
    (``ssd_calls`` 8) and the flash forward once, the experts' two stacks
    are read where they lie, and the compiler's peak is under 13.0e9
    bytes."""
    from tepdist_tpu.telemetry import metrics
    from tools.same_ops import compiled_step
    T, cell = 8192, "nemotron-3-nano-30b-a3b.train.s8192"
    compiled, params = compiled_step(cell, v5e_devices[0])
    print("peak", compiled.memory_analysis().peak_memory_in_bytes)

    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n_params == 986_254_848
    stacks = sum(a.size * a.dtype.itemsize for r in range(3)
                 for a in jax.tree_util.tree_leaves(params[f"run{r}"]))
    assert gauge("ga_fused_bytes") == stacks
    assert gauge("ga_unfused_bytes") == 2 * 16384 * 2688 * 2 + 2688 * 4
    assert gauge("ssd_calls") == 4 * 2          # forward and recomputation
    assert gauge("ssm_conv_calls") == 4 * 2
    assert gauge("attn_kept_calls") == 1
    # The attention layer's o in bf16 and float32 lse.
    assert gauge("attn_kept_bytes") == 32 * T * (128 * 2 + 4)
    assert gauge("ssd_state_bytes") == 64 * 64 * 128 * 4
    assert gauge("rope_calls") == 0
    assert gauge("moe_stack_in_place_calls") == 4 * 8

    text = compiled.as_text()
    calls = _custom_calls(text)
    # The experts' weights are read where they lie: nothing in the step
    # makes one layer's [16, 2688, 1856] out of a stack.
    made = [line.split(" = ", 1) for line in text.splitlines()
            if re.search(r" = bf16\[16,(?:2688,1856|1856,2688)\]\S* "
                         r"(?!parameter)", line)]
    assert not made, made[:3]
    # A walk a run of units: the state-space forward in its forward loop
    # and in its backward loop's recomputation, the backward once.
    assert len([c for c in calls if "tepdist_ssd_fwd" in c]) == 2 * 3, calls
    assert len([c for c in calls if "tepdist_ssd_bwd" in c]) == 3, calls
    for which in ("fwd", "dkv"):
        names = [c for c in calls if f"tepdist_flash_{which}__" in c]
        assert len(names) == 1, calls
    assert [c for c in calls if "tepdist_conv_fwd" in c] \
        and [c for c in calls if "tepdist_gmm_" in c], calls
    assert compiled.memory_analysis().peak_memory_in_bytes < 13.0e9
