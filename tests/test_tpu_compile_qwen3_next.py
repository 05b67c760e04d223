"""Compile the Qwen3-Next cell's kernels and its whole step for a DESCRIBED
v5e (no chip): ``tests/test_tpu_compile.py``'s cases for
``ops/pallas/gdn_attention.py``, the flash kernels at a head width of 256 and
``qwen3-next-80b-a3b.train.s8192``, in a file of their own so that a worker
other than that file's takes them (the suite is dealt out a file at a time).
A compile that passes is NOT a chip run: nothing executes here."""

import itertools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding
from test_tpu_compile import v5e_devices  # noqa: F401 — the fixture

CELL = "qwen3-next-80b-a3b.train.s8192"


def _custom_calls(text):
    return [line.split(" = ", 1)[0].strip() for line in text.splitlines()
            if " custom-call(" in line]


def test_gdn_kernels_compile_for_v5e(v5e_devices):
    """Forward and backward of the scalar-decay delta-rule kernels at the
    Qwen3-Next cell's ``[1, 8192, 16 | 32 x 128]``, chunks of 64 and of 128,
    not interpreted: two kernels under their names, the operands read in the
    projections' own layouts (``q, k`` 2048 wide under ``v`` 4096 wide,
    ``g`` and ``beta`` as ``[T, 32]``: no copy around a call), and nothing
    held but the states and the inverses."""
    from tepdist_tpu.ops.pallas.gdn_attention import gdn_attention
    one_chip = SingleDeviceSharding(v5e_devices[0])
    T, Hk, Hv, K = 8192, 16, 32, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    narrow, wide = sds((1, T, Hk * K), jnp.bfloat16), \
        sds((1, T, Hv * K), jnp.bfloat16)
    head = sds((1, T, Hv), jnp.float32)
    for chunk in (64, 128):
        def both(q, k, v, g, beta, do, chunk=chunk):
            out, vjp = jax.vjp(lambda *a: gdn_attention(
                *a, chunk=chunk, interpret=False), q, k, v, g, beta)
            return (out,) + vjp(do)

        compiled = jax.jit(both).lower(narrow, narrow, wide, head, head,
                                       wide).compile()
        text = compiled.as_text()
        names = _custom_calls(text)
        for kernel in ("tepdist_gdn_fwd", "tepdist_gdn_bwd"):
            assert sum(kernel in n for n in names) == 1, names
        assert f"f32[1,{T // chunk},{Hv},{K},{K}]" in text
        assert f"f32[1,{T // chunk},{Hv},{chunk},{chunk}]" in text
        # (An inverse of 64 columns is tiled to the 128 lanes.)
        pair = T // chunk * Hv * (K * K + chunk * max(chunk, 128)) * 4
        assert compiled.memory_analysis().temp_size_in_bytes < pair + 2 ** 20
        copies = [line for line in text.splitlines()
                  if re.search(rf"{T},(?:{Hk * K}|{Hv * K})\]",
                               line.split(" = ", 1)[-1][:60])
                  and (" copy(" in line or " transpose(" in line)]
        assert not copies, copies[:2]


def test_flash_at_a_head_width_of_256_compiles_for_v5e(v5e_devices):
    """The first call at D = 256: 16 query heads over 2 key/value heads at
    8,192 positions, forward and backward (a whole ``(8192, 256)`` K and V a
    grid step, 4 MiB each, and the backward's float32 ``dQ^T``), tiles of
    512 as the cell has them."""
    from tepdist_tpu.ops.pallas.flash_attention import flash_attention
    one_chip = SingleDeviceSharding(v5e_devices[0])

    def sds(heads):
        return jax.ShapeDtypeStruct((1, heads, 8192, 256), jnp.bfloat16,
                                    sharding=one_chip)

    def both(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: flash_attention(
            *a, causal=True, block_q=512, block_k=512, interpret=False),
            q, k, v)
        return (out,) + vjp(do)

    names = _custom_calls(jax.jit(both).lower(
        sds(16), sds(2), sds(2), sds(16)).compile().as_text())
    assert sum("tepdist_flash_fwd" in n for n in names) == 1, names
    assert sum("tepdist_flash_dkv" in n for n in names) == 1, names


@pytest.fixture(scope="module")
def cell_step(v5e_devices):
    """(the cell's compiled step, its parameters' shapes, its gauges), one
    compile for the tests below; a later trace in this process zeroes the
    gauges, so they are read here."""
    from tepdist_tpu.telemetry import traced
    from tools.same_ops import compiled_step
    compiled, params = compiled_step(CELL, v5e_devices[0])
    return compiled, params, traced.values()


def test_the_qwen3_next_cells_step_compiles_for_v5e(cell_step):
    """``qwen3-next-80b-a3b.train.s8192``'s step from the cell's own files
    (8 micro batches of one 8,192-token sequence; four layers in two walks
    of unequal shape; ``adamw_bf16``), kernels not interpreted: every walk's
    leaves accumulate inside its backward layer loop, the delta rule's
    forward runs once a Gated-DeltaNet layer and micro batch (``gdn_calls``
    3) and the flash forward once, the experts' stacks are read where they
    lie, and the compiler's peak is under 15.0e9 bytes."""
    from benchmark.lib import cells
    T, cell = 8192, CELL
    compiled, params, gauges = cell_step
    print("peak", compiled.memory_analysis().peak_memory_in_bytes)

    gauge = gauges.get
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n_params == 1_028_320_320
    stacks = sum(a.size * a.dtype.itemsize for r in range(2)
                 for a in jax.tree_util.tree_leaves(params[f"run{r}"]))
    assert gauge("ga_fused_bytes") == stacks
    assert gauge("ga_unfused_bytes") == 2 * 18992 * 2048 * 2 + 2048 * 4
    assert gauge("gdn_calls") == 3              # kept: once a layer
    assert gauge("kda_calls") == 0
    assert gauge("attn_kept_calls") == 1 + 3
    chunk = cells.load_cell(cell).config["program"]["gdn_chunk"]
    # A Gated-DeltaNet layer's o in bf16, its chunks' states [32, 128, 128]
    # and inverses float32; the attention layer's o in bf16 and float32 lse.
    assert gauge("attn_kept_bytes") == 3 * (
        T * 4096 * 2 + T // chunk * 32 * (128 * 128 + chunk * chunk) * 4) \
        + 16 * T * (256 * 2 + 4)
    # One conv a layer, made again in the walk's recomputation.
    assert gauge("ssm_conv_calls") == 3 * 2
    assert gauge("gdn_state_bytes") == 32 * 128 * 128 * 4
    assert gauge("attn_rotary_dim") == 64
    assert gauge("moe_stack_in_place_calls") == 4 * 12

    text = compiled.as_text()
    calls = _custom_calls(text)
    # The experts' weights are read where they lie: nothing in the step
    # makes one layer's [64, 2048, 512] out of a stack.
    made = [line.split(" = ", 1) for line in text.splitlines()
            if re.search(r" = bf16\[64,(?:2048,512|512,2048)\]\S* "
                         r"(?!parameter)", line)]
    assert not made, made[:3]
    # One walk holds the Gated-DeltaNet layers: the forward in its forward
    # loop and nowhere in its backward loop's recomputation.
    assert len([c for c in calls if "tepdist_gdn_fwd" in c]) == 1, calls
    assert len([c for c in calls if "tepdist_gdn_bwd" in c]) == 1, calls
    assert not [c for c in calls if "tepdist_kda_" in c], calls
    for which in ("fwd", "dkv"):
        names = [c for c in calls if f"tepdist_flash_{which}__" in c]
        assert len(names) == 1, calls
    assert [c for c in calls if "tepdist_conv_fwd" in c] \
        and [c for c in calls if "tepdist_gmm_" in c], calls
    assert compiled.memory_analysis().peak_memory_in_bytes < 15.0e9


def test_the_routers_choice_is_one_kernel_and_no_sort_no_scatter(cell_step):
    """The step's four routers (10 of 512 experts, 8,192 tokens) choose in
    ``tepdist_router_choice``, once in each walk's forward loop and once in
    its backward loop's recomputation: nothing in the compiled step sorts or
    scatters an array of tokens x experts elements (``lax.top_k``'s sort,
    the gradient of its values), the scores reach the kernel experts-major
    without a copy, and no ``[tokens, k, experts]`` comparison is named
    anywhere."""
    from benchmark.lib import cells
    compiled, _, gauges = cell_step
    model = cells.load_cell(CELL).config
    S, E, k = 8192, model["router_num_experts"], model["num_experts_per_tok"]
    assert (E, k) == (512, 10) and gauges["router_choice_calls"] == 4
    text = compiled.as_text()
    assert len([c for c in _custom_calls(text)
                if "tepdist_router_choice" in c]) == 2 * 2

    def sizes(line):
        return [math.prod(map(int, shape.split(",")))
                for shape in re.findall(r"\w+\[([\d,]+)\]", line)]

    wide = [line.strip()[:160] for line in text.splitlines()
            if re.search(r" (sort|scatter)\(", line)
            and S * E in sizes(line)]
    assert not wide, wide[:2]
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf"= f32\[(?:{S},{E}|{E},{S})\]\S* "
                          r"(?:copy|transpose)\(", line)]
    assert not moved, moved[:2]
    assert not [shape for shape in itertools.permutations((S, k, E))
                if "[%d,%d,%d]" % shape in text]
