"""Compile the main path's pallas kernels for a DESCRIBED v5e (no chip).

The TPU compiler is installed without a device: it compiles for a topology
that is described, not attached, and refuses what the chip would refuse —
a slice off the tiling, too much VMEM, a Mosaic kernel left to the SPMD
partitioner. Interpret-mode tests cannot see any of that. A compile that
passes is NOT a chip run: nothing executes here.
"""

import collections
import contextlib
import dataclasses
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import SingleDeviceSharding

from tepdist_tpu.ops.pallas.flash_attention import flash_attention


@pytest.fixture(scope="module")
def v5e_devices(optimized_programs):
    """The devices of a described v5e:2x2 (``tools/described_chip.py``).
    Every case of the five described-chip files asks for them, so every one
    compiles at the level its peaks and counts were pinned at."""
    from tools.described_chip import described_v5e
    with contextlib.ExitStack() as stack:
        try:
            devices = stack.enter_context(described_v5e())
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
        yield devices


# A row statistic stored one to a row, [.., T, 1], is tiled (8, 128) in HBM:
# 128 times its size, as much as q, k, v and o together (PERF.md, PR 25).
_PADDED_ROWS = re.compile(r"f32\[(?:\d+,)*1\]\{[^}]*T\(8,128\)")


def _flash_call_shapes(text):
    """For each flash custom call of a compiled module, the shapes (with
    layouts) of its results and of its operands, as one string."""
    defined = {}
    calls = []
    for line in text.splitlines():
        name, eq, rhs = line.strip().removeprefix("ROOT ").partition(" = ")
        if not eq or not name.startswith("%"):
            continue
        defined[name] = rhs.split("(%", 1)[0]
        if "tpu_custom_call" in rhs and " custom-call(" in rhs \
                and "tepdist_flash_" in name:
            operands = rhs.split(" custom-call(", 1)[1].split(")", 1)[0]
            calls.append((name, re.findall(r"%[\w.\-]+", operands)))
    return {name: defined[name] + " <- " + " ".join(
        defined.get(o, "?") for o in operands) for name, operands in calls}


# (B, H, T, D), dtype, tile, of the main path: GPT-2 117M at batch 8, GPT-2
# 1.5B (25 heads) at micro batch 4 and, with the 512 tiles its configuration
# sets, at the benchmark cell's micro batch 3; a 32-head x 128 long-sequence
# shape; one float32 shape (float32 callers keep float32 matmuls); and the
# module docstring's limit, T = 8192.
@pytest.mark.parametrize("shape,dtype,block", [
    ((8, 12, 1024, 64), jnp.bfloat16, None),
    ((4, 25, 1024, 64), jnp.bfloat16, None),
    ((2, 32, 2048, 128), jnp.bfloat16, None),
    ((3, 25, 1024, 64), jnp.bfloat16, 512),
    ((2, 4, 1024, 64), jnp.float32, None),
    ((1, 12, 8192, 64), jnp.bfloat16, None),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple)
    else getattr(v, "__name__", str(v)))
def test_flash_kernels_compile_for_v5e(v5e_devices, shape, dtype, block):
    """The forward and the backward kernel (via jax.grad), not interpreted;
    no operand or result of a kernel is a row statistic padded 128-fold."""
    one_chip = SingleDeviceSharding(v5e_devices[0])
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=block, block_k=block,
                                       interpret=False).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    calls = _flash_call_shapes(text)
    assert len(calls) == 2, sorted(calls)
    for name, shapes in calls.items():
        assert "?" not in shapes, (name, shapes)
        assert not _PADDED_ROWS.search(shapes), (name, shapes)


# The Trinity cell's attention: 32 query heads over 4 key/value heads of 128
# at 8192 positions, 512 tiles; a window the tiles divide (its layers'), none
# (its global layer's) and one they do not divide (the masked-everywhere
# path).
# The Mellum2 cell's: the same heads at 16384 positions, window 1024 (two
# key blocks wide) and none. There the two whole-sequence operands of a grid
# step, double-buffered, are 16 MiB, the compiler's default limit for one
# kernel: the calls ask for more (``flash_attention._compiler_params``), and
# no forward call of the shapes above does. The backward call holds the
# head's dQ^T in float32 and its dQ block beside them (``_bwd_holds``): at
# 8192 that is 16 MiB and it asks for 32, at 16384 for 48.
@pytest.mark.parametrize("T,window", [(8192, 2048), (8192, None),
                                      (8192, 1000), (16384, 1024),
                                      (16384, None)])
def test_windowed_grouped_query_flash_compiles_for_v5e(v5e_devices, T,
                                                       window):
    from tepdist_tpu.ops.pallas.flash_attention import (
        _bwd_holds,
        _compiler_params,
    )
    assert (_compiler_params(T, 128, 2) is None) == (T == 8192)
    assert _bwd_holds(T, 128, 2) == T * 128 * (4 + 2 * 2)
    assert _compiler_params(T, 128, 2, _bwd_holds(T, 128, 2)) \
        .vmem_limit_bytes == (32 if T == 8192 else 48) * 2 ** 20
    one_chip = SingleDeviceSharding(v5e_devices[0])
    q = jax.ShapeDtypeStruct((1, 32, T, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, T, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, block_q=512, block_k=512, interpret=False,
            window=window).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    calls = _flash_call_shapes(text)
    assert len(calls) == 2, sorted(calls)
    tag = ("" if window is None else f"__w{window}") + "__kv4"
    for name, shapes in calls.items():
        assert tag in name and "?" not in shapes, (name, shapes)
        assert not _PADDED_ROWS.search(shapes), (name, shapes)
        # k and v reach the kernels at their own head count: no broadcast.
        assert f"bf16[4,{T},128]" in shapes, (name, shapes)


def test_the_backward_pass_is_one_kernel_at_16k_for_v5e(v5e_devices):
    """Mellum2's global layer, [1, 32, 16384, 128] over 4 key/value heads:
    the backward pass is the one ``tepdist_flash_dkv`` call, six operands
    with q first and dq, dk, dv as its results, each a query head's
    [T, D] in bf16 (no float32 dQ crosses HBM); it asks for 48 MiB of
    scoped VMEM (q and dO whole, double-buffered, 16; the head's dQ^T in
    float32 and its dQ block 16; 16 for the rest) where the forward asks
    for 32; the compiled module holds no ``tepdist_flash_dq``."""
    one_chip = SingleDeviceSharding(v5e_devices[0])
    T = 16384
    q = jax.ShapeDtypeStruct((1, 32, T, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, T, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=512, block_k=512,
                                       interpret=False).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert "tepdist_flash_dq" not in text
    calls = _flash_call_shapes(text)
    (name, shapes), = [c for c in calls.items() if "tepdist_flash_dkv" in c[0]]
    results, _, operands = shapes.partition(" <- ")
    assert re.findall(r"\w+\[[\d,]+\]", results) \
        == [f"bf16[32,{T},128]"] * 3, shapes
    assert re.findall(r"\w+\[[\d,]+\]", operands) == [
        f"bf16[32,{T},128]", f"bf16[4,{T},128]", f"bf16[4,{T},128]",
        f"bf16[32,{T},128]", "f32[32,32,1,512]", "f32[32,32,1,512]"], shapes
    asked = {name.split("tepdist_flash_")[1][:3]: int(size) for name, size
             in re.findall(r"^\s*(?:ROOT )?(%\S*tepdist_flash_\S+) = .*?"
                           r'scoped_memory_configs":\[\{"memory_space":"1",'
                           r'"offset":"0","size":"(\d+)"', text, re.M)}
    assert asked == {"fwd": 32 * 2 ** 20, "dkv": 48 * 2 ** 20}, asked


@pytest.mark.parametrize("direction", ["value", "vjp"])
def test_rope_keeps_a_heads_lanes_whole_for_v5e(v5e_devices, direction):
    """``models/layers.py:rope`` at [1, 4, 2048, 128] in bf16, a head of 128
    lanes whose rotate-half pairs lane ``i`` with ``i + 64``: the optimised
    HLO of its value and of its vjp holds no ``concatenate`` and no result
    whose minor dimension is 64 (a head cut or joined at lane 64 is a
    relayout on the chip), and the product with the signed permutation is
    inside a fusion with its multiply-add."""
    from tepdist_tpu.models.layers import rope
    from tools.rope_bench import split_lanes
    x = jax.ShapeDtypeStruct((1, 4, 2048, 128), jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e_devices[0]))

    def value(x):
        return rope(x, 10000.0)

    def vjp(x, g):
        return jax.vjp(value, x)[1](g)[0]

    text = (jax.jit(value).lower(x) if direction == "value"
            else jax.jit(vjp).lower(x, x)).compile().as_text()
    assert split_lanes(text, 64) \
        == {"concatenates": 0, "half_wide_results": 0}
    assert "bf16[1,4,2048,128]" in text
    entry = text[text.index("ENTRY "):]
    assert "convolution(" in text and "convolution(" not in entry, entry


# The OLMoE cell's grouped matmuls: 8192 tokens x 8 experts a token in the
# tile-aligned layout (65536 rows + 64 tiles of pads), 64 experts of
# 2048 x 1024 (gate, up) and 1024 x 2048 (down).
# The Mellum2 cell's: one sequence of 16384 tokens x 8 choices over the 16
# held experts of 2304 x 896 (gate, up) and 896 x 2304 (down), in the worst-
# case layout of 131072 rows + 16 tiles of pads + 1 spare: widths that are
# no power of two (the blocks fall to 768 = 2304 / 3 and 896).
# The size below the worst case that a held share's layout may take on the
# device (``ops/grouped_matmul.py:layout_rows``): Mellum2's 53,504 rows, and
# the Trinity cell's 33,024 and 73,984 over its 32 held experts of 2048 x
# 1024 (``rows`` here leaves out a tile a group).
@pytest.mark.parametrize("K,N,E,rows", [
    (2048, 1024, 64, 65536), (1024, 2048, 64, 65536),
    (2304, 896, 16, 131072 + 256), (896, 2304, 16, 131072 + 256),
    (2304, 896, 16, 53504 - 4096), (896, 2304, 16, 53504 - 4096),
    (2048, 1024, 32, 33024 - 8192), (1024, 2048, 32, 33024 - 8192),
    (2048, 1024, 32, 73984 - 8192), (1024, 2048, 32, 73984 - 8192)])
def test_grouped_matmul_kernels_compile_for_v5e(v5e_devices, K, N, E, rows):
    """Forward, input gradient (weights contracted as stored) and weight
    gradient, not interpreted, at the model's tile."""
    from tepdist_tpu.models.olmoe import CONFIGS
    from tepdist_tpu.ops.pallas import grouped_matmul as gmm
    tile = CONFIGS["1B-7B"].moe_tile_m
    one_chip = SingleDeviceSharding(v5e_devices[0])
    tiles = rows // tile + E

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def three(x, dy, w, tile_group, n_tiles):
        kw = dict(tile_m=tile, interpret=False)
        return (gmm.gmm(x, w, tile_group, n_tiles, **kw),
                gmm.gmm(dy, w, tile_group, n_tiles, transpose_rhs=True,
                        name="tepdist_gmm_dx", **kw),
                gmm.tgmm(x, dy, tile_group, n_tiles, E, **kw))

    text = jax.jit(three).lower(
        sds((tiles * tile, K)), sds((tiles * tile, N)), sds((E, K, N)),
        sds((tiles,), jnp.int32), sds((1,), jnp.int32)).compile().as_text()
    for name in ("tepdist_gmm_fwd", "tepdist_gmm_dx", "tepdist_gmm_dw"):
        assert f"%{name}" in text, name
    assert not [line for line in text.splitlines()    # no transposed weights
                if " copy(" in line and f"= bf16[{E}," in line]


# The stack forms at the two cells that walk the widest expert stacks: ZAYA1's
# five layers of 16 experts of 2048 x 2048 (8,192 rows and a tile's pads an
# expert, tile 128) and Trinity's four of 32 of 2048 x 1024 (the smaller of
# its layout's sizes, tile 256).
@pytest.mark.parametrize("L,E,K,N,tile,rows", [
    (5, 16, 2048, 2048, 128, 8192 + 16 * 128),
    (4, 32, 2048, 1024, 256, 33024)])
def test_grouped_matmul_stack_forms_compile_for_v5e(v5e_devices, L, E, K, N,
                                                    tile, rows):
    """Forward, input gradient and weight gradient over ``[L, E, K, N]``
    and the layer's index, not interpreted: the kernels' weight operand is
    the whole stack, the weight gradient's result is its accumulator's own
    buffer, and nothing beside the three kernels makes an ``[E, K, N]`` or
    an ``[L, E, K, N]`` array (no slice out, no update in, no copy)."""
    from tepdist_tpu.ops.pallas import grouped_matmul as gmm
    one_chip = SingleDeviceSharding(v5e_devices[0])
    tiles = rows // tile

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def three(x, dy, w, into, tile_group, n_tiles, layer):
        kw = dict(tile_m=tile, interpret=False)
        return (gmm.gmm(x, w, tile_group, n_tiles, layer, **kw),
                gmm.gmm(dy, w, tile_group, n_tiles, layer,
                        transpose_rhs=True, name="tepdist_gmm_dx", **kw),
                gmm.tgmm(x, dy, tile_group, n_tiles, E, into, layer, **kw))

    text = jax.jit(three, donate_argnums=3).lower(
        sds((rows, K)), sds((rows, N)), sds((L, E, K, N)),
        sds((L, E, K, N)), sds((tiles,), jnp.int32), sds((1,), jnp.int32),
        sds((1,), jnp.int32)).compile().as_text()
    for name in ("tepdist_gmm_fwd", "tepdist_gmm_dx", "tepdist_gmm_dw"):
        assert f"%{name}" in text, name
    stack = rf"bf16\[{L},{E},\d+,\d+\]"
    made = [line.strip() for line in text.splitlines() if re.search(
        rf"= (?:{stack}|bf16\[{E},\d+,\d+\])\S* (?!parameter\()", line)]
    assert len(made) == 1 and "%tepdist_gmm_dw" in made[0] \
        and "output_to_operand_aliasing" in made[0], made


# The epilogues at the cells' shapes that differ in tiling: OLMoE's whole
# layer (81,920 rows, 64 experts of 2048 x 1024), Mellum2's smaller layout
# (16 of 2304 x 896: a column block 896 wide) and Qwen3-Next's (64 held
# experts of 2048 x 512, the smaller of its layout's sizes); tile 256.
@pytest.mark.parametrize("L", [0, 4], ids=["sliced", "stacked"])
@pytest.mark.parametrize("E,d,f,rows", [
    (64, 2048, 1024, 81920), (16, 2304, 896, 53504), (64, 2048, 512, 32000)])
def test_grouped_matmul_epilogues_compile_for_v5e(v5e_devices, E, d, f, rows,
                                                  L):
    """The up projection with each activation as its epilogue and the input
    gradient that adds another, not interpreted, over ``[E, K, N]`` and
    over ``[L, E, K, N]`` with the layer's index: three kernels under the
    names they had, the addend's buffer the result's, and no operation of
    XLA's on an ``[rows, .]`` array."""
    from tepdist_tpu.ops.pallas import grouped_matmul as gmm
    one_chip = SingleDeviceSharding(v5e_devices[0])
    tile = 256

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def three(x, gate, d_up, dx, row_weight, w, tile_group, n_tiles, layer):
        layer = (layer,) if L else ()
        kw = dict(tile_m=tile, interpret=False)
        return (gmm.gmm(x, w, tile_group, n_tiles, *layer,
                        act=(gate, row_weight), **kw),
                gmm.gmm(x, w, tile_group, n_tiles, *layer,
                        act=(row_weight,), **kw),
                gmm.gmm(d_up, w, tile_group, n_tiles, *layer, add=dx,
                        transpose_rhs=True, name="tepdist_gmm_dx", **kw))

    text = jax.jit(three, donate_argnums=3).lower(
        sds((rows, d)), sds((rows, f)), sds((rows, f)), sds((rows, d)),
        sds((rows, 1), jnp.float32), sds((L, E, d, f)[not L:]),
        sds((rows // tile,), jnp.int32), sds((1,), jnp.int32),
        sds((1,), jnp.int32)).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if " custom-call(" in line
             and "tepdist_gmm_" in line.split(" = ", 1)[0]]
    assert sorted(re.match(r"%(\w+)", c)[1] for c in calls) == [
        "tepdist_gmm_dx", "tepdist_gmm_fwd", "tepdist_gmm_fwd"], calls
    assert ["output_to_operand_aliasing" in c for c in calls
            if "%tepdist_gmm_dx" in c] == [True]
    # The rows' weights go in one to a lane (a bitcast), and nothing else
    # makes an array as wide as the rows' or the result's.
    assert not [line for line in text.splitlines() if re.search(
        rf"= \w+\[{rows},\d\d+\]\S* (?!parameter\(|custom-call\()", line)]
    assert not re.search(rf"= f32\[{rows},1\]\S* copy\(", text)


def test_a_held_share_with_its_switch_compiles_for_v5e(v5e_devices,
                                                       monkeypatch):
    """``routed_experts`` at the Mellum2 cell's shapes (16,384 tokens, 16 of
    64 experts held, 2304 x 896), forward and gradient, kernels not
    interpreted: one ``conditional`` of two branches each way, the three
    kernels at both of the ladder's row counts, and the untaken size's
    residual slots filled by nothing."""
    from tepdist_tpu.ops import grouped_matmul as gm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    S, k, E, G, d, f, tile = 16384, 8, 64, 16, 2304, 896, 256
    sizes = gm.layout_rows(S, k, G, E, tile)
    assert sizes == (53504, 135424)
    one_chip = SingleDeviceSharding(v5e_devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(h, weights, experts, w_gate, w_up, w_down):
        y = gm.routed_experts(h, weights, experts, w_gate, w_up, w_down, E,
                              tile, held=(16, G))
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
        sds((S, d)), sds((S, k), jnp.float32), sds((S, k), jnp.int32),
        sds((G, d, f)), sds((G, d, f)), sds((G, f, d))).compile().as_text()
    branches = re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}",
                          text)
    assert [len(b.split(",")) for b in branches] == [2, 2], branches
    for name in ("tepdist_gmm_fwd", "tepdist_gmm_dx", "tepdist_gmm_dw"):
        calls = [line for line in text.splitlines()
                 if " custom-call(" in line
                 and name in line.split(" = ", 1)[0]]
        for rows in sizes:
            assert any(f"bf16[{rows}," in c for c in calls), (name, rows)
    # No zeros are written for the size not taken: its wide residuals are
    # results of the kernel that writes nothing.
    wide = re.compile(r"= bf16\[(53504|135424),\d+\]\S* broadcast\(")
    assert not wide.findall(text)
    assert "tepdist_unwritten" in text


# The rows out of the layout at the three expert cells' shapes: OLMoE's whole
# layer (81,920 rows of 2048), and both sizes a held share's layout may take
# in Trinity (2048 wide, 8,192 tokens) and in Mellum2 (2304 wide, a row
# padded to 24 x 128, 16,384 tokens); 8 choices a token.
@pytest.mark.parametrize("M,d,S", [
    (81920, 2048, 8192), (33024, 2048, 8192), (73984, 2048, 8192),
    (53504, 2304, 16384), (135424, 2304, 16384)])
def test_rows_sum_kernels_compile_for_v5e(v5e_devices, M, d, S):
    """The relayout of the live rows and the row-copy sum, not interpreted:
    a row copied by its leading index out of ``[M, r, 128]``, and no XLA
    copy, pad or gather of a layout-sized array beside the two kernels."""
    from tepdist_tpu.ops.pallas.rows_sum import rows_sum
    one_chip = SingleDeviceSharding(v5e_devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lambda y, dest, bound: rows_sum(
        y, dest, bound, interpret=False)).lower(
            sds((M, d), jnp.bfloat16), sds((S, 8), jnp.int32),
            sds((1,), jnp.int32)).compile().as_text()
    r = -(-d // 1024) * 8
    calls = {name: line for line in text.splitlines()
             for name in ("tepdist_rows_tiled", "tepdist_rows_sum")
             if " custom-call(" in line
             and line.strip().removeprefix("ROOT ").startswith(f"%{name}")}
    assert f"= bf16[{M},{r},128]" in calls["tepdist_rows_tiled"]
    assert f"= bf16[{S},{d}]" in calls["tepdist_rows_sum"]
    assert not re.findall(r"= bf16\[\d+,[\d,]+\]\S* (?:copy|pad|gather|fusion)\(",
                          text)


def test_flash_bf16_compiles_under_highest_matmul_precision(v5e_devices):
    """A global ``jax_default_matmul_precision`` must not reach the bf16
    kernels: Mosaic refuses a float32-precision matmul on bf16 operands."""
    one_chip = SingleDeviceSharding(v5e_devices[0])
    x = jax.ShapeDtypeStruct((3, 25, 1024, 64), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=False)
                       .astype(jnp.float32))

    with jax.default_matmul_precision("highest"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile().as_text()
    assert len(_flash_call_shapes(text)) == 2


def test_planned_step_with_kernel_in_scan_compiles_for_mesh(v5e_devices):
    """The SPMD path over four chips: XLA refuses to partition a Mosaic
    kernel ("wrap the call in a shard_map"), so SpmdTransform binds every
    pallas_call under one — including those inside the gradient-
    accumulation scan, which only a re-trace of the scan body reaches."""
    from tepdist_tpu.core.mesh import MeshTopology
    from tepdist_tpu.parallel.auto_parallel import auto_parallel
    from tepdist_tpu.parallel.sync_free import build_ga_step

    B, H, T, D = 4, 2, 128, 64
    tx = optax.sgd(0.1)

    def loss_fn(w, x):
        q = (x @ w).reshape(-1, T, H, D).transpose(0, 2, 1, 3)
        return jnp.mean(flash_attention(q, q, q, interpret=False)
                        .astype(jnp.float32) ** 2)

    def apply_fn(w, s, g):
        updates, s = tx.update(g, s, w)
        return optax.apply_updates(w, updates), s

    w = jnp.ones((H * D, H * D), jnp.bfloat16)
    x = jnp.ones((B, T, H * D), jnp.bfloat16)
    step = build_ga_step(lambda w, x: jax.value_and_grad(loss_fn)(w, x),
                         apply_fn, num_micro_batches=2)
    plan = auto_parallel(step, MeshTopology([("data", 4)]), w, tx.init(w), x)
    fn = plan.executable(devices=v5e_devices)
    args = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
            for v in plan.graph.invars]
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


def test_ga_step_accumulates_in_place_at_the_1p5b_cells_shapes(v5e_devices):
    """``gpt2-1.5b.train.b48``'s step (48 sequences as 16 micro batches, 48
    stacked layers, full remat, ``adamw_bf16``) with the blocks' gradients
    accumulated inside the backward layer loop: the compiler's peak falls
    by most of one stacked gradient (2.95e9 bytes; 2.45e9 read) against the
    tree-wide add, so the carried accumulator is updated in place, and it
    copies no whole stack inside a loop."""
    import functools

    from tepdist_tpu.models import gpt2
    from tepdist_tpu.optim import make_optimizer
    from tepdist_tpu.parallel.sync_free import build_ga_step

    cfg = dataclasses.replace(
        gpt2.CONFIGS["1.5B"], dtype=jnp.bfloat16, attn="flash", remat=True,
        loss_chunk=512)
    attn = functools.partial(flash_attention, block_q=512, block_k=512,
                             interpret=False)
    tx = make_optimizer({"name": "adamw_bf16", "learning_rate": 1e-4})

    def loss(p, t):
        return gpt2.loss_fn_stacked(p, t, cfg, attn)

    def apply_fn(p, s, g):
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    one_chip = SingleDeviceSharding(v5e_devices[0])
    params = jax.eval_shape(
        lambda: gpt2.stacked_init_params(cfg, jax.random.PRNGKey(0)))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, jax.eval_shape(tx.init, params),
         jax.ShapeDtypeStruct((48, 1025), jnp.int32)))

    def compiled(**kwargs):
        step = build_ga_step(lambda p, t: jax.value_and_grad(loss)(p, t),
                             apply_fn, 16, **kwargs)
        return jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()

    fused, tree_add = compiled(loss_fn=loss), compiled()
    saved = (tree_add.memory_analysis().peak_memory_in_bytes
             - fused.memory_analysis().peak_memory_in_bytes)
    assert saved >= 2.0e9, saved
    entry = fused.as_text().split("\nENTRY ", 1)
    assert not re.findall(r"= \w+\[48,[\d,]+\]\S* copy\(", entry[0])


def test_a_walked_block_keeps_its_flash_forward_in_the_compiled_step(
        v5e_devices, monkeypatch):
    """A gradient-accumulation step over a stack of window, global, window
    layers (Mellum2's kinds at a reduced width, its head size, kernels not
    interpreted): the compiled step holds each kind's forward kernel once
    (the layer loop's; the backward loop's recomputation runs none), its one
    backward kernel once and no dQ kernel, and the kept ``o`` rides the
    walk's stack at the kernel's own shape, a layer a slot."""
    from tepdist_tpu.models import mellum
    from tepdist_tpu.parallel.sync_free import build_ga_step
    from tepdist_tpu.telemetry import metrics
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        mellum.CONFIGS["test"], hidden_size=256, head_dim=128,
        num_attention_heads=4, num_key_value_heads=2, sliding_window=512,
        moe_intermediate_size=128, moe_tile_m=128, dtype=jnp.bfloat16,
        flash_block_q=512, flash_block_k=512, remat=True, loss_chunk=512)
    T, micro = 1024, 2
    tx = optax.sgd(1e-3)

    def loss(p, t):
        return mellum.loss_fn(p, t, cfg)

    def apply_fn(p, s, g):
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    one_chip = SingleDeviceSharding(v5e_devices[0])
    params = jax.eval_shape(
        lambda: mellum.stacked_init_params(cfg, jax.random.PRNGKey(0)))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, jax.eval_shape(tx.init, params),
         jax.ShapeDtypeStruct((micro, T + 1), jnp.int32)))
    step = build_ga_step(lambda p, t: jax.value_and_grad(loss)(p, t),
                         apply_fn, micro, loss_fn=loss)
    text = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile() \
        .as_text()
    assert metrics().gauge("attn_kept_calls").value == 3
    assert metrics().gauge("flash_bwd_calls").value == 3
    assert metrics().gauge("attn_kept_bytes").value \
        == 3 * 4 * T * (128 * 2 + 4)
    # Each layer holds a share of the experts: its rows out of the layout
    # go through the row-copy kernel, forward and in ``dispatch``'s backward.
    assert metrics().gauge("moe_rows_sum_calls").value == 2 * 3
    assert "tepdist_rows_sum" in text and "tepdist_rows_tiled" in text
    # ... and its kernels read the experts out of the stack through both
    # sizes' branches of the ``switch``: every weight gradient's result is
    # its accumulator's stack, and no update and no copy makes one (a
    # conditional that handed a stack on by copying it would).
    assert metrics().gauge("moe_stack_in_place_calls").value == 12 * 3
    stack = r" = bf16\[3,4,(?:256,128|128,256)\]\S* "
    made = [line.split(" = ", 1)[0].strip() for line in text.splitlines()
            if re.search(stack + r"(?:custom-call|copy|dynamic-update-slice)"
                         r"\(", line)]
    assert len(made) == 6 and all("tepdist_gmm_dw" in m for m in made), made
    calls = [line.split(" = ", 1)[0] for line in text.splitlines()
             if " custom-call(" in line and "tepdist_flash_" in line]
    for which in ("fwd", "dkv"):
        kinds = sorted(re.sub(r".*(__h4(__w512)?__kv2).*", r"\1", c)
                       for c in calls if f"tepdist_flash_{which}__" in c)
        assert kinds == ["__h4__kv2", "__h4__w512__kv2"], (which, calls)
    assert not [c for c in calls if "tepdist_flash_dq" in c], calls
    assert f"bf16[3,1,4,{T},128]" in text


def test_the_jamba_cells_step_compiles_for_v5e(v5e_devices):
    """``jamba2-3b.train.s8192``'s step from the cell's own files (4 micro
    batches of one 8192-token sequence; Mamba x 7, attention, Mamba x 6 as
    three walks; ``adamw_bf16``), kernels not interpreted: every walk's
    leaves accumulate inside the backward layer loop, the attention layer's
    flash forward is kept, the scan's and the conv's forward run twice a
    Mamba layer and micro batch, the scan is in its kernels and nowhere as a
    whole-sequence array, the conv is in its kernels with no padded float32
    copy of its input, and the compiler's peak fits the chip, not above what
    the ``jax.numpy`` conv compiled to (14.63e9)."""
    from tepdist_tpu.telemetry import metrics
    from tools.same_ops import compiled_step
    compiled, params = compiled_step("jamba2-3b.train.s8192", v5e_devices[0])

    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    fused, unfused = gauge("ga_fused_bytes"), gauge("ga_unfused_bytes")
    stacks = sum(a.size * a.dtype.itemsize for name, run in params.items()
                 if name not in ("tok_emb", "norm_f")
                 for a in jax.tree_util.tree_leaves(run))
    assert fused == stacks and 2 * 1_430_781_376 < stacks < 2.87e9
    assert fused / (fused + unfused) == pytest.approx(0.9715, abs=5e-4)
    assert gauge("attn_kept_calls") == 1 and gauge("flash_bwd_calls") == 1
    assert gauge("attn_kept_bytes") == 20 * 8192 * (128 * 2 + 4)
    assert gauge("ssm_scan_calls") == 26
    assert gauge("ssm_conv_calls") == 26
    assert gauge("moe_rows_sum_calls") == 0                # no expert layer
    assert gauge("ssm_boundary_bytes") == 128 * 16 * 5120 * 4

    text = compiled.as_text()
    calls = [line.split(" = ", 1)[0].strip() for line in text.splitlines()
             if " custom-call(" in line]
    # A walk of 7 and a walk of 6: each the forward in the walk and in its
    # recomputation, and the backward; the attention layer's two kernels,
    # the forward once.
    assert sum("tepdist_ssm_fwd" in c for c in calls) == 4, calls
    assert sum("tepdist_ssm_bwd" in c for c in calls) == 2, calls
    assert sum("tepdist_conv_fwd" in c for c in calls) == 4, calls
    assert sum("tepdist_conv_bwd" in c for c in calls) == 2, calls
    for which in ("fwd", "dkv"):
        names = [c for c in calls if f"tepdist_flash_{which}__" in c]
        assert len(names) == 1 and "__h20__kv1" in names[0], (which, calls)
    assert not [c for c in calls if "tepdist_flash_dq" in c], calls
    # No array of the step has the sequence, the channels and the states
    # together: no whole-sequence scan.
    shapes = set(re.findall(r"\w+\[([\d,]+)\]", text))
    whole = [s for s in shapes
             if {"8192", "5120", "16"} <= set(s.split(","))]
    assert not whole, whole
    assert "f32[1,8195,5120]" not in text
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 12.5e9 < peak <= 14.63e9, peak


# The MiniCPM-SALA cell's two mechanisms at its tiling: 32 heads of 128 over
# 32,768 positions, the linear attention at its chunk of 256, the block
# top-k attention over 2 key/value groups with a group's keys, values and
# float32 gradients resident in VMEM (16 + 32 MiB).
def test_sala_kernels_compile_for_v5e(v5e_devices):
    """Forward and backward of the linear-attention and of the block top-k
    attention kernels, and the choice beside them, not interpreted: five
    kernels under their names, the lightning operands read in the
    projections' own ``[T, heads * D]`` layout (no copy around a call), and
    no ``[T, T]`` array anywhere."""
    from tepdist_tpu.ops.pallas import block_topk_attention as bt
    from tepdist_tpu.ops.pallas.lightning_attention import lightning_attention
    one_chip = SingleDeviceSharding(v5e_devices[0])
    T, H, G, D = 32768, 32, 2, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def calls_of(text):
        return [line.split(" = ", 1)[0].strip() for line in text.splitlines()
                if " custom-call(" in line]

    def lightning(q, k, v, ld, do):
        out, vjp = jax.vjp(lambda q, k, v: lightning_attention(
            q, k, v, ld, interpret=False), q, k, v)
        return (out,) + vjp(do)

    x = sds((1, T, H * D), jnp.bfloat16)
    compiled = jax.jit(lightning).lower(
        x, x, x, sds((H,), jnp.float32), x).compile()
    names = calls_of(compiled.as_text())
    for kernel in ("tepdist_lightning_fwd", "tepdist_lightning_bwd_dq",
                   "tepdist_lightning_bwd_dkv"):
        assert sum(kernel in n for n in names) == 1, names
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20

    geo = bt.BlockGeometry()

    def sparse(q, k, v, do):
        idx = bt.select_blocks(q, k, geo)
        out, vjp = jax.vjp(lambda q, k, v: bt.topk_attention(
            q, k, v, idx, geo, interpret=False), q, k, v)
        return (out,) + vjp(do)

    q, kv = sds((1, T, H, D), jnp.bfloat16), sds((1, T, G, D), jnp.bfloat16)
    text = jax.jit(sparse).lower(q, kv, kv, q).compile().as_text()
    names = calls_of(text)
    for kernel in ("tepdist_topk_attn_fwd", "tepdist_topk_attn_bwd"):
        assert sum(kernel in n for n in names) == 1, names
    assert f"s32[1,{G},{T},64]" in text or f"s32[{G * T * 64}]" in text
    shapes = set(re.findall(r"\w+\[([\d,]+)\]", text))
    square = [s for s in shapes if s.split(",").count(str(T)) > 1]
    assert not square, square


def test_the_minicpm_sala_cells_step_compiles_for_v5e(v5e_devices):
    """``minicpm-sala.train.s32768``'s step from the cell's own files (2
    micro batches of one 32,768-token sequence; a sparse layer and three
    lightning layers as two walks; ``adamw_bf16``), kernels not interpreted:
    both walks' leaves accumulate inside the backward layer loop, the
    linear-attention kernel's forward runs twice a layer and micro batch and
    the block top-k attention's once (the walk keeps its ``(o, lse)`` and
    the chosen sets: two hand-overs, 289,406,976 bytes a micro batch), the
    sparse layer visits chosen blocks (no plain causal flash call, no ``[T,
    T]`` array), no array is as wide as ``[T, intermediate]`` (the block's
    token-wise parts run in chunks), and the compiler's peak fits the
    chip."""
    from tepdist_tpu.telemetry import metrics
    from tools.same_ops import compiled_step
    T = 32768
    compiled, params = compiled_step(
        "minicpm-sala.train.s32768", v5e_devices[0])

    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    fused, unfused = gauge("ga_fused_bytes"), gauge("ga_unfused_bytes")
    stacks = sum(a.size * a.dtype.itemsize for name, run in params.items()
                 if name not in ("tok_emb", "lm_head", "norm_f")
                 for a in jax.tree_util.tree_leaves(run))
    assert fused == stacks and 2 * 1_109_393_408 < stacks < 2.22e9
    assert fused / (fused + unfused) == pytest.approx(0.8802, abs=5e-4)
    assert gauge("lin_attn_calls") == 6         # 3 layers, each twice
    assert gauge("topk_attn_calls") == 1        # kept: not run again
    assert gauge("topk_attn_dense_calls") == 0
    assert gauge("topk_attn_keys_per_query") == 3812.5
    # The sparse layer's two hand-overs: o bf16 [1, T, 32, 128] with lse
    # float32 [1, 2, 16, T], and the sets int32 [1, 2, T, 64].
    assert gauge("attn_kept_calls") == 2 and gauge("ssm_scan_calls") == 0
    assert gauge("flash_bwd_calls") == 0
    assert gauge("attn_kept_bytes") == T * (32 * 128 * 2 + 32 * 4) \
        + 2 * T * 64 * 4 == 289_406_976

    text = compiled.as_text()
    calls = [line.split(" = ", 1)[0].strip() for line in text.splitlines()
             if " custom-call(" in line]
    for kernel, times in (("tepdist_lightning_fwd", 2),
                          ("tepdist_lightning_bwd_dq", 1),
                          ("tepdist_lightning_bwd_dkv", 1),
                          ("tepdist_topk_attn_fwd", 1),
                          ("tepdist_topk_attn_bwd", 1)):
        assert sum(kernel in c for c in calls) == times, (kernel, calls)
    assert not [c for c in calls if "tepdist_flash_" in c], calls
    shapes = set(re.findall(r"\w+\[([\d,]+)\]", text))
    wide = [s for s in shapes if s.split(",").count(str(T)) > 1
            or {str(T), "16384"} <= set(s.split(","))]
    assert not wide, wide
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 12.5e9 < peak < 15.5e9, peak


# The sarvam-105b cell's attention: a rank's 16 heads of latent attention at
# 16,384 positions, 128 + 64 query/key channels (the 64 one shared rotary
# key) and 128 value channels, 512 tiles. The three whole-sequence operands
# of a grid step are 24 MiB double-buffered: the calls ask for their scoped
# VMEM (``mla_attention._vmem``), the backward also for what it holds beside
# them (``_bwd_holds``).
def test_mla_kernels_compile_for_v5e(v5e_devices):
    """Forward and the one backward kernel (via jax.grad), not interpreted:
    two kernels under their own names and no ``tepdist_mla_dq``, ``k_rope``
    at one head a batch row and no key or value wider than published in any
    operand, no row statistic padded 128-fold, and no ``[T, T]`` array. The
    forward asks for 40 MiB of scoped VMEM; the backward for 68: the three
    whole-sequence operands 24, the head's two dQ^T in float32 8 + 4, its
    two dQ result blocks double-buffered 8 + 8 (a ``Dr`` of 64 fills a
    128-lane tile), 16 for the rest."""
    from tepdist_tpu.ops.pallas.mla_attention import (_bwd_holds, _vmem,
                                                      mla_attention)
    T, H = 16384, 16
    assert _vmem(T, 2).vmem_limit_bytes == 40 * 2 ** 20
    assert _bwd_holds(T, 128, 64, 2) == 28 * 2 ** 20
    assert _vmem(T, 2, _bwd_holds(T, 128, 64, 2)).vmem_limit_bytes \
        == 68 * 2 ** 20
    assert _vmem(4096, 2) is None
    assert _vmem(512, 2, _bwd_holds(512, 128, 64, 2)) is None
    one_chip = SingleDeviceSharding(v5e_devices[0])

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(*operands):
        return jnp.sum(mla_attention(
            *operands, scale=0.1352, block_q=512, block_k=512,
            interpret=False).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        sds(1, H, T, 128), sds(1, H, T, 64), sds(1, H, T, 128),
        sds(1, 1, T, 64), sds(1, H, T, 128)).compile().as_text()
    assert "tepdist_mla_dq" not in text
    calls = [line.strip() for line in text.splitlines()
             if " custom-call(" in line and "tepdist_mla_" in line]
    assert len(calls) == 2, calls
    asked = {}
    for which in ("fwd", "dkv"):
        call = next(c for c in calls
                    if f"tepdist_mla_{which}__c1__s0.1352__h16" in c)
        listed = call.split("operand_layout_constraints={", 1)[1]
        assert listed.startswith(
            f"bf16[{H},{T},128]{{2,1,0}}, bf16[{H},{T},64]{{2,1,0}}, "
            f"bf16[{H},{T},128]{{2,1,0}}, bf16[1,{T},64]{{2,1,0}}, "
            f"bf16[{H},{T},128]{{2,1,0}}"), call
        assert "192]" not in call and not _PADDED_ROWS.search(call), call
        asked[which] = int(re.search(
            r'scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
            r'"size":"(\d+)"', call).group(1))
    assert asked == {"fwd": 40 * 2 ** 20, "dkv": 68 * 2 ** 20}, asked
    # dq_nope, dq_rope, dk_nope, a head's part of dk_rope and dv, each at
    # its operand's width in bf16: no float32 dQ crosses HBM.
    results = next(c for c in calls if "tepdist_mla_dkv" in c).partition(
        " custom-call(")[0]
    assert re.findall(r"\w+\[[\d,]+\]", results) == [
        f"bf16[{H},{T},{D}]" for D in (128, 64, 128, 64, 128)], results
    assert "tepdist_flash_" not in text
    shapes = set(re.findall(r"\w+\[([\d,]+)\]", text))
    square = [s for s in shapes if s.split(",").count(str(T)) > 1]
    assert not square, square


def test_the_sarvam_cells_step_compiles_for_v5e(v5e_devices):
    """``sarvam-105b.train.s16384``'s step from the cell's own files (4
    micro batches of one 16,384-token sequence; a dense layer and four
    expert layers as two walks; ``adamw_bf16_router_bias``), kernels not
    interpreted: both walks' leaves accumulate inside the backward layer
    loop, the latent-attention forward runs once a layer and micro batch
    (the walk keeps its ``(o, lse)``: 5 hand-overs, 340,787,200 bytes a
    micro batch), each layer's backward pass is the one ``tepdist_mla_dkv``
    call (``mla_bwd_calls`` 5, no ``tepdist_mla_dq``), no array is as wide
    as ``[T, intermediate]`` or ``[T, T]``
    and none holds the whole sequence's worst-case expert layout (the
    block's token-wise parts run in chunks), and the compiler's peak fits
    the chip. The issue asked for a peak under 15.5e9 bytes: the
    accumulation scan's entry alone holds the state, the zeroed accumulators
    and the loop's own, 10 bytes a parameter, 15.05e9."""
    from tepdist_tpu.telemetry import metrics
    from tools.same_ops import compiled_step
    T = 16384
    compiled, params = compiled_step(
        "sarvam-105b.train.s16384", v5e_devices[0])

    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n_params == 1_505_016_832
    fused, unfused = gauge("ga_fused_bytes"), gauge("ga_unfused_bytes")
    stacks = sum(a.size * a.dtype.itemsize for name in ("dense", "blocks")
                 for a in jax.tree_util.tree_leaves(params[name]))
    assert fused == stacks == 2_473_242_624
    assert unfused == 2 * 32768 * 4096 * 2 + 4096 * 4
    assert gauge("mla_fwd_calls") == 5          # kept: not run again
    assert gauge("mla_bwd_calls") == 5          # each one kernel
    assert gauge("attn_kept_calls") == 5
    assert gauge("attn_kept_bytes") == 5 * 16 * T * (128 * 2 + 4) \
        == 340_787_200
    assert gauge("mla_heads_held") == 16
    assert gauge("mla_latent_bytes") == T * (512 + 64) * 2
    assert gauge("moe_rows_sum_calls") == 8     # 2 a walked expert layer
    # 12 a walked expert layer, a chunk's trace standing for its 8 chunks.
    assert gauge("moe_stack_in_place_calls") == 4 * 12

    text = compiled.as_text()
    calls = [line.split(" = ", 1)[0].strip() for line in text.splitlines()
             if " custom-call(" in line]
    # A walk of one and a walk of four: the forward in each walk's forward
    # loop alone, the one backward kernel in its backward loop.
    for which in ("fwd", "dkv"):
        names = [c for c in calls if f"tepdist_mla_{which}__" in c]
        assert len(names) == 2 and all("__h16" in n for n in names), calls
    assert not [c for c in calls if "tepdist_mla_dq" in c], calls
    assert not [c for c in calls if "tepdist_flash_" in c], calls
    assert [c for c in calls if "tepdist_gmm_fwd" in c], calls
    shapes = set(re.findall(r"\w+\[([\d,]+)\]", text))
    wide = [s for s in shapes if s.split(",").count(str(T)) > 1
            or {str(T), "8192"} <= set(s.split(","))]
    assert not wide, wide
    # A chunk of 2,048 tokens: the worst-case layout is its own 16,384
    # choices and 9 tiles of 128, never the sequence's 131,072.
    assert "[17536,4096]" in text
    assert not [s for s in shapes if s.split(",")[0] in ("132224", "133376")]
    # The experts' stacks where they lie (PR 51): every grouped matmul takes
    # the ``[4, 8, K, N]`` stack, no array of one layer's experts is left
    # anywhere (no slice out of a stack, no update into one, no chunk's
    # weight gradient summed into a loop's carry), and a whole stack is the
    # result of the weight-gradient kernels (three a layout size), of the
    # optimizer and of the accumulators' zero fill, never of a copy.
    stack = r"bf16\[4,8,(?:4096,2048|2048,4096)\]"
    gmm = [line for line in text.splitlines() if " custom-call(" in line
           and "tepdist_gmm_" in line.split(" = ", 1)[0]]
    assert gmm and all(re.search(stack, line) for line in gmm)
    assert not re.search(r"bf16\[(?:1,)?8,(?:4096,2048|2048,4096)\]", text)
    makers = collections.Counter(re.findall(
        rf"= {stack}\S* ([\w\-]+)\(", text))
    assert makers["custom-call"] == 6 and not set(makers) - {
        "custom-call", "fusion", "broadcast", "convert", "parameter",
        "get-tuple-element"}, makers
    peak = compiled.memory_analysis().peak_memory_in_bytes
    # What the described chip reports since the chunk loop carries the
    # accumulators (PR 51: 15,178,642,944, from PR 46's 15,973,381,120):
    # three chunk gradients, three loop carries and the slices of a layer's
    # experts are no longer live beside a layer's backward. The optimizer's
    # 10 bytes a parameter stay under it.
    assert 10 * n_params < peak <= 15_178_642_944, peak


def test_cca_mix_kernels_compile_for_v5e(v5e_devices):
    """The compressed attention's mixing kernels at the zaya1-8b cell's shape
    (one 8,192-token sequence, 8 query and 2 key heads of 128 in bf16, blocks
    of 1,024 rows), not interpreted: two kernels under their own names, the
    latents token-major and the results head-major in their operands, the
    latents' gradients in bf16 and the weights' sums in float32 (three
    ``[128, 128]`` and four ``[128]`` a head), no padded and no float32 copy
    of a latent anywhere in the program."""
    from tepdist_tpu.ops.pallas.cca_mix import cca_mix
    T, H, Hkv, D = 8192, 8, 2, 128
    N = H + Hkv
    one_chip = SingleDeviceSharding(v5e_devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(*operands):
        q, k = cca_mix(*operands, interpret=False)
        return jnp.sum(q.astype(jnp.float32)) + jnp.sum(k.astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)))).lower(
        sds((1, T, H * D)), sds((1, T, Hkv * D)),
        sds((2, N * D), jnp.float32), sds((N * D,), jnp.float32),
        sds((2, N, D, D)), sds((N * D,), jnp.float32)).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if " custom-call(" in line and "tepdist_cca_mix_" in line]
    assert len(calls) == 2, calls
    fwd = next(c for c in calls if "tepdist_cca_mix_fwd" in c)
    bwd = next(c for c in calls if "tepdist_cca_mix_bwd" in c)
    latents = f"bf16[1,{T},{H * D}]{{2,1,0}}, bf16[1,{T},{Hkv * D}]{{2,1,0}}"
    for call in calls:
        assert call.split("operand_layout_constraints={", 1)[1].startswith(
            latents), call
    assert re.findall(r"\w+\[[\d,]+\]", fwd.partition(" custom-call(")[0]) \
        == [f"bf16[1,{H},{T},{D}]", f"bf16[1,{Hkv},{T},{D}]"], fwd
    assert re.findall(r"\w+\[[\d,]+\]", bwd.partition(" custom-call(")[0]) \
        == [f"bf16[1,{T},{H * D}]", f"bf16[1,{T},{Hkv * D}]",
            f"f32[1,{H},3,{D},{D}]", f"f32[1,{Hkv},3,{D},{D}]",
            f"f32[1,{H},4,{D}]", f"f32[1,{Hkv},4,{D}]"], bwd
    wide = set(re.findall(rf"f32\[1,{T},(?:{H * D}|{N * D}|{Hkv * D})\]",
                          text))
    assert not wide, wide


def test_the_zaya_cells_step_compiles_for_v5e(v5e_devices):
    """``zaya1-8b.train.s8192``'s step from the cell's own files (8 micro
    batches of one 8,192-token sequence; five layers in one walk whose carry
    is the pair ``(x, r)``; ``adamw_bf16_router_bias``), kernels not
    interpreted: the walk's leaves accumulate inside the backward layer
    loop, the flash forward at 8 heads over 2 runs once a layer and micro
    batch (the walk keeps its ``(o, lse)``), the mixing's forward twice (a
    walked block recomputes it: ``cca_mix_calls`` 10), the top-1 layout has
    its one size of 10,240 rows, and the compiler's peak is under 13e9
    bytes."""
    from tepdist_tpu.telemetry import metrics
    from tools.same_ops import compiled_step
    T = 8192
    compiled, params = compiled_step("zaya1-8b.train.s8192", v5e_devices[0])

    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n_params == 1_104_975_450
    stack = sum(a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(params["blocks"]))
    assert gauge("ga_fused_bytes") == stack
    assert gauge("ga_unfused_bytes") == 32784 * 2048 * 2 + 2048 * 4
    assert gauge("cca_mix_calls") == 10         # a walked block's, twice
    assert gauge("attn_kept_calls") == 5        # kept: not run again
    assert gauge("attn_kept_bytes") == 5 * 8 * T * (128 * 2 + 4)
    assert gauge("cca_latent_bytes") == T * (1024 + 256 + 256) * 2
    assert gauge("router_carry_bytes") == T * 256 * 4
    assert gauge("moe_top1_rows") == 10240
    assert gauge("moe_rows_sum_calls") == 0     # every expert is resident
    assert gauge("moe_stack_in_place_calls") == 5 * 12

    text = compiled.as_text()
    calls = [line.split(" = ", 1)[0].strip() for line in text.splitlines()
             if " custom-call(" in line]
    # The experts' weights are read and summed where they lie: nothing in
    # the step makes one layer's [16, 2048, 2048] (no slice out of a stack,
    # no gradient on its way in), and of the kernels only the weight
    # gradient's result is a whole stack, its accumulator's own buffer.
    made = [line.split(" = ", 1) for line in text.splitlines()
            if re.search(r" = bf16\[(?:1,)?16,2048,2048\]\S* (?!parameter)",
                         line)]
    assert not made, made[:3]
    whole = [c for c in calls if re.search(
        rf"{re.escape(c)} = bf16\[5,16,2048,2048\]", text)]
    assert len(whole) == 3 and all("tepdist_gmm_dw" in c for c in whole), \
        whole
    # One walk: the mixing's forward in its forward loop and in the backward
    # loop's recomputation, the flash forward in the forward loop alone.
    assert len([c for c in calls if "tepdist_cca_mix_fwd" in c]) == 2, calls
    assert len([c for c in calls if "tepdist_cca_mix_bwd" in c]) == 1, calls
    for which in ("fwd", "dkv"):
        names = [c for c in calls if f"tepdist_flash_{which}__" in c]
        assert len(names) == 1 and "__h8" in names[0] \
            and "__kv2" in names[0], calls
    assert not [c for c in calls if "tepdist_flash_dq" in c], calls
    assert [c for c in calls if "tepdist_gmm_" in c], calls
    # The router's state rides in float32 beside x, a layer's kept input.
    assert f"f32[5,1,{T},256]" in text and f"bf16[5,1,{T},2048]" in text
    assert compiled.memory_analysis().peak_memory_in_bytes < 13e9
