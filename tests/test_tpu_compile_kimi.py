"""Compile the Kimi Linear cell's kernels and its whole step for a DESCRIBED
v5e (no chip): ``tests/test_tpu_compile.py``'s cases for
``ops/pallas/kda_attention.py`` and ``kimi-linear-48b-a3b.train.s8192``, in a
file of their own so that a worker other than that file's takes them (the
suite is dealt out a file at a time). A compile that passes is NOT a chip
run: nothing executes here."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding
from test_tpu_compile import v5e_devices  # noqa: F401 — the fixture


def test_kda_kernels_compile_for_v5e(v5e_devices):
    """Forward and backward of the delta-rule kernels at the Kimi Linear
    cell's ``[1, 8192, 32 x 128]``, chunks of 64 and of 128, not
    interpreted: two kernels under their names (the differentiated forward
    writes the states before every chunk and the chunks' inverses as its
    second and third result, so no sweep makes them again), the operands
    read in the projections' own ``[T, heads * K]`` layout and ``beta`` as
    ``[T, heads]`` (no copy around a call), and nothing held but that pair."""
    from tepdist_tpu.ops.pallas.kda_attention import kda_attention
    one_chip = SingleDeviceSharding(v5e_devices[0])
    T, H, K = 8192, 32, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x = sds((1, T, H * K), jnp.bfloat16)
    for chunk in (64, 128):
        def both(q, k, v, g, beta, do, chunk=chunk):
            out, vjp = jax.vjp(lambda *a: kda_attention(
                *a, chunk=chunk, interpret=False), q, k, v, g, beta)
            return (out,) + vjp(do)

        compiled = jax.jit(both).lower(
            x, x, x, sds((1, T, H * K), jnp.float32),
            sds((1, T, H), jnp.float32), x).compile()
        text = compiled.as_text()
        names = [line.split(" = ", 1)[0].strip()
                 for line in text.splitlines() if " custom-call(" in line]
        for kernel in ("tepdist_kda_fwd", "tepdist_kda_bwd"):
            assert sum(kernel in n for n in names) == 1, names
        assert not [n for n in names if "tepdist_kda_bwd_states" in n]
        assert f"f32[1,{T // chunk},{H},{K},{K}]" in text
        assert f"f32[1,{T // chunk},{H},{chunk},{chunk}]" in text
        # (An inverse of 64 columns is tiled to the 128 lanes.)
        pair = T // chunk * H * (K * K + chunk * max(chunk, 128)) * 4
        assert compiled.memory_analysis().temp_size_in_bytes < pair + 2 ** 20
        wide = [line for line in text.splitlines()
                if f"{T},{H * K}]" in line.split(" = ", 1)[-1][:60]
                and (" copy(" in line or " transpose(" in line)]
        assert not wide, wide[:2]


def test_the_kimi_linear_cells_step_compiles_for_v5e(v5e_devices):
    """``kimi-linear-48b-a3b.train.s8192``'s step from the cell's own files
    (8 micro batches of one 8,192-token sequence; five layers in four walks
    of unequal shape; ``adamw_bf16_router_bias``), kernels not interpreted:
    every walk's leaves accumulate inside its backward layer loop, the
    delta rule's forward and the latent layer's run once a layer and micro
    batch (the walks keep ``(o, states, inv)`` and ``(o, lse)``:
    ``kda_calls`` 4), the experts' stacks are read where they lie in all
    three expert runs, and the compiler's peak is under 13e9 bytes."""
    from tepdist_tpu.telemetry import metrics
    from tools.same_ops import compiled_step
    T = 8192
    compiled, params = compiled_step(
        "kimi-linear-48b-a3b.train.s8192", v5e_devices[0])

    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n_params == 828_926_848
    stacks = sum(a.size * a.dtype.itemsize for r in range(4)
                 for a in jax.tree_util.tree_leaves(params[f"run{r}"]))
    assert gauge("ga_fused_bytes") == stacks
    assert gauge("ga_unfused_bytes") == 2 * 20480 * 2304 * 2 + 2304 * 4
    assert gauge("kda_calls") == 4              # kept: once a layer
    assert gauge("mla_fwd_calls") == 1 and gauge("attn_kept_calls") == 1 + 4
    # A KDA layer's o in bf16, its 64 chunks' states and inverses [32, 128,
    # 128] float32 each; the latent layer's o in bf16 and float32 lse.
    assert gauge("attn_kept_bytes") == 4 * (
        T * 4096 * 2 + 2 * 64 * 32 * 128 * 128 * 4) + 32 * T * (128 * 2 + 4)
    assert gauge("mla_bwd_calls") == 1 and gauge("mla_heads_held") == 32
    assert gauge("ssm_conv_calls") == 24        # three a mixer's run
    assert gauge("kda_state_bytes") == 32 * 128 * 128 * 4
    assert gauge("kda_decay_bytes") == T * 4096 * 4
    assert gauge("mla_latent_bytes") == T * (512 + 64) * 2
    assert gauge("moe_stack_in_place_calls") == 4 * 12

    text = compiled.as_text()
    calls = [line.split(" = ", 1)[0].strip() for line in text.splitlines()
             if " custom-call(" in line]
    # The experts' weights are read where they lie: nothing in the step
    # makes one layer's [16, 2304, 1024] out of a stack (a run of one layer
    # is its own stack, [1, 16, ...]).
    made = [line.split(" = ", 1) for line in text.splitlines()
            if re.search(r" = bf16\[16,(?:2304,1024|1024,2304)\]\S* "
                         r"(?!parameter)", line)]
    assert not made, made[:3]
    # Three walks hold KDA layers: the forward in each one's forward loop
    # and nowhere in its backward loop's recomputation.
    assert len([c for c in calls if "tepdist_kda_fwd" in c]) == 3, calls
    assert len([c for c in calls if "tepdist_kda_bwd" in c]) == 3, calls
    assert not [c for c in calls if "tepdist_kda_bwd_states" in c], calls
    for which in ("fwd", "dkv"):
        names = [c for c in calls if f"tepdist_mla_{which}__" in c]
        assert len(names) == 1 and "__h32" in names[0], calls
    assert [c for c in calls if "tepdist_conv_fwd" in c] \
        and [c for c in calls if "tepdist_gmm_" in c], calls
    assert compiled.memory_analysis().peak_memory_in_bytes < 13e9
