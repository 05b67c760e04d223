"""Compile the Xing4.0 cell's whole step for a DESCRIBED v5e (no chip):
``tests/test_tpu_compile.py``'s case for ``xing4.0-29b-a4b.train.s4096``, in
a file of its own so that a worker other than that file's takes it (the suite
is dealt out a file at a time). A compile that passes is NOT a chip run:
nothing executes here."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
from test_tpu_compile import v5e_devices  # noqa: F401 — the fixture


def test_the_xing_cells_step_compiles_for_v5e(v5e_devices):
    """``xing4.0-29b-a4b.train.s4096``'s step from the cell's own files (8
    micro batches of two 4,096-token sequences; six layers in three walks,
    the prediction module's the third; ``adamw_bf16_router_bias``), kernels
    not interpreted: every walk's leaves accumulate inside its backward
    layer loop, each latent-attention forward runs once and is kept, the
    experts' stacks are read where they lie, the stream is four lanes of
    235e6 bytes a micro batch, the second loss weights its positions, the
    Sinkhorn rounds are loops (a trip a round, not 40 divisions written
    out a call), and the compiler's peak is under the chip's 15.75e9
    bytes."""
    from tepdist_tpu.telemetry import metrics
    from tools.same_ops import compiled_step
    T, d, cell = 4096, 3584, "xing4.0-29b-a4b.train.s4096"
    compiled, params = compiled_step(cell, v5e_devices[0])
    peak = compiled.memory_analysis().peak_memory_in_bytes
    print("peak", peak)

    gauge = lambda n: metrics().gauge(n).value              # noqa: E731
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n_params == 913_473_668
    walked = ("dense", "blocks", "mtp", "hcdense", "hcblocks", "hcmtp")
    stacks = sum(a.size * a.dtype.itemsize for name in walked
                 for a in jax.tree_util.tree_leaves(params[name]))
    assert gauge("ga_fused_bytes") == stacks
    # The embedding, the head and W_eh in bf16; four norms in float32.
    assert gauge("ga_unfused_bytes") \
        == (2 * 16384 * d + 2 * d * d) * 2 + 4 * d * 4
    assert gauge("attn_kept_calls") == gauge("mla_fwd_calls") \
        == gauge("mla_bwd_calls") == 6
    # A layer's o in bf16 and float32 lse, 32 heads, two sequences.
    assert gauge("attn_kept_bytes") == 6 * 2 * 32 * T * (128 * 2 + 4)
    assert gauge("mla_heads_held") == 32
    assert gauge("moe_stack_in_place_calls") == 12 * 5
    assert gauge("router_choice_calls") == 5
    assert gauge("residual_lanes") == 4
    assert gauge("mhc_sinkhorn_rounds") == 20
    assert gauge("mhc_stream_bytes") == 2 * T * 4 * d * 2 == 234_881_024
    assert gauge("mtp_depth") == 1 and gauge("mtp_loss_weight") == 0.1
    assert gauge("ce_weighted_positions") == 2 * T
    assert gauge("ce_fused_chunks") == 16

    text = compiled.as_text()
    calls = [line.split(" = ", 1)[0].strip() for line in text.splitlines()
             if " custom-call(" in line]
    # A walk a stack: the forward kernel in its forward loop, the backward
    # pass in one kernel.
    for which in ("fwd", "dkv"):
        names = [c for c in calls if f"tepdist_mla_{which}__" in c]
        assert len(names) == 3 and all("__h32" in c for c in names), calls
    assert not [c for c in calls if "tepdist_mla_dq" in c]
    assert [c for c in calls if "tepdist_gmm_" in c], calls
    # The experts' weights are read where they lie: nothing in the step
    # makes one layer's [8, 3584, 1024] out of a stack.
    made = [line.split(" = ", 1) for line in text.splitlines()
            if re.search(r" = bf16\[8,(?:3584,1024|1024,3584)\]\S* "
                         r"(?!parameter)", line)]
    assert not made, made[:3]
    # The mixing matrix [4, 4, a chunk's 2 x 1,024 tokens as 8 x 256] is
    # multiplied in loops' bodies: a few dozen instructions, where the
    # rounds written out left 14,700.
    mixes = len(re.findall(r" = f32\[4,4,8,256\]\S* multiply\(", text))
    assert 0 < mixes < 400, mixes
    assert peak < 15.75e9
