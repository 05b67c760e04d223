"""Depth test for PP x TP composition (VERDICT r4 #7).

An 8-LAYER GPT-2 planned, scheduled, and EXECUTED at S=4 stages x TP=2
within each stage through the task-graph runtime on the 8-device CPU
mesh, asserting exact numerics against the unsharded reference — the
composition depth where stage-boundary bookkeeping bugs (DefContext-style
wiring, per-stage planner dims, cotangent routing) hide. The prior
deepest exact-numerics composition was S=2 x TP2.

Reference: nested split ordinals, pjrt/dev_id_util.h:94-192.
"""

import dataclasses
import time

import jax
import numpy as np
import optax
import pytest

from tepdist_tpu.models import gpt2
from tepdist_tpu.parallel.pipeline import plan_pipeline
from tepdist_tpu.runtime.executor import PipelineExecutable


def test_gpt2_8layer_s4_tp2_exact(devices):
    if len(devices) < 8:
        pytest.skip("needs the 8-device mesh")
    cfg = dataclasses.replace(gpt2.CONFIGS["test"], n_layer=8)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    toks = gpt2.fake_batch(cfg, 8, 32)
    tx = optax.sgd(0.05)
    M = 4

    prog = plan_pipeline(lambda p, t: gpt2.loss_fn(p, t, cfg), 4, M,
                         params, toks)
    # Stage balance at depth: the bottleneck-objective stage ILP must not
    # park most blocks in one stage.
    fl = prog.stage_flops()
    assert max(fl) <= 2.0 * (sum(fl) / len(fl)), fl

    exe = PipelineExecutable(prog, devices=devices[:8], optimizer=tx,
                             intra_stage_tp=2)
    assert exe.tp == 2
    assert len(exe.stage_devices) == 4
    exe.load_variables(params)
    losses = [exe.step(toks) for _ in range(2)]

    # Unsharded reference trajectory (same GA semantics via
    # reference_step).
    def apply_fn(pp, ss, g):
        updates, ss = tx.update(g, ss, pp)
        return optax.apply_updates(pp, updates), ss

    # Eager on purpose: jitting this reference XLA-compiles the unrolled
    # M=4 x 8-layer train step (~40s on CPU) for two evaluations; the
    # op-by-op trajectory is identical within the tolerances below.
    ref_step = prog.reference_step(apply_fn)
    opt_state = tx.init(params)
    ref_losses = []
    pref = params
    for _ in range(2):
        l, pref, opt_state = ref_step(pref, opt_state, toks)
        ref_losses.append(float(l))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)
    got = exe.fetch_variables()
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5),
        got, jax.device_get(pref))

    # Steady-state step time, informational only: one short post-warmup
    # sample on the CPU mesh is enough here.
    t0 = time.perf_counter()
    for _ in range(2):
        exe.step(toks)
    best = (time.perf_counter() - t0) / 2
    print(f"\n[depth] gpt2-8L S=4 x TP=2 task-graph: {best * 1e3:.1f} "
          "ms/step on the 8-device CPU mesh")
    assert best > 0
