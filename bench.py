"""Benchmark: GPT-2 training throughput with a fully automatic plan.

North-star metric (BASELINE.md / BASELINE.json): tokens/sec/chip on **GPT-2
1.5B** with an auto plan — the headline JSON line. The model trains on ONE
16 GB v5e chip via the framework's memory levers: pallas flash attention
(O(T) activation memory), per-block rematerialisation, scan-over-layers,
gradient accumulation from the sync-free analysis, and bf16-moment AdamW
(4 bytes/param optimizer state). MFU is reported at the standard 6*N*tokens
accounting against the attached chip's bf16 peak, looked up by
``device_kind`` (parallel/performance_utils.py; an unknown kind is an error).

Needs a TPU: without one ``python bench.py`` says so in one line and exits
non-zero — there is no CPU fallback and no replayed number. A headline or
secondary line that raised makes the exit code non-zero.

The reference publishes no numbers, so baselines are self-measured: the
first run of each config writes ``bench_baseline.json`` and later runs
report the ratio. Secondary lines (GPT-2 117M round-1 continuity config,
pallas-flash vs XLA-einsum long-context attention, WideResNet images/s,
GPT-MoE tokens/s) are written to ``bench_extra.json`` each round so
regressions in non-headline paths stay visible.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": ...}
"""

from __future__ import annotations

import dataclasses
import json
import os
import statistics
import sys
import time
import traceback

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
BASELINE_FILE = os.path.join(HERE, "bench_baseline.json")
EXTRA_FILE = os.path.join(HERE, "bench_extra.json")


def _peak_flops() -> float:
    """bf16 peak FLOP/s of the attached chip, from the planner's table."""
    from tepdist_tpu.parallel.performance_utils import (
        chip_spec_for_device_kind,
    )
    return chip_spec_for_device_kind(
        jax.devices()[0].device_kind).bf16_tflops * 1e12


def _read_baselines() -> dict:
    """Parse the baseline file once; {} when absent/corrupt (a corrupt
    file is never overwritten — other metrics' baselines would be
    lost)."""
    if not os.path.exists(BASELINE_FILE):
        return {}
    try:
        return json.load(open(BASELINE_FILE))
    except Exception:  # noqa: BLE001
        return {"_corrupt": True}


def _vs_baseline(metric: str, value: float, extra: dict | None = None,
                 record: bool = True, data: dict | None = None) -> float:
    """Ratio against the stored baseline. ``record=True`` lets a first
    run seed the metric baseline and backfill missing ``extra``
    reference keys (e.g. the host canary) for existing metrics; a
    flagged run (noisy/loaded host) passes ``record=False`` so it can
    never poison a reference — neither the primary baseline nor the
    extras. ``data``: pre-parsed baseline contents (single read)."""
    data = dict(_read_baselines() if data is None else data)
    if data.pop("_corrupt", None):
        return 1.0
    baseline = data.get(metric)
    dirty = False
    if baseline is None:
        baseline = value
        if record:
            data[metric] = value
            dirty = True
    if record:
        for k, v in (extra or {}).items():
            if f"{metric}_{k}" not in data:
                data[f"{metric}_{k}"] = v
                dirty = True
    if dirty:
        try:
            tmp = f"{BASELINE_FILE}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(data, f, indent=1)
            os.replace(tmp, BASELINE_FILE)
        except Exception:
            pass
    return value / baseline


def _timed_windows(step, flat, thread_state, steps: int, windows: int = 5
                   ) -> dict:
    """N timed windows, each ended by ``block_until_ready``.

    Returns {median, best, spread} window seconds. The MEDIAN is the
    reported number (a single best-of window made a noisy-host swing
    indistinguishable from a real regression — VERDICT r4 weak #1);
    spread = (max - min) / median flags untrustworthy runs."""
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        outs = None
        for _ in range(steps):
            outs = step(*flat)
            flat = thread_state(flat, outs)
        jax.block_until_ready(outs)
        times.append(time.perf_counter() - t0)
    times.sort()
    median = times[len(times) // 2]
    return {"median": median, "best": times[0],
            "spread": (times[-1] - times[0]) / median if median else 0.0}


# Above this window dispersion the run carries no regression verdict:
# vs_baseline is withheld (null) rather than reported from noise.
SPREAD_VERDICT_LIMIT = 0.10
# A UNIFORMLY slowed host (competing process through the whole run) shows
# LOW spread with a depressed median — the canary below catches it: a
# fixed numpy workload timed alongside the benchmark, compared to its
# own recorded baseline.
CANARY_SLOWDOWN_LIMIT = 1.3


def _host_canary_ms() -> float:
    """Median time of a fixed CPU workload (pure numpy, no jax): the
    host-speed reference the throughput verdicts are conditioned on."""
    import numpy as np

    a = np.random.default_rng(0).standard_normal((384, 384),
                                                 dtype=np.float32)
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        b = a
        for _ in range(12):
            b = b @ a
            b *= 1.0 / np.abs(b).max()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e3


def _verdict_fields(metric: str, value: float, spread: float,
                    extra: dict | None = None) -> dict:
    """vs_baseline + dispersion fields, refusing a verdict on noisy or
    host-speed-drifted runs (spread guard + symmetric canary guard)."""
    canary = _host_canary_ms()
    extra = dict(extra or {})
    extra["canary_ms"] = canary
    spread_bad = spread > SPREAD_VERDICT_LIMIT
    # Drift is judged BEFORE any baseline write, from one parse of the
    # file — a loaded host must not backfill its own canary reference
    # and then self-approve against it.
    data = _read_baselines()
    canary_base = data.get(f"{metric}_canary_ms")
    # Symmetric: a slowed host makes phantom regressions, a faster host
    # (or a reference recorded under load) makes phantom improvements —
    # neither run carries a throughput verdict.
    drift = (canary / canary_base
             if canary_base is not None and canary_base > 0 else 1.0)
    drift_bad = (drift > CANARY_SLOWDOWN_LIMIT
                 or drift < 1.0 / CANARY_SLOWDOWN_LIMIT)
    # A flagged run records NOTHING (neither a first-run metric baseline
    # nor reference backfills).
    ratio = _vs_baseline(metric, value, extra,
                         record=not (spread_bad or drift_bad), data=data)
    out = {"spread": round(spread, 4), "host_canary_ms": round(canary, 2)}
    if spread_bad or drift_bad:
        out["vs_baseline"] = None
        out["vs_baseline_raw"] = round(ratio, 4)
        reasons = []
        if spread_bad:
            reasons.append(
                f"window spread {spread:.1%} > {SPREAD_VERDICT_LIMIT:.0%}")
        if drift_bad:
            reasons.append(f"host canary {drift:.2f}x its baseline")
        out["verdict_note"] = ("; ".join(reasons)
                               + ": noisy/loaded host, no regression "
                                 "verdict")
    else:
        out["vs_baseline"] = round(ratio, 4)
    return out


# ---------------------------------------------------------------------------
# Headline: GPT-2 1.5B on one chip, fully automatic plan.
# ---------------------------------------------------------------------------

def bench_gpt2_15b() -> dict:
    from tepdist_tpu.models import gpt2
    from tepdist_tpu.optim import adamw_bf16
    from tepdist_tpu.train import plan_training

    cfg = dataclasses.replace(gpt2.CONFIGS["1.5B"], attn="flash", remat=True,
                              remat_policy=os.environ.get(
                                  "BENCH_15B_REMAT", "full"),
                              loss_chunk=int(os.environ.get(
                                  "BENCH_15B_LOSS_CHUNK", "512")),
                              flash_block_q=int(os.environ.get(
                                  "BENCH_15B_BLOCK_Q", "512")),
                              flash_block_k=int(os.environ.get(
                                  "BENCH_15B_BLOCK_K", "512")))
    n_params = gpt2.num_params(cfg)
    batch = int(os.environ.get("BENCH_15B_BATCH", "48"))
    seq, micro, steps = 1024, int(os.environ.get(
        "BENCH_15B_MICRO", "16")), 3

    params = gpt2.stacked_init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, batch, seq)
    tx = adamw_bf16(1e-4)

    def loss_fn(p, toks):
        return gpt2.loss_fn_stacked(p, toks, cfg)

    t0 = time.perf_counter()
    plan = plan_training(loss_fn, tx, params, tokens,
                         num_micro_batches=micro)
    planner_seconds = time.perf_counter() - t0
    if "tpu_custom_call" not in plan.compiled_step_text():
        raise RuntimeError("no tpu_custom_call in the compiled step: the "
                           "flash kernel is interpreted or replaced")
    plan.step(tokens)  # compile + settle steady-state signature
    plan.step(tokens)

    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = plan.step(tokens)  # step() round-trips the loss (barrier)
        times.append(time.perf_counter() - t0)
    times.sort()
    median = times[len(times) // 2]
    spread = (times[-1] - times[0]) / median if median else 0.0
    tps = batch * seq * steps / median
    mfu = 6.0 * n_params * tps / _peak_flops()
    metric = "gpt2_15b_tokens_per_sec_per_chip"
    return {
        "metric": metric,
        "value": round(tps, 2),
        "unit": "tokens/s/chip",
        **_verdict_fields(metric, tps, spread,
                          {"planner_seconds": planner_seconds}),
        "mfu": round(mfu, 4),
        "planner_seconds": round(planner_seconds, 2),
        "loss": round(float(loss), 4),
    }


# ---------------------------------------------------------------------------
# Round-1 continuity config: GPT-2 117M, the recipe of the round-1 record.
# ---------------------------------------------------------------------------

def bench_gpt2_117m() -> dict:
    import optax

    from tepdist_tpu.core.mesh import MeshTopology
    from tepdist_tpu.models import gpt2
    from tepdist_tpu.parallel.auto_parallel import auto_parallel

    devices = jax.devices()
    cfg = gpt2.CONFIGS["117M"]
    batch, seq, steps = 16, 512, 20
    model_name = "gpt2_117m"

    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, batch, seq)
    tx = optax.adamw(1e-4, b1=0.9, b2=0.95, weight_decay=0.01)
    opt_state = tx.init(params)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: gpt2.loss_fn(p, tokens, cfg))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return loss, params, opt_state

    n_dev = len(devices)
    topo = MeshTopology([("data", max(n_dev, 1))])
    n_state = len(jax.tree_util.tree_leaves((params, opt_state)))
    state_alias = {1 + k: k for k in range(n_state)}
    t0 = time.perf_counter()
    plan = auto_parallel(train_step, topo, params, opt_state, tokens,
                         state_alias=state_alias)
    step = plan.executable(devices=devices)
    planner_seconds = time.perf_counter() - t0

    flat, _ = jax.tree_util.tree_flatten(((params, opt_state, tokens), {}))
    shardings = plan.input_shardings(devices)
    flat = [jax.device_put(x, s) for x, s in zip(flat, shardings)]

    def thread_state(flat, outs):
        n = len(outs) - 1
        return list(outs[1:]) + flat[n:]

    for _ in range(2):   # compile + settle steady-state signature
        outs = step(*flat)
        jax.block_until_ready(outs)
        flat = thread_state(flat, outs)

    tw = _timed_windows(step, flat, thread_state, steps)
    tps_chip = batch * seq * steps / tw["median"] / n_dev
    n_params = gpt2.num_params(cfg)
    metric = f"{model_name}_tokens_per_sec_per_chip"
    return {
        "metric": metric,
        "value": round(tps_chip, 2),
        "unit": "tokens/s/chip",
        **_verdict_fields(metric, tps_chip, tw["spread"],
                          {"planner_seconds": planner_seconds}),
        "mfu": round(6.0 * n_params * tps_chip / _peak_flops(), 4),
        "planner_seconds": round(planner_seconds, 2),
    }


# ---------------------------------------------------------------------------
# Pallas flash attention vs the reference-style XLA einsum at long context.
# vs_baseline here is measured IN THIS RUN: einsum time / flash time.
# ---------------------------------------------------------------------------

def bench_flash_attention_long() -> dict:
    import math

    from tepdist_tpu.ops.pallas.flash_attention import flash_attention

    B, H, T, D = 2, 12, 4096, 64
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, H, T, D), jnp.bfloat16)
    k = jax.random.normal(k2, (B, H, T, D), jnp.bfloat16)
    v = jax.random.normal(k3, (B, H, T, D), jnp.bfloat16)

    def einsum_attn(q, k, v):
        scale = 1.0 / math.sqrt(D)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        mask = jnp.tril(jnp.ones((T, T), bool))
        logits = jnp.where(mask, logits.astype(jnp.float32), -1e9)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

    def train_like(attn):
        def f(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32))
        g = jax.jit(jax.grad(f, argnums=(0, 1, 2)))
        g(q, k, v)  # compile
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(5):
                out = g(q, k, v)
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best / 5

    t_flash = train_like(flash_attention)
    t_einsum = train_like(einsum_attn)
    return {
        "metric": "flash_attention_fwdbwd_T4096_ms",
        "value": round(t_flash * 1e3, 2),
        "unit": "ms",
        # >1.0 == pallas beats the XLA einsum reference implementation.
        "vs_baseline": round(t_einsum / t_flash, 4),
        "einsum_ms": round(t_einsum * 1e3, 2),
    }


# ---------------------------------------------------------------------------
# WideResNet images/s (reference examples/wide_resnet fake-input benchmark).
# ---------------------------------------------------------------------------

def bench_wrn() -> dict:
    import optax

    from tepdist_tpu.core.mesh import MeshTopology
    from tepdist_tpu.models import wide_resnet as wrn
    from tepdist_tpu.parallel.auto_parallel import auto_parallel

    cfg = wrn.CONFIGS[0]
    batch, image, steps = 32, 224, 10
    params = wrn.init_params(cfg, jax.random.PRNGKey(0))
    images, labels = wrn.fake_batch(cfg, batch, image)
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def train_step(params, opt_state, images, labels):
        loss, grads = jax.value_and_grad(
            lambda p: wrn.loss_fn(p, images, labels, cfg))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    n_state = len(jax.tree_util.tree_leaves((params, opt_state)))
    plan = auto_parallel(train_step,
                         MeshTopology([("data", len(jax.devices()))]),
                         params, opt_state, images, labels,
                         state_alias={1 + k: k for k in range(n_state)})
    step = plan.executable()
    flat, _ = jax.tree_util.tree_flatten(
        ((params, opt_state, images, labels), {}))
    flat = [jax.device_put(v, s)
            for v, s in zip(flat, plan.input_shardings())]

    def thread_state(flat, outs):
        n = len(outs) - 1
        return list(outs[1:]) + flat[n:]

    outs = step(*flat)
    _ = float(jax.device_get(outs[0]))
    flat = thread_state(flat, outs)
    tw = _timed_windows(step, flat, thread_state, steps)
    ips = batch * steps / tw["median"]
    metric = "wrn250m_images_per_sec"
    return {
        "metric": metric,
        "value": round(ips, 2),
        "unit": "images/s",
        **_verdict_fields(metric, ips, tw["spread"]),
    }


# ---------------------------------------------------------------------------
# llama-1B tokens/s (surplus model family; flash attention + auto plan).
# ---------------------------------------------------------------------------

def bench_llama() -> dict:
    import dataclasses as _dc

    import optax

    from tepdist_tpu.core.mesh import MeshTopology
    from tepdist_tpu.models import llama
    from tepdist_tpu.parallel.auto_parallel import auto_parallel

    cfg = _dc.replace(llama.CONFIGS["1B"], attn="flash")
    batch, seq, steps = 4, 512, 10
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                                cfg.vocab_size)
    tx = optax.adamw(1e-4)
    opt_state = tx.init(params)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, cfg))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    n_state = len(jax.tree_util.tree_leaves((params, opt_state)))
    plan = auto_parallel(train_step,
                         MeshTopology([("data", len(jax.devices()))]),
                         params, opt_state, tokens,
                         state_alias={1 + k: k for k in range(n_state)})
    step = plan.executable()
    flat, _ = jax.tree_util.tree_flatten(
        ((params, opt_state, tokens), {}))
    flat = [jax.device_put(v, s)
            for v, s in zip(flat, plan.input_shardings())]

    def thread_state(flat, outs):
        n = len(outs) - 1
        return list(outs[1:]) + flat[n:]

    outs = step(*flat)
    _ = float(jax.device_get(outs[0]))
    flat = thread_state(flat, outs)
    tw = _timed_windows(step, flat, thread_state, steps)
    tps = batch * seq * steps / tw["median"]
    metric = "llama1b_tokens_per_sec"
    return {
        "metric": metric,
        "value": round(tps, 2),
        "unit": "tokens/s",
        **_verdict_fields(metric, tps, tw["spread"]),
    }


# ---------------------------------------------------------------------------
# GPT-MoE tokens/s (reference examples/gpt_moe).
# ---------------------------------------------------------------------------

def bench_moe() -> dict:
    import optax

    from tepdist_tpu.core.dist_spec import DimStrategy
    from tepdist_tpu.core.mesh import MeshTopology
    from tepdist_tpu.models import gpt2, gpt_moe
    from tepdist_tpu.parallel.auto_parallel import auto_parallel

    cfg = gpt_moe.CONFIGS["base-8e"]
    batch, seq, steps = 8, 256, 10
    params = gpt_moe.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg.base, batch, seq)
    tx = optax.adamw(1e-4)
    opt_state = tx.init(params)

    n = len(jax.devices())
    ep = min(n, cfg.num_experts)
    topo = MeshTopology([("data", max(n // ep, 1)), ("expert", ep)])

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: gpt_moe.loss_fn(p, tokens, cfg))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    leaves = jax.tree_util.tree_leaves(params)
    annotations = {}
    for i, leaf in enumerate(leaves):
        if leaf.ndim == 3 and leaf.shape[0] == cfg.num_experts and ep > 1:
            annotations[i] = {"expert": DimStrategy.split_on(0, ep)}
    n_state = len(jax.tree_util.tree_leaves((params, opt_state)))
    plan = auto_parallel(train_step, topo, params, opt_state, tokens,
                         annotations=annotations or None,
                         state_alias={1 + k: k for k in range(n_state)})
    step = plan.executable()
    flat, _ = jax.tree_util.tree_flatten(((params, opt_state, tokens), {}))
    flat = [jax.device_put(v, s)
            for v, s in zip(flat, plan.input_shardings())]

    def thread_state(flat, outs):
        n_out = len(outs) - 1
        return list(outs[1:]) + flat[n_out:]

    outs = step(*flat)
    _ = float(jax.device_get(outs[0]))
    flat = thread_state(flat, outs)
    tw = _timed_windows(step, flat, thread_state, steps)
    tps = batch * seq * steps / tw["median"]
    metric = "gpt_moe_base8e_tokens_per_sec"
    return {
        "metric": metric,
        "value": round(tps, 2),
        "unit": "tokens/s",
        **_verdict_fields(metric, tps, tw["spread"]),
    }


_RUNTIME_BENCH_DEADLINE = [None]   # set by main(); caps the subprocess


def bench_runtime_protocol() -> dict:
    """Task-graph vs collective-pipeline under the PINNED protocol
    (tools/bench_runtime.py docstring; VERDICT r2 weak #2). Runs in a
    subprocess on the 8-device CPU mesh — the protocol's fixed fabric —
    regardless of the bench backend."""
    import subprocess

    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": (env.get("XLA_FLAGS", "") +
                              " --xla_force_host_platform_device_count=8")})
    timeout = 600.0
    if _RUNTIME_BENCH_DEADLINE[0] is not None:
        # Never starve the remaining secondary lines: cap at the unspent
        # extra budget (with a floor that lets a warm run finish).
        timeout = max(120.0, min(
            timeout, _RUNTIME_BENCH_DEADLINE[0] - time.monotonic()))
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "tools", "bench_runtime.py")],
        env=env, timeout=timeout, capture_output=True, text=True)
    if out.returncode != 0:
        # Surface the child's actual failure, not an opaque exit status.
        raise RuntimeError("bench_runtime subprocess failed: "
                           + (out.stderr or "")[-400:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_trace_overhead() -> dict:
    """Telemetry sanity line: the span() fast path must be a no-op when
    tracing is disabled (telemetry/trace.py contract — instrumented hot
    paths pay one branch, zero allocation). Measures both modes against a
    swapped-in private tracer so the numbers neither pollute nor drain
    the process ring buffer; the singleton identity is asserted outright,
    so a regression fails the line instead of shading the number."""
    from tepdist_tpu.telemetry import _NULL_SPAN
    from tepdist_tpu.telemetry import trace as _trace

    n = 20000
    prev = _trace.tracer()
    tmp = _trace.Tracer(capacity=n, enabled=False)
    _trace._TRACER = tmp
    try:
        assert _trace.span("bench", cat="bench") is _NULL_SPAN, \
            "disabled span() must return the shared no-op singleton"
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with _trace.span("bench", cat="bench"):
                pass
        disabled_ns = (time.perf_counter_ns() - t0) / n

        tmp.enabled = True
        # Min of repeated loops: on a tight loop, host noise is strictly
        # additive (deschedules, frequency dips only ever ADD time), so
        # the minimum is the estimator of the true per-span cost — and
        # unlike the median it is stable across processes on a loaded
        # host, which the perf-gate history band depends on.  The gated
        # budget is <= 600 ns/span (ISSUE 16).
        reps = []
        for _ in range(5):
            t0 = time.perf_counter_ns()
            for _ in range(n):
                with _trace.span("bench", cat="bench"):
                    pass
            reps.append((time.perf_counter_ns() - t0) / n)
            tmp.clear()
        enabled_ns = min(reps)
    finally:
        _trace._TRACER = prev
    return {
        "metric": "trace_overhead",
        "value": round(disabled_ns, 1),
        "unit": "ns/span disabled",
        "trace_enabled_ns_per_span": round(enabled_ns, 1),
        "enabled_ns_per_span": round(enabled_ns, 1),
        "native_core": tmp._core is not None,
        "gate_below_600ns": bool(enabled_ns <= 600.0),
        "noop_fast_path": True,
    }


def bench_plan_verify(rounds: int = 20) -> dict:
    """Pre-dispatch plan-verifier cost on the 8-device pipeline fixture
    (4 stages x 2 devices): verify_plan() runs every static check
    (acyclicity, transfer pairing, wait-cycle, exactly-once, signature,
    peak-HBM) and must stay well under 1% of the time the planner took
    to produce the plan, so TEPDIST_VERIFY_PLAN can gate every dispatch
    for free. ``pct_of_plan`` is the ratio this line exists to bound."""
    from tools.verify_plan import build_fixture

    from tepdist_tpu.analysis.plan_verify import verify_plan

    t0 = time.perf_counter()
    prog, dag, schedule = build_fixture(stages=4, micro=4, devices=8)
    plan_ms = (time.perf_counter() - t0) * 1e3
    vals = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        verify_plan(dag, schedule=schedule, prog=prog, where="bench")
        vals.append((time.perf_counter() - t0) * 1e3)
    vals.sort()
    med = vals[len(vals) // 2]
    return {
        "metric": "plan_verify_ms",
        "value": round(med, 3),
        "unit": "ms",
        "plan_ms": round(plan_ms, 1),
        "pct_of_plan": round(100.0 * med / plan_ms, 3) if plan_ms else None,
        "n_tasks": len(dag.nodes),
        "gate_below_1pct": bool(plan_ms and med / plan_ms < 0.01),
    }


def bench_ledger_overhead(ab_pairs: int = 5, null_pairs: int = 3,
                          window_steps: int = 10, warmup: int = 6) -> dict:
    """RPC-ledger + flight-recorder cost on the two-worker in-proc fleet
    fixture, measured with the ISSUE 16 noise-guarded methodology.

    The naive A/B (one OFF run, one ON run, compare mins) cannot resolve
    a ~30 us effect on a ~4 ms multi-threaded step on a drifting host: an
    OFF-vs-OFF null experiment on this class of machine shows the same
    magnitude of "overhead" as a real ON run.  So the bench measures
    three things on ONE warm session and decides which is trustworthy:

    1. NULL CALIBRATION — ``null_pairs`` interleaved OFF/OFF window pairs
       (min-of-steps per window, alternating order).  The median absolute
       pair delta is the host's A/B noise floor for this workload.
    2. A/B — ``ab_pairs`` interleaved OFF/ON pairs, same estimator.
    3. PER-OP ACCOUNTING — record/scope volumes counted from a drained
       enabled step, times per-op in-situ costs measured in a tight loop
       (the full hook pattern: clocks + the bound native record call, and
       the full scope/hint context lifecycle).

    ``value`` is the A/B median when it clears the measured noise floor
    (a quiet host measures directly), else the per-op accounting total
    (a noisy host reports the physically attributable cost rather than a
    random draw from its own jitter).  Both are always reported, with the
    methodology stamped.  The acceptance bound is <= 2% of step time;
    disabled stays the ``active() is None`` branch-only fast path
    (``disabled_noop`` asserts it)."""
    import optax

    from tepdist_tpu import telemetry
    from tepdist_tpu.parallel.pipeline import plan_pipeline
    from tepdist_tpu.rpc.inproc import (close_inproc_cluster,
                                        make_inproc_cluster)
    from tepdist_tpu.runtime.distributed_executor import (
        DistributedPipelineSession,
    )
    from tepdist_tpu.telemetry import flight
    from tepdist_tpu.telemetry import ledger

    def loss_fn(params, x, y):
        h = x
        for i in range(4):
            h = jnp.tanh(h @ params[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    k = jax.random.PRNGKey(0)
    keys = jax.random.split(k, 6)
    params = {f"w{i}": jax.random.normal(keys[i], (16, 16)) * 0.3
              for i in range(4)}
    x = jax.random.normal(keys[4], (8, 16))
    y = jax.random.normal(keys[5], (8, 16))

    telemetry.trace.configure(enabled=False)
    led = ledger.ledger()

    prog = plan_pipeline(loss_fn, 2, 2, params, x, y)
    cluster, _serv = make_inproc_cluster(2, jax.devices()[:1])
    sess = DistributedPipelineSession(prog, cluster,
                                      optimizer=optax.sgd(1e-2))
    try:
        sess.load_variables(params)
        for _ in range(warmup):
            sess.step(x, y)

        def window_ms(on: bool) -> float:
            ledger.configure(enabled=on)
            flight.configure(enabled=on)
            best = float("inf")
            for _ in range(window_steps):
                t0 = time.perf_counter()
                sess.step(x, y)
                best = min(best, time.perf_counter() - t0)
            led.clear()
            return best * 1e3

        # 1. Null calibration: both windows OFF — any nonzero delta is
        # host noise, and its magnitude is the floor below which a real
        # A/B delta is unreadable.
        null_pcts = []
        for p in range(null_pairs):
            a = window_ms(False)
            b = window_ms(False)
            null_pcts.append((b - a) / a * 100.0 if a else 0.0)
        noise_floor = statistics.median(abs(v) for v in null_pcts)

        ledger.configure(enabled=False)
        noop = ledger.active() is None

        # 2. Paired A/B, ABBA order so secular drift cancels per pair.
        ab_pcts = []
        off_mins = []
        for p in range(ab_pairs):
            if p % 2 == 0:
                off = window_ms(False)
                on = window_ms(True)
            else:
                on = window_ms(True)
                off = window_ms(False)
            off_mins.append(off)
            ab_pcts.append((on - off) / off * 100.0 if off else 0.0)
        ab_median = statistics.median(ab_pcts)
        off_ms = statistics.median(off_mins)

        # 3. Per-op accounting: volumes from one drained enabled window,
        # costs from tight in-situ loops.
        ledger.configure(enabled=True)
        led.clear()
        acct_steps = 4
        for _ in range(acct_steps):
            sess.step(x, y)
        recs, _cats, _lost, _names = led._drain()
        led.clear()
        kind_count = [0] * 8
        for r in recs:
            kind_count[r[0]] += 1
        # Wire hooks (PACK/UNPACK/ENCODE/DECODE/RETRY) each cost two
        # clock reads plus one bound record call; CALL/HANDLER/WINDOW
        # records come from scope objects whose lifecycle includes their
        # exit record.  Step hints leave no record — sites fire about
        # once per dispatch RPC, costed at the measured hint lifecycle.
        wire_per_step = sum(kind_count[i] for i in (0, 1, 2, 3, 6)) \
            / acct_steps
        scopes_per_step = sum(kind_count[i] for i in (4, 5, 7)) / acct_steps
        calls_per_step = kind_count[4] / acct_steps

        # Min-of-reps per-op costs: on a tight loop, host noise is
        # strictly additive, so the minimum is the estimator of the
        # true cost and is stable across processes on a loaded host.
        n = 5000
        def _min_ns(body):
            reps = []
            for _ in range(4):
                t0 = time.perf_counter_ns()
                body(n)
                reps.append((time.perf_counter_ns() - t0) / n)
            return min(reps)

        def _hook(m):
            for _ in range(m):
                ta = time.monotonic_ns()
                tb = time.monotonic_ns()
                led.record_pack(64, 256, ta, tb)

        def _scope(m):
            for _ in range(m):
                with ledger.client_scope("bench:acct"):
                    pass

        def _hint(m):
            for _ in range(m):
                with ledger.step_hint(3):
                    pass

        hook_ns = _min_ns(_hook)
        scope_ns = _min_ns(_scope)
        hint_ns = _min_ns(_hint)
        led.clear()

        accounted_us = (wire_per_step * hook_ns + scopes_per_step * scope_ns
                        + calls_per_step * hint_ns) / 1e3
        # Denominator: the floor across all OFF windows (each already
        # min-of-steps) — the same additive-noise argument as the
        # per-op loops, keeping the ratio stable run to run.
        off_floor_ms = min(off_mins) if off_mins else 0.0
        accounted_pct = accounted_us / (off_floor_ms * 1e3) * 100.0 \
            if off_floor_ms else 0.0

        off_spread = ((max(off_mins) - min(off_mins)) / off_ms
                      if off_ms else 0.0)
    finally:
        sess.close()
        close_inproc_cluster(cluster)
        ledger.configure(enabled=False)
        flight.configure(enabled=True)   # flight defaults ON

    # The A/B median is trustworthy only when it clears the
    # null-calibrated floor AND the pairs are internally coherent: a
    # single pair of the wrong sign, or the OFF-window spread guard
    # firing, is direct evidence that noise operates at the same scale
    # as the claimed effect — fall back to per-op accounting.
    if ab_median <= noise_floor:
        ab_unreadable = "below host noise floor"
    elif off_spread > SPREAD_VERDICT_LIMIT:
        ab_unreadable = (f"window spread {off_spread:.1%} "
                         f"> {SPREAD_VERDICT_LIMIT:.0%}, loaded host")
    elif min(ab_pcts) <= 0.0:
        ab_unreadable = "pairs straddle zero"
    else:
        ab_unreadable = None
    pct = max(accounted_pct if ab_unreadable else ab_median, 0.0)
    methodology = ("ab_paired_windows" if ab_unreadable is None
                   else f"per_op_accounting (A/B {ab_unreadable})")
    return {
        "metric": "ledger_overhead_pct",
        "value": round(pct, 2),
        "unit": "% of fleet step (ledger+flight enabled vs off)",
        "methodology": methodology,
        "fleet_step_off_ms": round(off_ms, 3),
        "ab_median_pct": round(ab_median, 2),
        "ab_pair_pcts": [round(v, 2) for v in ab_pcts],
        "noise_floor_pct": round(noise_floor, 2),
        "accounted_pct": round(accounted_pct, 3),
        "accounted_us_per_step": round(accounted_us, 1),
        "wire_records_per_step": round(wire_per_step, 1),
        "scope_records_per_step": round(scopes_per_step, 1),
        "per_record_hook_ns": round(hook_ns, 1),
        "per_scope_ns": round(scope_ns, 1),
        "per_hint_ns": round(hint_ns, 1),
        "disabled_noop": noop,
        "gate_below_2pct": bool(pct <= 2.0),
        **_verdict_fields("ledger_overhead_pct", pct, off_spread),
    }


def bench_explore_report(rounds: int = 3) -> dict:
    """Exploration-observatory capture cost: min-of-rounds ``explore()``
    wall on an abstract MLP with the observatory OFF (no collector, no
    prune records, no report build) vs ON (full candidate ledger +
    typed prunes + ranked report). The report is assembled from data
    the argmin already produced, so the acceptance bound is <= 2% of
    explore time."""
    from tepdist_tpu.parallel.exploration import explore
    from tepdist_tpu.telemetry import observatory

    def loss_fn(params, x, y):
        h = x
        for i in range(4):
            h = jnp.tanh(h @ params[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    params = {f"w{i}": jax.ShapeDtypeStruct((256, 256), jnp.float32)
              for i in range(4)}
    x = jax.ShapeDtypeStruct((8, 256), jnp.float32)
    y = jax.ShapeDtypeStruct((8, 256), jnp.float32)

    def explore_min_ms(obs_on: bool) -> float:
        observatory.configure(enabled=obs_on)
        best = float("inf")
        for _ in range(rounds + 1):   # first round absorbs trace compile
            t0 = time.perf_counter()
            explore(loss_fn, params, x, y, n_devices=8,
                    num_micro_batches=2, entry_point="bench")
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    try:
        off_ms = explore_min_ms(False)
        on_ms = explore_min_ms(True)
    finally:
        observatory.configure(enabled=True)   # observatory defaults ON
    report_ms = max(on_ms - off_ms, 0.0)
    pct = (report_ms / off_ms * 100.0) if off_ms else 0.0
    return {
        "metric": "explore_report_ms",
        "value": round(report_ms, 3),
        "unit": "ms of explore() spent on report capture (min-of-rounds,"
                " observatory on vs off)",
        "explore_off_ms": round(off_ms, 3),
        "explore_on_ms": round(on_ms, 3),
        "pct_of_explore": round(pct, 2),
        "gate_below_2pct": bool(pct <= 2.0),
    }


def bench_serving(n_requests: int = 16, rounds: int = 3) -> dict:
    """Continuous-batching serving throughput (tepdist_tpu/serving/):
    one engine, mixed prompt/output lengths, decode tokens/s with the
    scheduler + slot pool + length-bucketed executables on the path.
    One warmup round absorbs the prefill/decode compiles; the median of
    the measured rounds is reported under the spread guard like every
    other line."""
    import numpy as np

    from tepdist_tpu.models import gpt2
    from tepdist_tpu.serving import ServingEngine

    cfg = gpt2.CONFIGS["test"]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, slots=4, max_len=32,
                        max_queue=n_requests + 1, name="bench")
    rng = np.random.RandomState(0)

    def one_round(tag: str) -> float:
        toks = 0
        for i in range(n_requests):
            t = int(rng.randint(3, 13))
            m = int(rng.randint(2, 8))
            eng.submit(f"{tag}-{i}",
                       rng.randint(0, cfg.vocab_size,
                                   size=t).astype(np.int32),
                       max_new_tokens=m)
            toks += m
        t0 = time.perf_counter()
        eng.run_until_idle()
        return toks / (time.perf_counter() - t0)

    one_round("warm")
    vals = sorted(one_round(f"r{k}") for k in range(rounds))
    med = vals[len(vals) // 2]
    spread = (vals[-1] - vals[0]) / med if med else 0.0
    return {
        "metric": "serving_tok_s",
        "value": round(med, 1),
        "unit": "tokens/s",
        "n_requests": n_requests,
        "slots": 4,
        **_verdict_fields("serving_tok_s", med, spread),
    }


def bench_paged_capacity() -> dict:
    """Max resident requests at a FIXED emulated HBM budget: the KV
    bytes a 2-slot x 32-token slot pool reserves, given instead to the
    paged engine (16-token pages, per-request worst-case reservation).
    Short requests pin a whole max_len row under slots but only
    pages_for(T+max_new-1) pages under paging — the ratio is the
    admission-capacity win the paged subsystem exists for.
    Deterministic (counts, not timings): no spread guard."""
    import numpy as np

    from tepdist_tpu.models import gpt2
    from tepdist_tpu.serving import ServingEngine
    from tepdist_tpu.serving.paged_kv import page_bytes, pages_for

    cfg = gpt2.CONFIGS["test"]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    slots, max_len, ps = 2, 32, 16
    budget = pages_for(slots * max_len, ps) * page_bytes(cfg, ps)
    residents = {}
    for mode in ("slots", "paged"):
        eng = ServingEngine(
            params, cfg, kv_mode=mode, slots=slots, max_len=max_len,
            page_size=ps, hbm_budget_bytes=(budget if mode == "paged"
                                            else None),
            max_queue=16, name=f"cap-{mode}")
        rng = np.random.RandomState(0)
        for i in range(8):
            eng.submit(f"c{i}",
                       rng.randint(0, cfg.vocab_size,
                                   size=5).astype(np.int32),
                       max_new_tokens=5)
        eng.step()            # one admission wave at the same budget
        st = eng.stats()
        residents[mode] = (st["resident"] if mode == "paged"
                           else st["slots_used"])
        eng.run_until_idle()  # finish cleanly (also exercises decode)
    ratio = (residents["paged"] / residents["slots"]
             if residents["slots"] else None)
    return {
        "metric": "paged_capacity_x",
        "value": round(ratio, 2) if ratio else None,
        "unit": "x slot residents at equal HBM budget",
        "hbm_budget_bytes": budget,
        "slot_residents": residents["slots"],
        "paged_residents": residents["paged"],
        "gate_2x": bool(ratio and ratio >= 2.0),
    }


def bench_quantized_ar() -> dict:
    """Fidelity-vs-int8 gradient AllReduce A/B over the SAME tensor set:
    every gradient-shaped leaf is encoded through the real wire path
    (rpc/protocol.encode_literal) once at fidelity f32 and once as
    chunk-scale int8, then decoded back. The reported value is wire
    bytes fidelity/int8 — deterministic (bytes, not timings), the
    bandwidth term the evaluator's compressed_all_reduce_cost scales by.
    Encode+decode wall time rides along as sub-keys (the quantize
    compute its quantize_overhead term models); round-trip error is
    reported so the lossy arm's numerics stay visible."""
    import numpy as np

    from tepdist_tpu.rpc import protocol

    rng = np.random.default_rng(0)
    shapes = [(256, 256), (256,), (1024, 64), (64,), (4, 256, 32)]
    grads = [rng.standard_normal(s).astype(np.float32) * 0.02
             for s in shapes]

    def arm(wd):
        total, err = 0, 0.0
        t0 = time.perf_counter()
        for g in grads:
            meta, blob = protocol.encode_literal(g, wire_dtype=wd)
            total += memoryview(blob).nbytes
            out = protocol.decode_literal(meta, blob)
            err = max(err, float(np.max(np.abs(out - g))))
        return total, (time.perf_counter() - t0) * 1e3, err

    fid_bytes, fid_ms, fid_err = arm(None)
    q_bytes, q_ms, q_err = arm("int8")
    ratio = fid_bytes / q_bytes if q_bytes else None
    return {
        "metric": "quantized_ar_x",
        "value": round(ratio, 3) if ratio else None,
        "unit": "x wire bytes vs fidelity f32 (same gradient tensors)",
        "fidelity_bytes": fid_bytes,
        "int8_bytes": q_bytes,
        "fidelity_roundtrip_err": fid_err,   # must be exactly 0.0
        "int8_roundtrip_err": round(q_err, 6),
        "encode_fidelity_wall_ms": round(fid_ms, 2),
        "encode_int8_wall_ms": round(q_ms, 2),
        "gate_1p5x": bool(ratio and ratio >= 1.5),
    }


_ZERO_MEM_SCRIPT = r"""
import json, os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np, optax
from tepdist_tpu.core.mesh import MeshTopology
from tepdist_tpu.parallel.auto_parallel import auto_parallel
from tepdist_tpu.parallel.sync_free import build_ga_step

def loss_fn(p, x, y):
    h = jnp.tanh(x @ p["w1"])
    return jnp.mean((h @ p["w2"] - y) ** 2)

k = jax.random.PRNGKey(0)
params = {"w1": jax.random.normal(k, (128, 256)) * 0.02,
          "w2": jax.random.normal(k, (256, 128)) * 0.02}
x = jax.random.normal(k, (8, 128)); y = jax.random.normal(k, (8, 128))
opt = optax.adam(1e-3)

def grad_fn(p, *b):
    return jax.value_and_grad(loss_fn)(p, *b)

def apply_fn(p, s, g):
    u, s = opt.update(g, s, p)
    return optax.apply_updates(p, u), s

def measure(zero):
    step = build_ga_step(grad_fn, apply_fn, 1, batch_argnums=(1, 2))
    state = opt.init(params)
    n_param = len(jax.tree_util.tree_leaves(params))
    n_state = len(jax.tree_util.tree_leaves((params, state)))
    zi = list(range(n_param, n_state)) if zero else None
    plan = auto_parallel(step, MeshTopology([("data", 2)]), params, state,
                         x, y, state_alias={1 + i: i for i in range(n_state)},
                         zero_invars=zi)
    sh = plan.input_shardings(jax.devices())
    flat = jax.tree_util.tree_leaves((params, state))
    placed = [jax.device_put(v, s) for v, s in zip(flat, sh[:n_state])]
    dev0 = jax.devices()[0]
    tot = 0
    for v in placed[n_param:]:
        for s_ in v.addressable_shards:
            if s_.device == dev0:
                tot += int(np.prod(s_.data.shape)) * v.dtype.itemsize
    return tot

print(json.dumps({"fid": measure(False), "zero": measure(True)}))
"""


def bench_zero_opt_mem() -> dict:
    """MEASURED per-device optimizer-state bytes, fidelity DP vs ZeRO at
    dp=2, on the planner path (auto_parallel ``zero_invars``): both plans
    place their real Adam state through ``input_shardings`` and device-0's
    addressable shard bytes are summed — actual buffer shapes, not the
    cost model. Runs in a subprocess (2 forced CPU host devices; the
    parent backend is already initialized). value = fidelity/zero bytes;
    the Adam count scalar stays replicated, so the ratio lands just under
    2.0 — gate at >= 1.8x."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run(
        [sys.executable, "-c", _ZERO_MEM_SCRIPT], env=env, text=True,
        capture_output=True, timeout=300,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    if proc.returncode != 0:
        raise RuntimeError(
            f"zero mem probe failed: {proc.stderr.strip().splitlines()[-1]}")
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    ratio = data["fid"] / data["zero"] if data["zero"] else None
    return {
        "metric": "zero_opt_mem_x",
        "value": round(ratio, 3) if ratio else None,
        "unit": "x per-device optimizer-state bytes vs fidelity DP (dp=2)",
        "fidelity_bytes_per_device": data["fid"],
        "zero_bytes_per_device": data["zero"],
        "gate_1p8x": bool(ratio and ratio >= 1.8),
    }


def bench_host_push_bytes(steps: int = 4) -> dict:
    """Fleet activation-wire bytes per training step on the two-worker
    in-proc pipeline fixture, read from the ledger's byte-exact tx_blob
    accounting (telemetry/ledger.py): one session per wire mode — the
    wire dtype latches at session/worker construction — with the compile
    step excluded. value = fidelity bytes/step (lower is better, so
    payload bloat trips the gate); ``host_push_compression_x`` =
    fidelity/int8 rides along under the gate's higher-is-better watch."""
    import optax

    from tepdist_tpu.core.service_env import ServiceEnv
    from tepdist_tpu.parallel.pipeline import plan_pipeline
    from tepdist_tpu.rpc.inproc import (close_inproc_cluster,
                                        make_inproc_cluster)
    from tepdist_tpu.runtime.distributed_executor import (
        DistributedPipelineSession,
    )
    from tepdist_tpu.telemetry import ledger

    def loss_fn(params, x, y):
        h = x
        for i in range(4):
            h = jnp.tanh(h @ params[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    params = {f"w{i}": jax.random.normal(keys[i], (16, 16)) * 0.3
              for i in range(4)}
    x = jax.random.normal(keys[4], (8, 16))
    y = jax.random.normal(keys[5], (8, 16))

    env = ServiceEnv.get()
    prev_wd = env.tepdist_wire_dtype
    prev_led = ledger.enabled()

    def bytes_per_step(wd: str) -> float:
        env.set("TEPDIST_WIRE_DTYPE", wd)
        led = ledger.configure(enabled=True)
        prog = plan_pipeline(loss_fn, 2, 2, params, x, y)
        cluster, _serv = make_inproc_cluster(2, jax.devices()[:1])
        sess = DistributedPipelineSession(prog, cluster,
                                          optimizer=optax.sgd(1e-2))
        try:
            sess.load_variables(params)
            sess.step(x, y)          # compile + first-dispatch envelopes
            led.clear()
            for _ in range(steps):
                sess.step(x, y)
            snap = led.snapshot(clear=True)
        finally:
            sess.close()
            close_inproc_cluster(cluster)
        total = sum(s.get("tx_blob_bytes", 0.0)
                    for s in snap["verbs"].values())
        return total / steps

    try:
        fid = bytes_per_step("")
        bf16 = bytes_per_step("bfloat16")
        q8 = bytes_per_step("int8")
    finally:
        env.set("TEPDIST_WIRE_DTYPE", prev_wd)
        ledger.configure(enabled=prev_led)
    return {
        "metric": "host_push_bytes_per_step",
        "value": round(fid, 1),
        "unit": "tx blob bytes/step, 2-worker in-proc fleet "
                "(fidelity wire)",
        "bf16_bytes_per_step": round(bf16, 1),
        "int8_bytes_per_step": round(q8, 1),
        "host_push_compression_x": round(fid / q8, 3) if q8 else None,
        "steps": steps,
    }


def main() -> None:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU; jax found {devices[0].platform!r} "
            f"({devices[0].device_kind}). Nothing was measured.")
    from tepdist_tpu.core.compile_cache import configure_compile_cache
    configure_compile_cache()

    only = os.environ.get("BENCH_ONLY", "")

    headline = None
    headline_err = None
    if only in ("", "15b"):
        try:
            headline = bench_gpt2_15b()
        except Exception:
            headline_err = traceback.format_exc(limit=5)
        if headline is not None:
            # Emit the headline the moment it exists (flush!): if a later
            # secondary line wedges past the driver's bench timeout, the
            # recorded stdout still carries the real number.
            print(json.dumps(headline), flush=True)

    # Secondary lines, cheapest first; each is budgeted so a slow/seized
    # config cannot starve the rest (driver-side bench timeout), and
    # bench_extra.json is rewritten after EVERY line for the same reason.
    extra = []
    budget_deadline = time.monotonic() + float(
        os.environ.get("BENCH_EXTRA_BUDGET_S", "480"))
    _RUNTIME_BENCH_DEADLINE[0] = budget_deadline

    def flush_extra():
        try:
            tmp = f"{EXTRA_FILE}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({"extra": extra, "headline": headline,
                           "headline_error": headline_err}, f, indent=1)
            os.replace(tmp, EXTRA_FILE)   # atomic: a mid-write kill
        except Exception:                 # cannot truncate prior lines
            pass
    selected = {
        "trace": bench_trace_overhead,   # ~ms; telemetry no-op guarantee
        "ledger": bench_ledger_overhead,  # RPC ledger+flight hook cost
        "explore": bench_explore_report,  # observatory capture cost
        "qar": bench_quantized_ar,        # fidelity-vs-int8 AR wire bytes
        "zeromem": bench_zero_opt_mem,   # fidelity-vs-ZeRO opt-state bytes
        "hostpush": bench_host_push_bytes,  # fleet activation wire bytes
        "serving": bench_serving,        # continuous-batching decode tok/s
        "paged": bench_paged_capacity,   # paged-vs-slots admission capacity
        "117m": bench_gpt2_117m,
        "runtime": bench_runtime_protocol,   # pinned protocol, every round
        "flash": bench_flash_attention_long,
        "wrn": bench_wrn,
        "moe": bench_moe,
        "llama": bench_llama,
    }
    if only and only != "15b":
        selected = {k: v for k, v in selected.items() if k == only}
    elif only == "15b":
        selected = {}
    for name, fn in selected.items():
        if time.monotonic() > budget_deadline:
            extra.append({"metric": name, "skipped": "extra budget spent"})
            continue
        t0 = time.monotonic()
        try:
            line = fn()
            line["bench_seconds"] = round(time.monotonic() - t0, 1)
            extra.append(line)
        except Exception:
            extra.append({"metric": name, "error":
                          traceback.format_exc(limit=3).splitlines()[-1],
                          "bench_seconds": round(time.monotonic() - t0, 1)})
        flush_extra()
    flush_extra()

    if headline is None and headline_err is None:
        # Headline skipped (BENCH_ONLY): print the selected line so the
        # caller still reads a number from stdout.
        line = next((e for e in extra if "value" in e), None)
        if line is not None:
            print(json.dumps(line))
    failed = [e["metric"] for e in extra if "error" in e]
    if headline_err is not None:
        sys.stderr.write(headline_err)
        failed.insert(0, "headline")
    if failed:
        raise SystemExit(f"bench.py: lines that raised: {failed} "
                         f"(see {EXTRA_FILE})")


if __name__ == "__main__":
    main()
