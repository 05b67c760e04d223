"""Quickest proof that the main path still starts on the chip.

    python chip_smoke.py            # one chip: phases A, B, M, Z, K, Q, N, X
    python chip_smoke.py --chips 4  # one host, four chips: that phase only

Drives GPT-2 117M at published widths (12 x 768 x 12 heads, vocab 50257,
n_ctx 1024), flash attention, bf16, AdamW, random weights from a fixed seed,
through the entry points a user calls:

  phase A  the RPC server binary (``--platform tpu``) as a child process and
           a CPU-pinned ``TepdistSession`` client: compile_train_step + 5
           ``run`` steps at batch 8 x seq 1024.
  phase B  a child process that calls ``plan_training`` on the same model,
           seed and batch and takes 5 steps; asserts the pallas kernel is in
           the compiled step and that the planner's chip table describes the
           attached device.
  phase M  a child process that calls ``plan_training`` on the newest model
           of the zoo at its ``smoke`` preset (``models/sarvam_mla.py``: a
           rank's 2 of 4 latent-attention heads at the published head widths,
           4 of 16 sigmoid-routed experts), 2 micro batches of one
           1024-token sequence, 5 steps; asserts its two attention kernels
           (forward; the backward pass in one) and the grouped matmuls are in
           the compiled step and that the walk kept one forward a layer.
  phase Z  the same for ``models/zaya.py`` at its ``smoke`` preset (8 query
           heads over 2 of the published 128, 16 experts, one a token), 2
           micro batches of one 1024-token sequence, 5 steps; asserts the
           mixing kernel pair, the flash kernels at 8 heads over 2 and the
           grouped matmuls are in the compiled step, that the walk carried
           the router's state and kept one flash forward a layer.
  phase K  the same for ``models/kimi_linear.py`` at its ``smoke`` preset
           (three delta-rule layers of 2 heads of the published 128 to one
           latent-attention layer without rotary at 128 + 64 / 128, 16 of 32
           sigmoid-routed experts, 8 a token), 2 micro batches of one
           1024-token sequence, 5 steps; asserts the three delta-rule
           kernels, the conv pair, the latent kernels and the grouped
           matmuls are in the compiled step and that the delta rule's
           forward ran twice a KDA layer and the latent layer's once.
  phase N  the same for ``models/nemotron_h.py`` at its ``smoke`` preset
           (the published pattern's first nine layers MEMEM*EME in the units
           ME ME M*E ME: four Mamba-2 layers of 8 heads of the published 64
           over 2 groups of 128 states, four layers of 8 of 32
           sigmoid-routed squared-relu experts without a gate matrix, 6 a
           token, one attention layer without positions at 2 heads of the
           published 128), 2 micro batches of one 1024-token sequence, 5
           steps; asserts the state-space pair, the conv pair, the flash
           kernels and the grouped matmuls are in the compiled step and
           that the state-space rule's forward ran twice a Mamba-2 layer.
  phase Q  the same for ``models/qwen3_next.py`` at its ``smoke`` preset
           (three scalar-decay delta-rule layers of one key head under two
           value heads of the published 128 to one gated attention layer of
           2 heads of the published 256 with 64 channels rotated, 16 of 32
           softmax-routed experts, 10 a token, a gated shared one), 2 micro
           batches of one 1024-token sequence, 5 steps; asserts the two
           scalar-decay kernels, the conv pair, the flash kernels and the
           grouped matmuls are in the compiled step and that the delta
           rule's forward ran once a Gated-DeltaNet layer.
  phase X  the same for ``models/xing.py`` at its ``smoke`` preset (a
           residual stream of four lanes of 256 mixed by hyper-connection
           maps with their 20 Sinkhorn rounds, latent attention behind a
           query latent at 4 heads of the published 128 + 64 / 128, 2 of 8
           sigmoid-routed experts, one prediction module), 2 micro batches
           of one 1024-token sequence, 5 steps; asserts the latent kernels
           and the grouped matmuls are in the compiled step, that the three
           walks (dense, expert layers, prediction module) kept one forward
           a layer, and that the second loss weighted its positions.
  --chips 4  one child owning all four chips: ``plan_training(explore=True)``
           over ``jax.devices()`` at batch 16, 5 steps, then the same 5 steps
           on ``devices[:1]``; every device must hold a shard and the
           compiled step must contain a collective.

A chip belongs to one process at a time, so this script itself never
initialises a backend other than the CPU and runs each phase's chip owner to
completion before the next starts. There is no CPU fallback: any failed
check, any phase that exits non-zero, any platform other than ``tpu`` ends
the script with a non-zero exit code and no result line.

Earlier stdout lines are one JSON object per phase (losses, planner and
first-step seconds as SET-UP times, compile-cache traffic, peak device
memory, whether the native helpers were built). On success the last line is
exactly ``{"ok": true, "device": {"platform": ..., "kind": ..., "count":
...}}`` as reported by the process that held the chip.

(``python chip_smoke.py phase_b`` / ``phase_four`` is how the script starts
its own children; a scratch script can also import the phase functions and
call them with ``platform="cpu"`` at the tiny ``test`` config to rehearse.)
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import subprocess
import sys
import time

import jax
import optax

from tepdist_tpu.core.compile_cache import (
    compile_cache_dir,
    configure_compile_cache,
)
from tepdist_tpu.models import gpt2
from tepdist_tpu.rpc.local_server import pin_client_to_cpu, spawn_local_server

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
STEPS = 5
CHILD_TIMEOUT_S = 900
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def _check(ok: bool, why: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {why}")


def _model(cfg_name: str, batch: int, seq: int):
    """Same weights and tokens in every phase: everything from SEED."""
    cfg = dataclasses.replace(gpt2.CONFIGS[cfg_name], attn="flash")
    params = gpt2.init_params(cfg, jax.random.PRNGKey(SEED))
    tokens = gpt2.fake_batch(cfg, batch, seq, seed=SEED)
    return cfg, params, tokens, optax.adamw(1e-3)


def _native_helpers() -> dict:
    """Whether the C helpers were built from source here or fell back to
    their Python twins (nothing prebuilt is committed)."""
    from tepdist_tpu.native import native_available
    from tepdist_tpu.telemetry import _fastobs
    return {"native_scheduler": native_available(),
            "fastobs": _fastobs.available()}


def _cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _cache_traffic() -> dict:
    """This process's persistent-compile-cache lookups, hits and writes, by
    the program's own compile counter (``telemetry.compile_stats``; jax
    writes an entry only for a compile of a second or more)."""
    from tepdist_tpu.telemetry import compile_stats

    stats = compile_stats()
    return {"cache_requests": stats["cache_requests"],
            "cache_hits": stats["cache_hits"],
            "cache_writes": stats["cache_misses"]}


def _own_devices(platform: str) -> list:
    """Make this process the chip's owner; a missing chip is an error."""
    jax.config.update("jax_platforms", platform)
    devices = jax.devices()
    _check(devices[0].platform == platform,
           f"wanted platform {platform!r}, jax gave {devices[0].platform!r}")
    return devices


def _device_record(devices) -> dict:
    stats = devices[0].memory_stats() or {}
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "n_devices": len(devices),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def _take_steps(step_once, phase: str) -> tuple:
    """(losses, seconds of step 0 incl. its compile). ``step_once`` returns
    the loss as a host float, so a step has finished when it returns."""
    t0 = time.perf_counter()
    losses = [step_once()]
    first = time.perf_counter() - t0
    losses += [step_once() for _ in range(STEPS - 1)]
    _check(all(math.isfinite(l) for l in losses),
           f"{phase}: losses not finite: {losses}")
    _check(losses[-1] < losses[0],
           f"{phase}: loss did not fall over {STEPS} steps: {losses}")
    return losses, round(first, 3)


def _plan(cfg_name: str, batch: int, seq: int, devices, **plan_kwargs):
    """``plan_training`` on the smoke's model: (plan, tokens, record).
    Weights are made anew for each plan — a plan's step donates them.
    ``cache_hit``: the step program's first compile in this process (the
    winner's post-check, inside ``plan_training``) was read from the
    persistent cache and nothing that long had to be written."""
    from tepdist_tpu.telemetry import traced
    from tepdist_tpu.train import plan_training

    cfg, params, tokens, tx = _model(cfg_name, batch, seq)
    before = _cache_traffic()
    t0 = time.perf_counter()
    tplan = plan_training(lambda p, t: gpt2.loss_fn(p, t, cfg), tx, params,
                          tokens, devices=devices, **plan_kwargs)
    seconds = round(time.perf_counter() - t0, 3)
    in_plan = {"plan_" + k: v - before[k]
               for k, v in _cache_traffic().items()}
    return tplan, tokens, {
        "setup_planner_seconds": seconds, **in_plan,
        # The gauges set while the step was traced, each described where it
        # is counted (tepdist_tpu/telemetry/traced.py: GROUP).
        **traced.values(),
        "cache_hit": in_plan["plan_cache_hits"] > 0
        and in_plan["plan_cache_writes"] == 0}


def _compiled_text_with_kernel(tplan, platform: str, phase: str) -> str:
    text = tplan.compiled_step_text()
    if platform == "tpu":
        _check("tpu_custom_call" in text,
               f"{phase}: no tpu_custom_call in the compiled step — the "
               "flash kernel is interpreted or replaced by the einsum")
    return text


# ---------------------------------------------------------------------------
# Phase A: CPU-pinned client + server child that owns the chip.
# ---------------------------------------------------------------------------

def phase_a(cfg_name: str = "117M", batch: int = 8, seq: int = 1024,
            platform: str = "tpu") -> dict:
    import grpc

    from tepdist_tpu.client.session import TepdistSession

    cache_dir = compile_cache_dir()    # the server's; this client has none
    entries_before = _cache_entries(cache_dir)
    # Server chatter goes to stderr: stdout carries the JSON lines only.
    proc, port = spawn_local_server(platform, stdout=sys.stderr)
    try:
        # The client builds the model while the server reaches the chip.
        cfg, params, tokens, tx = _model(cfg_name, batch, seq)

        def step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: gpt2.loss_fn(p, tokens, cfg))(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return loss, optax.apply_updates(params, updates), opt_state

        sess = TepdistSession(f"127.0.0.1:{port}")
        deadline = time.monotonic() + 180
        while True:
            _check(proc.poll() is None,
                   f"phase A: server exited with code {proc.returncode} "
                   f"before listening (no {platform} device?)")
            try:
                sess.client.wait_ready(timeout=2.0)
                break
            except grpc.FutureTimeoutError:
                _check(time.monotonic() < deadline,
                       "phase A: server not ready after 180 s")
        info = sess.client.ping()
        _check(info["platform"] == platform,
               f"phase A: server reports platform {info['platform']!r}, "
               f"wanted {platform!r}")
        summary = sess.compile_train_step(step, params, tx.init(params),
                                          tokens)
        losses, first = _take_steps(lambda: sess.run(tokens), "phase A")
        sess.close()
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    new_entries = _cache_entries(cache_dir) - entries_before
    return {
        "phase": "A", "entry": "rpc.server + TepdistSession",
        "model": f"gpt2-{cfg_name}", "batch": batch, "seq": seq,
        "platform": info["platform"], "device_kind": info["device_kind"],
        "n_devices": info["n_devices"], "axes": summary["axes"],
        "losses": losses,
        "setup_planner_seconds": summary["planner_seconds"],
        "setup_first_step_seconds": first,
        # The server owns the chip: no verb reports its cache traffic or
        # its memory. New files in its cache directory are all a client
        # can count; the first-step seconds say more.
        "cache_dir": cache_dir, "cache_new_entries": new_entries,
        "cache_hit": None, "peak_bytes_in_use": None,
        **_native_helpers(),
    }


# ---------------------------------------------------------------------------
# Phase B: plan_training in the process that owns the chip.
# ---------------------------------------------------------------------------

def phase_b(cfg_name: str = "117M", batch: int = 8, seq: int = 1024,
            platform: str = "tpu") -> dict:
    from tepdist_tpu.parallel.performance_utils import (
        chip_spec,
        chip_spec_for_device_kind,
    )

    devices = _own_devices(platform)[:1]
    cache_dir = configure_compile_cache()
    attached = chip_spec_for_device_kind(devices[0].device_kind).name
    _check(chip_spec().name == attached,
           f"phase B: planner prices a {chip_spec().name!r} chip but the "
           f"attached device is {devices[0].device_kind!r} ({attached!r})")

    tplan, tokens, planned = _plan(cfg_name, batch, seq, devices)
    text = _compiled_text_with_kernel(tplan, platform, "phase B")
    losses, first = _take_steps(lambda: tplan.step(tokens), "phase B")
    return {
        "phase": "B", "entry": "plan_training",
        "model": f"gpt2-{cfg_name}", "batch": batch, "seq": seq,
        **_device_record(devices),
        "axes": list(tplan.parallel_plan.topology.device_axes()),
        "chip_spec": chip_spec().name,
        "kernel_calls_in_hlo": text.count("tpu_custom_call"),
        "losses": losses, **planned,
        "setup_first_step_seconds": first,
        "cache_dir": cache_dir, **_cache_traffic(),
        **_native_helpers(),
    }


# ---------------------------------------------------------------------------
# Phase M: the zoo's newest model through plan_training, its kernels compiled.
# ---------------------------------------------------------------------------

def _plan_zoo_model(model, cfg, batch: int, seq: int, platform: str):
    """``plan_training`` of one of the zoo's expert models (stacked
    parameters from the fixed seed, 2 micro batches, the bias's optimizer)
    on one chip: (devices, plan, tokens, the traced step's gauges)."""
    from tepdist_tpu.optim import make_optimizer
    from tepdist_tpu.telemetry import traced
    from tepdist_tpu.train import plan_training

    devices = _own_devices(platform)[:1]
    configure_compile_cache()
    params = model.stacked_init_params(cfg, jax.random.PRNGKey(SEED))
    tokens = model.fake_batch(cfg, batch, seq, seed=SEED)
    tplan = plan_training(
        lambda p, t: model.loss_fn(p, t, cfg),
        make_optimizer({"name": "adamw_bf16_router_bias",
                        "learning_rate": 1e-3, "bias_rate": 0.001}),
        params, tokens, devices=devices, explore=False, num_micro_batches=2)
    return devices, tplan, tokens, traced.values()


def _step_zoo_model(phase: str, model: str, devices, tplan, tokens, gauges,
                    platform: str, kernels) -> dict:
    """``kernels`` are in the compiled step (on the chip); five steps; the
    phase's record."""
    text = _compiled_text_with_kernel(tplan, platform, f"phase {phase}")
    if platform == "tpu":
        for kernel in kernels:
            _check(kernel in text,
                   f"phase {phase}: no {kernel} in the compiled step")
        _check("tepdist_mla_dq" not in text and "tepdist_flash_dq" not in text,
               f"phase {phase}: a backward pass is not one kernel")
    losses, first = _take_steps(lambda: tplan.step(tokens), f"phase {phase}")
    return {"phase": phase, "entry": "plan_training", "model": model,
            "batch": tokens.shape[0], "seq": tokens.shape[1] - 1,
            **_device_record(devices), "losses": losses, **gauges,
            "setup_first_step_seconds": first}


def phase_mla(preset: str = "smoke", batch: int = 2, seq: int = 1024,
              platform: str = "tpu") -> dict:
    from tepdist_tpu.models import sarvam_mla

    cfg = dataclasses.replace(sarvam_mla.CONFIGS[preset], remat=True)
    devices, tplan, tokens, gauges = _plan_zoo_model(
        sarvam_mla, cfg, batch, seq, platform)
    layers = cfg.num_hidden_layers
    _check(gauges["attn_kept_calls"] == gauges["mla_fwd_calls"]
           == gauges["mla_bwd_calls"] == layers,
           f"phase M: the walk kept {gauges['attn_kept_calls']} forward "
           f"passes and counted {gauges['mla_bwd_calls']} backward calls "
           f"of {layers} layers")
    return _step_zoo_model(
        "M", f"sarvam_mla-{preset}", devices, tplan, tokens, gauges, platform,
        ("tepdist_mla_fwd", "tepdist_mla_dkv", "tepdist_gmm_fwd"))


# ---------------------------------------------------------------------------
# Phase Z: compressed attention's mixing kernels and a top-1 expert layer
# whose router's state the walk carries.
# ---------------------------------------------------------------------------

def phase_zaya(preset: str = "smoke", batch: int = 2, seq: int = 1024,
               platform: str = "tpu") -> dict:
    from tepdist_tpu.models import zaya

    cfg = zaya.CONFIGS[preset]
    devices, tplan, tokens, gauges = _plan_zoo_model(
        zaya, cfg, batch, seq, platform)
    layers = cfg.num_hidden_layers
    _check(gauges["attn_kept_calls"] == layers
           and gauges["cca_mix_calls"] == 2 * layers,
           f"phase Z: the walk kept {gauges['attn_kept_calls']} forward "
           f"passes and ran the mixing {gauges['cca_mix_calls']} times a "
           f"micro batch over {layers} layers")
    _check(gauges["router_carry_bytes"]
           == batch // 2 * seq * cfg.router_hidden_size * 4,
           f"phase Z: the router's carry reads "
           f"{gauges['router_carry_bytes']} bytes")
    return _step_zoo_model(
        "Z", f"zaya-{preset}", devices, tplan, tokens, gauges, platform,
        ("tepdist_cca_mix_fwd", "tepdist_cca_mix_bwd", "tepdist_flash_fwd",
         "tepdist_gmm_fwd"))


# ---------------------------------------------------------------------------
# Phase K: the delta-rule kernels beside a latent-attention layer without
# rotary, four walks of unequal shape.
# ---------------------------------------------------------------------------

def phase_kimi(preset: str = "smoke", batch: int = 2, seq: int = 1024,
               platform: str = "tpu") -> dict:
    from tepdist_tpu.models import kimi_linear

    cfg = kimi_linear.CONFIGS[preset]
    devices, tplan, tokens, gauges = _plan_zoo_model(
        kimi_linear, cfg, batch, seq, platform)
    kda = cfg.mixers.count(kimi_linear.KDA)
    latent = cfg.num_hidden_layers - kda
    _check(gauges["kda_calls"] == kda and gauges["mla_fwd_calls"] == latent
           and gauges["attn_kept_calls"] == kda + latent,
           f"phase K: the delta rule's forward ran {gauges['kda_calls']} "
           f"times a micro batch over {kda} layers and the latent layer's "
           f"{gauges['mla_fwd_calls']} over {latent}; the walks kept "
           f"{gauges['attn_kept_calls']} calls' forward")
    _check(gauges["kda_state_bytes"]
           == batch // 2 * cfg.kda_num_heads * cfg.kda_head_dim ** 2 * 4,
           f"phase K: a layer's state reads {gauges['kda_state_bytes']} "
           "bytes")
    return _step_zoo_model(
        "K", f"kimi_linear-{preset}", devices, tplan, tokens, gauges,
        platform,
        ("tepdist_kda_fwd", "tepdist_kda_bwd", "tepdist_conv_fwd",
         "tepdist_conv_bwd", "tepdist_mla_fwd", "tepdist_mla_dkv",
         "tepdist_gmm_fwd"))


# ---------------------------------------------------------------------------
# Phase Q: the scalar-decay delta-rule kernels over shared key heads beside a
# gated attention layer at a head width of 256, two walks of unequal shape.
# ---------------------------------------------------------------------------

def phase_qwen(preset: str = "smoke", batch: int = 2, seq: int = 1024,
               platform: str = "tpu") -> dict:
    from tepdist_tpu.models import qwen3_next

    cfg = qwen3_next.CONFIGS[preset]
    devices, tplan, tokens, gauges = _plan_zoo_model(
        qwen3_next, cfg, batch, seq, platform)
    gdn = cfg.kinds.count(qwen3_next.GDN)
    _check(gauges["gdn_calls"] == gdn and gauges["kda_calls"] == 0
           and gauges["attn_kept_calls"] == cfg.num_hidden_layers,
           f"phase Q: the delta rule's forward ran {gauges['gdn_calls']} "
           f"times a micro batch over {gdn} layers; the walks kept "
           f"{gauges['attn_kept_calls']} calls' forward of "
           f"{cfg.num_hidden_layers} layers")
    _check(gauges["gdn_state_bytes"] == batch // 2
           * cfg.linear_num_value_heads * cfg.linear_key_head_dim ** 2 * 4
           and gauges["attn_rotary_dim"] == cfg.rotary_dim,
           f"phase Q: a layer's state reads {gauges['gdn_state_bytes']} "
           f"bytes and {gauges['attn_rotary_dim']} channels are rotated")
    return _step_zoo_model(
        "Q", f"qwen3_next-{preset}", devices, tplan, tokens, gauges,
        platform,
        ("tepdist_gdn_fwd", "tepdist_gdn_bwd", "tepdist_conv_fwd",
         "tepdist_conv_bwd", "tepdist_flash_fwd", "tepdist_flash_dkv",
         "tepdist_gmm_fwd"))


# ---------------------------------------------------------------------------
# Phase N: the state-space-dual kernels (heads of 64, two a lane block, over
# shared B/C groups), ungated experts and a layer that is one part alone, in
# units of two and three layers a walk.
# ---------------------------------------------------------------------------

def phase_nemotron(preset: str = "smoke", batch: int = 2, seq: int = 1024,
                   platform: str = "tpu") -> dict:
    from tepdist_tpu.models import nemotron_h

    cfg = nemotron_h.CONFIGS[preset]
    devices, tplan, tokens, gauges = _plan_zoo_model(
        nemotron_h, cfg, batch, seq, platform)
    mamba = cfg.kinds.count(nemotron_h.MAMBA)
    # The state-space forward runs in a unit's forward and again in its
    # recomputation; the attention layer's forward is kept by its walk.
    _check(gauges["ssd_calls"] == 2 * mamba
           and gauges["ssm_conv_calls"] == 2 * mamba
           and gauges["attn_kept_calls"] == cfg.kinds.count(nemotron_h.ATTN),
           f"phase N: the state-space rule's forward ran "
           f"{gauges['ssd_calls']} times a micro batch over {mamba} layers; "
           f"the walks kept {gauges['attn_kept_calls']} calls' forward")
    _check(gauges["ssd_state_bytes"] == batch // 2 * cfg.mamba_num_heads
           * cfg.mamba_head_dim * cfg.ssm_state_size * 4,
           f"phase N: a layer's state reads {gauges['ssd_state_bytes']} "
           "bytes")
    return _step_zoo_model(
        "N", f"nemotron_h-{preset}", devices, tplan, tokens, gauges,
        platform,
        ("tepdist_ssd_fwd", "tepdist_ssd_bwd", "tepdist_conv_fwd",
         "tepdist_conv_bwd", "tepdist_flash_fwd", "tepdist_flash_dkv",
         "tepdist_gmm_fwd"))


# ---------------------------------------------------------------------------
# Phase X: a residual stream of four lanes, a query latent, and a second loss
# through the shared head; three walks.
# ---------------------------------------------------------------------------

def phase_xing(preset: str = "smoke", batch: int = 2, seq: int = 1024,
               platform: str = "tpu") -> dict:
    from tepdist_tpu.models import xing

    cfg = xing.CONFIGS[preset]
    devices, tplan, tokens, gauges = _plan_zoo_model(
        xing, cfg, batch, seq, platform)
    layers = cfg.num_hidden_layers + cfg.num_nextn_predict_layers
    _check(gauges["attn_kept_calls"] == gauges["mla_fwd_calls"]
           == gauges["mla_bwd_calls"] == layers,
           f"phase X: the walks kept {gauges['attn_kept_calls']} forward "
           f"passes and counted {gauges['mla_bwd_calls']} backward calls "
           f"of {layers} layers, the prediction module's among them")
    _check(gauges["residual_lanes"] == cfg.hc_mult
           and gauges["mhc_sinkhorn_rounds"] == cfg.hc_sinkhorn_iters
           and gauges["mhc_stream_bytes"]
           == batch // 2 * seq * cfg.hc_mult * cfg.hidden_size * 2
           and gauges["mtp_depth"] == 1
           and gauges["ce_weighted_positions"] == batch // 2 * seq,
           f"phase X: the gauges read {gauges['residual_lanes']} lanes of "
           f"{gauges['mhc_stream_bytes']} bytes, {gauges['mtp_depth']} "
           f"prediction module(s) and {gauges['ce_weighted_positions']} "
           "weighted positions")
    return _step_zoo_model(
        "X", f"xing-{preset}", devices, tplan, tokens, gauges, platform,
        ("tepdist_mla_fwd", "tepdist_mla_dkv", "tepdist_gmm_fwd"))


# ---------------------------------------------------------------------------
# Four chips: explored layout over the host's devices vs the same steps on
# one of them, in one process that owns all four.
# ---------------------------------------------------------------------------

def phase_four(cfg_name: str = "117M", batch: int = 16, seq: int = 1024,
               platform: str = "tpu", n_devices: int = 4) -> dict:
    devices = _own_devices(platform)
    _check(len(devices) == n_devices,
           f"four-chip phase: {len(devices)} devices attached, wanted "
           f"{n_devices}")
    cache_dir = configure_compile_cache()

    # No gradient accumulation: the SPMD planner cannot see into the GA
    # scan, and a plan with micro batches shards nothing (every chip would
    # run the whole step, which plan_training warns about).
    tplan, tokens, planned = _plan(cfg_name, batch, seq, devices,
                                   explore=True, num_micro_batches=1)
    winner = tplan.exploration_report["winner"]
    _check(winner["kind"] == "spmd",
           f"four-chip phase: winner {winner} is not an SPMD plan; the "
           "shard and collective checks below read one compiled program")
    text = _compiled_text_with_kernel(tplan, platform, "four-chip phase")
    found = [c for c in COLLECTIVES if c in text]
    _check(bool(found), "four-chip phase: no collective in the compiled "
           "step — nothing crosses chips")
    losses, first = _take_steps(lambda: tplan.step(tokens),
                                "four-chip phase")
    # Code that has never seen a second chip may put everything on the
    # first: every device must hold a shard of some state array.
    holders = {s.device.id for leaf in tplan._device_state()
               for s in leaf.addressable_shards}
    _check(holders == {d.id for d in devices},
           f"four-chip phase: state shards live on devices "
           f"{sorted(holders)} only, of {[d.id for d in devices]}")
    record = _device_record(devices)
    del tplan

    # Same weights, tokens and steps on one chip.
    one, tokens, _ = _plan(cfg_name, batch, seq, devices[:1],
                           num_micro_batches=1)
    one_losses, _ = _take_steps(lambda: one.step(tokens),
                                "one-chip comparison")
    for i, (a, b) in enumerate(zip(losses, one_losses)):
        _check(abs(a - b) <= 2e-2 * abs(b),
               f"four-chip phase: step {i} loss {a} vs one chip {b} "
               "differ by more than 2e-2 relative")
    return {
        "phase": "four", "entry": "plan_training(explore=True)",
        "model": f"gpt2-{cfg_name}", "batch": batch, "seq": seq,
        **record,
        "winner": winner, "collectives_in_hlo": found,
        "devices_holding_shards": sorted(holders),
        "losses": losses, "one_chip_losses": one_losses, **planned,
        "setup_first_step_seconds": first,
        "cache_dir": cache_dir, **_cache_traffic(),
        **_native_helpers(),
    }


CHILD_PHASES = {"phase_b": phase_b, "phase_four": phase_four,
                "phase_mla": phase_mla, "phase_zaya": phase_zaya,
                "phase_kimi": phase_kimi, "phase_qwen": phase_qwen,
                "phase_nemotron": phase_nemotron, "phase_xing": phase_xing}


def _run_child(phase: str) -> dict:
    """Run one chip-owning phase as a child to completion; its last stdout
    line is its record. A non-zero exit or a timeout fails the script."""
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), phase], cwd=HERE,
        stdout=subprocess.PIPE, text=True, timeout=CHILD_TIMEOUT_S)
    _check(out.returncode == 0,
           f"{phase} child exited with code {out.returncode}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("phase", nargs="?", choices=sorted(CHILD_PHASES),
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.phase:
        _emit(CHILD_PHASES[args.phase]())
        return

    # This process stays off the chip; each phase's child owns it in turn.
    pin_client_to_cpu()
    if args.chips == 4:
        holder = _run_child("phase_four")
        _emit(holder)
    else:
        a = phase_a()
        _emit(a)
        holder = _run_child("phase_b")
        _emit(holder)
        _check((a["platform"], a["device_kind"])
               == (holder["platform"], holder["device_kind"]),
               "phases A and B ran on different devices")
        _check(abs(a["losses"][0] - holder["losses"][0])
               <= 1e-2 * abs(holder["losses"][0]),
               f"step-0 loss of phase A {a['losses'][0]} and phase B "
               f"{holder['losses'][0]} differ by more than 1e-2 relative "
               "(same weights, same tokens, no update yet)")
        _emit(_run_child("phase_mla"))
        _emit(_run_child("phase_zaya"))
        _emit(_run_child("phase_kimi"))
        _emit(_run_child("phase_qwen"))
        _emit(_run_child("phase_nemotron"))
        _emit(_run_child("phase_xing"))
    _check(holder["platform"] == "tpu" and holder["n_devices"] == args.chips,
           f"ran on {holder['n_devices']} {holder['platform']} device(s), "
           f"wanted {args.chips} tpu")
    print(json.dumps({"ok": True, "device": {
        "platform": holder["platform"], "kind": holder["device_kind"],
        "count": holder["n_devices"]}}), flush=True)


if __name__ == "__main__":
    main()
