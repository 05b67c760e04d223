"""MFU lever sweep for the GPT-2 1.5B single-chip headline.

The lever list (GA shape with chunked CE, remat-policy variants, flash tile
sizes, donated batch buffers) run as one grid in ONE command; the winning
cell maps onto ``bench.py``'s BENCH_15B_* settings.

Usage (on a live TPU):

    python tools/mfu_sweep.py                 # full grid (~30-60 min)
    python tools/mfu_sweep.py --quick         # GA shapes only
    python tools/mfu_sweep.py --config 1.5B --seq 1024

Each cell reports tokens/s/chip and 6N-accounting MFU against the attached
chip's peak (looked up by ``device_kind``; unknown kinds are an error).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mfu(tokens_per_sec: float, n_params: float, peak_tflops: float) -> float:
    # 6N flops/token accounting (fwd 2N + bwd 4N).
    return tokens_per_sec * 6.0 * n_params / (peak_tflops * 1e12)


def run_cell(cfg_name: str, seq: int, batch: int, micro: int,
             remat_policy: str, block_q: int, block_k: int,
             loss_chunk: int, steps: int = 8) -> dict:
    import dataclasses

    import jax

    from tepdist_tpu.models import gpt2
    from tepdist_tpu.optim import adamw_bf16
    from tepdist_tpu.parallel.performance_utils import (
        chip_spec_for_device_kind,
    )
    from tepdist_tpu.train import plan_training

    # Mirrors bench.py's headline construction exactly (stacked params +
    # scan-over-layers loss + bf16-moment adamw) so winning cells map 1:1
    # onto the BENCH_15B_* env knobs.
    cfg = dataclasses.replace(
        gpt2.CONFIGS[cfg_name], attn="flash", remat=True,
        remat_policy=remat_policy, flash_block_q=block_q,
        flash_block_k=block_k, loss_chunk=loss_chunk)
    params = gpt2.stacked_init_params(cfg, jax.random.PRNGKey(0))
    n_params = gpt2.num_params(cfg)
    tokens = gpt2.fake_batch(cfg, batch, seq)
    tx = adamw_bf16(1e-4)
    plan = plan_training(lambda p, t: gpt2.loss_fn_stacked(p, t, cfg),
                         tx, params, tokens, num_micro_batches=micro)
    plan.step(tokens)          # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        plan.step(tokens)
    dt = (time.perf_counter() - t0) / steps
    tps = batch * seq / dt
    spec = chip_spec_for_device_kind(jax.devices()[0].device_kind)
    return {"tokens_per_sec": round(tps, 1),
            "mfu": round(_mfu(tps, n_params, spec.bf16_tflops), 4),
            "step_ms": round(dt * 1e3, 1)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="1.5B")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="mfu_sweep.json")
    args = ap.parse_args()

    import jax
    if jax.devices()[0].platform == "cpu":
        sys.stderr.write("mfu_sweep needs a TPU backend\n")
        raise SystemExit(2)

    # Lever grid: GA shape x remat x flash tiles.
    ga_shapes = [(48, 16), (64, 16), (48, 12), (64, 32)]   # (batch, micro)
    remats = ["full"] if args.quick else ["full", "dots", "dots_no_batch"]
    blocks = [(512, 512)] if args.quick else [(512, 512), (256, 512),
                                              (512, 256), (1024, 512)]
    results = []
    for (batch, micro), remat, (bq, bk) in itertools.product(
            ga_shapes, remats, blocks):
        cell = {"batch": batch, "micro": micro, "remat": remat,
                "block_q": bq, "block_k": bk}
        try:
            cell.update(run_cell(args.config, args.seq, batch, micro,
                                 remat, bq, bk, loss_chunk=512))
        except Exception as e:  # noqa: BLE001 — OOM cells are data too
            cell["error"] = repr(e)[:200]
        results.append(cell)
        print(json.dumps(cell), flush=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)

    ok = [c for c in results if "tokens_per_sec" in c]
    if ok:
        best = max(ok, key=lambda c: c["tokens_per_sec"])
        print("BEST:", json.dumps(best))
        print("now re-run `python bench.py` with BENCH_15B_BATCH/"
              "BENCH_15B_MICRO/BENCH_15B_REMAT/BENCH_15B_BLOCK_Q/"
              "BENCH_15B_BLOCK_K set to the winning cell")


if __name__ == "__main__":
    main()
