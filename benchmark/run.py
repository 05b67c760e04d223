"""One command, one cell, one run.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

A new process that owns the cell's chips. It fails (non-zero exit, no result
line) when JAX offers no TPU or fewer chips than the cell asks for. It makes
weights and traffic from ``--seed``, warms up the cell's own shapes, measures
for ``--seconds`` and prints, as the last line of its standard output, one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and, traced, ``breakdown``). With ``--trace 0`` the metrics are
the cell's end-to-end metrics and the profiler is never started; with
``--trace 1`` a short window runs under ``jax.profiler`` in the measured
window's place and the metrics are the cell's per-layer metrics. ``--trace 2``
is a ``--trace 0`` run, the same process up to the moment the measured window
closes, followed by a short traced window of the same traffic: its line
holds the end-to-end metrics of the measured window and the per-layer
metrics of the traced one side by side.

In every mode the program's span recorder (``tepdist_tpu.telemetry``) is on
from process start, so set-up leaves its spans, and every driver switches it
off as its measured window opens (``lib/recorder.py`` holds both switches);
a traced window switches it on again through the program's own control,
which also starts the profiler.

Which configuration, traffic, driver loop and readers a cell uses is data:
``BENCHMARK.json`` and the files it names under ``benchmark/``.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()          # process start, as near as Python allows

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    args = parser.parse_args(argv)

    from benchmark.lib import cells, device, recorder
    from benchmark.lib.host import CompileWatch, HostLog

    try:
        cell = cells.load_cell(args.workload, ROOT)
        recorder.on()               # imports the system under test
    except (cells.BenchError, ImportError) as e:
        device.fail(str(e))
    devices = device.own_chips(cell.chips)
    peaks = device.peaks_for(devices[0].device_kind, cell.bench_dir)
    cache_dir = device.configure_cache(ROOT)
    host = HostLog()
    host.t0 = _T0
    compiles = CompileWatch()
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "seconds": args.seconds, "trace": args.trace,
                      "platform": devices[0].platform,
                      "device_kind": devices[0].device_kind,
                      "device_count": len(devices),
                      "compile_cache": cache_dir}), flush=True)

    builder = cells.builder_for(cell)
    driver = cells.driver_for(cell)
    out = driver.run(cell, builder, devices, args.seed, args.seconds,
                     args.trace, host, compiles)

    record = device.device_record(devices, out.get("program_peak_bytes", 0))
    end_to_end = dict(out["end_to_end"])
    end_to_end["setup_s"] = host.counters["setup_s"]
    wanted = {m["name"]: m for m in cell.end_to_end}
    missing = set(wanted) - set(end_to_end)
    if missing:
        device.fail(f"the driver did not measure {sorted(missing)}")
    if "n_params" in cell.facts and "train_tokens_per_s_chip" in end_to_end:
        rate = end_to_end["train_tokens_per_s_chip"]
        print(f"mfu: {6.0 * cell.facts['n_params'] * rate / peaks['bf16_flops_per_s']:.4f}"
              f" (6 x {cell.facts['n_params']} params x {rate:.1f} tokens/s/chip"
              f" / {peaks['bf16_flops_per_s']:.3g} FLOP/s; recompute not counted;"
              f" {devices[0].device_kind} x {len(devices)})", flush=True)
    print("host spans (s): " + json.dumps(
        {n: round(host.seconds(n), 4)
         for n in dict.fromkeys(s[0] for s in host.spans)}), flush=True)

    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    measured = {name: {"value": float(end_to_end[name]), "unit": m["unit"]}
                for name, m in wanted.items()}
    if args.trace:
        from benchmark import trace_reduce
        from benchmark.lib import tracing
        summary = tracing.reduce_trace(cell.facts["trace_path"])
        host_facts = {"spans": host, "counters": host.counters,
                      "peaks": peaks, **out["host"]}
        metrics = cells.read_layer_metrics(cell, summary, host_facts)
        lacking = {m["name"] for m in cell.per_layer} - set(metrics)
        if lacking:
            print(f"per-layer metrics with nothing to read: "
                  f"{sorted(lacking)}", flush=True)
        record["busy_s"] = summary.busy_s
        record["window_s"] = summary.window_s
        result["metrics"] = metrics
        result["breakdown"] = trace_reduce.breakdown(summary)
        if args.trace == 2:
            result["metrics"] = {**measured, **metrics}
            tracing.discard(cell.facts["trace_path"])
    else:
        result["metrics"] = measured
    result["device"] = record
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
