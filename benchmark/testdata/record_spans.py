"""Record ``spans.xplane.pb`` on an attached TPU (chiprun -- python
benchmark/testdata/record_spans.py): a few steps of a tiny GPT-2 through
``plan_training``, traced through the program's own control, so the trace
holds the program's ``tepdist:`` spans beside the device's lines. Writes the
trace and what the readers of the program's spans read from it to
``chiprun_out/testdata/``; copy both into this directory."""

import json
import os
import shutil
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import optax

from benchmark import trace_reduce as tr
from benchmark.lib import cells, tracing
from benchmark.lib.host import HostLog

STEPS = 3
READERS = ("step_host_ms.train", "step_device_ms.train",
           "idle_attributed_share.train")


def read_all(trace_dir: str) -> dict:
    """What ``_program_spans.py`` and the three readers find in the trace
    under ``trace_dir`` (also run by tests/test_program_span_readers.py)."""
    from benchmark.layer_metrics import _program_spans
    summary = tr.reduce_file(tr.find_xplane(trace_dir))
    cell = types.SimpleNamespace(facts={"trace_path": trace_dir})
    found = _program_spans.self_seconds(_program_spans.within(
        _program_spans.traced(cell), summary.window))
    out = {"window_s": summary.window_s, "busy_s": summary.busy_s,
           "spans": {k: {"self_s": a, "whole_s": b, "count": n}
                     for k, (a, b, n) in sorted(found.items())}}
    folder = os.path.join(ROOT, "benchmark", "layer_metrics")
    for name in READERS:
        mod = cells.load_module(os.path.join(folder, name + ".py"),
                                "reader_" + name.replace(".", "_"))
        out[name] = mod.read(summary, {}, cell)
    return out


def main():
    from tepdist_tpu.models import gpt2
    from tepdist_tpu.train import plan_training

    assert jax.devices()[0].platform == "tpu", jax.devices()
    cfg = gpt2.CONFIGS["test"]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, 8, 32)
    plan = plan_training(lambda p, t: gpt2.loss_fn(p, t, cfg),
                         optax.adam(1e-3), params, tokens,
                         devices=jax.devices()[:1], num_micro_batches=2)
    plan.step(tokens)
    plan.step(tokens)
    host = HostLog()
    with tracing.traced_window(ROOT, "testdata-spans", host) as path:
        for _ in range(STEPS):
            with host.span("step"):
                plan.step(tokens)
            with host.span("pause"):
                time.sleep(0.02)
    out = os.path.join(ROOT, "chiprun_out", "testdata")
    os.makedirs(out, exist_ok=True)
    src = tr.find_xplane(path)
    shutil.copy(src, os.path.join(out, "spans.xplane.pb"))
    expected = {"recorded_on": jax.devices()[0].device_kind,
                "steps": STEPS, "bytes": os.path.getsize(src),
                **read_all(path)}
    with open(os.path.join(out, "spans.expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected))


if __name__ == "__main__":
    main()
