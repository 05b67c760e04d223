"""Re-record ``small.xplane.pb`` on an attached TPU (chiprun -- python
benchmark/testdata/record.py). Writes the trace and what the reduction read
from it to ``chiprun_out/testdata/``; copy both into this directory."""

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

from benchmark import trace_reduce as tr
from benchmark.lib.host import HostLog
from benchmark.lib import tracing


def main():
    assert jax.devices()[0].platform == "tpu", jax.devices()

    @jax.jit
    def small_step(x, w):
        for _ in range(3):
            x = jnp.tanh(x @ w)
        return x, jnp.sum(x)

    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.full((512, 512), 0.01, jnp.bfloat16)
    small_step(x, w)[1].block_until_ready()
    host = HostLog()
    with tracing.traced_window(ROOT, "testdata-small", host) as path:
        for _ in range(5):
            with host.span("step"):
                x, s = small_step(x, w)
                float(s)
            with host.span("pause"):
                time.sleep(0.05)
    out = os.path.join(ROOT, "chiprun_out", "testdata")
    os.makedirs(out, exist_ok=True)
    src = tr.find_xplane(path)
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    s = tr.reduce_file(src)
    expected = {
        "device_kind": jax.devices()[0].device_kind,
        "window_s": s.window_s, "busy_s": s.busy_s,
        "idle_gaps": dict(s.idle_gaps),
        "module_runs": len(s.module_runs(lambda n: "small_step" in n)),
        "top_ops": [n for n, _ in tr.breakdown(s)["device_ops"]],
        "bytes": os.path.getsize(src)}
    with open(os.path.join(out, "small.expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected))


if __name__ == "__main__":
    main()
