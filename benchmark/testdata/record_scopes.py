"""Record ``scopes.xplane.pb`` on an attached TPU (chiprun -- python
benchmark/testdata/record_scopes.py): three steps of a tiny GPT-2, its layers
stacked and walked, two micro batches, through ``plan_training`` and the
program's own trace control, so the trace's operations carry the program's
``part_*`` and ``walk_*`` scopes in their ``tf_op``. Writes the trace and what
the ``scope_*_share.train`` readers read from it to ``chiprun_out/testdata/``;
copy both into this directory."""

import dataclasses
import json
import os
import shutil
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr
from benchmark.lib import cells

STEPS = 3
READERS = tuple(f"scope_{name}_share.train" for name in (
    "embed", "mixer", "mlp", "moe", "head_loss", "optimizer", "unscoped",
    "recompute"))


def read_all(trace_dir: str) -> dict:
    """What ``_scopes.py`` and the eight readers find in the trace under
    ``trace_dir`` (also run by tests/test_scopes.py): the readers' values,
    the operations with a ``tf_op`` and the part x phase seconds."""
    from benchmark.layer_metrics import _scopes
    xplane = tr.find_xplane(trace_dir)
    summary = tr.reduce_file(xplane)
    cell = types.SimpleNamespace(facts={"trace_path": trace_dir})
    folder = os.path.join(ROOT, "benchmark", "layer_metrics")
    out = {"readers": {}}
    for name in READERS:
        mod = cells.load_module(os.path.join(folder, name + ".py"),
                                "reader_" + name.replace(".", "_"))
        out["readers"][name] = mod.read(summary, {}, cell)
    found = cell.facts["scopes"]
    scopes = _scopes.operation_scopes(xplane)
    out.update(
        operations=len(scopes), with_tf_op=sum(map(bool, scopes.values())),
        total_s=found["total_s"],
        by_phase={part: {phase: s for (p, phase), s
                         in sorted(found["by_phase"].items()) if p == part}
                  for part in sorted({p for p, _ in found["by_phase"]})})
    return out


def main():
    import jax
    import optax

    from benchmark.lib import tracing
    from benchmark.lib.host import HostLog
    from tepdist_tpu.models import gpt2
    from tepdist_tpu.train import plan_training

    assert jax.devices()[0].platform == "tpu", jax.devices()
    cfg = dataclasses.replace(gpt2.CONFIGS["test"], remat=True)
    params = gpt2.stacked_init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, 8, 32)
    plan = plan_training(lambda p, t: gpt2.loss_fn_stacked(p, t, cfg),
                         optax.adam(1e-3), params, tokens,
                         devices=jax.devices()[:1], num_micro_batches=2)
    plan.step(tokens)
    plan.step(tokens)
    host = HostLog()
    with tracing.traced_window(ROOT, "testdata-scopes", host) as path:
        for _ in range(STEPS):
            with host.span("step"):
                plan.step(tokens)
    out = os.path.join(ROOT, "chiprun_out", "testdata")
    os.makedirs(out, exist_ok=True)
    src = tr.find_xplane(path)
    shutil.copy(src, os.path.join(out, "scopes.xplane.pb"))
    expected = {"recorded_on": jax.devices()[0].device_kind,
                "steps": STEPS, "bytes": os.path.getsize(src),
                **read_all(path)}
    with open(os.path.join(out, "scopes.expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected))


if __name__ == "__main__":
    main()
