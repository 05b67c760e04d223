#!/usr/bin/env python3
"""Is a benchmark cell's step still the same program? Compiles the cell's
step for a DESCRIBED v5e (no chip, nothing runs) from the cell's own files
under ``benchmark/`` and counts the optimized HLO's instructions by (opcode,
result shape with layout, a custom call's kernel). In a copy of the parent
``JAX_PLATFORMS=cpu python tools/same_ops.py <cell> --save p.json``, in the
change ``... <cell> --against p.json``: the last line is one JSON object with
``same`` and what differs (exit 1 if anything), beside the gauges the step's
trace set (``telemetry/traced.py``: a kernel's calls a micro batch). It says
the operations are the parent's, not what they cost."""

import argparse
import collections
import hashlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.described_chip import described_v5e  # noqa: E402

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = "
                          r"(?P<shape>\(.*?\)|\S+) (?P<op>[\w\-]+)\(")


def compiled_step(cell_name: str, device):
    """The cell's step as ``plan_training`` builds it on one chip, state
    donated, compiled for ``device`` at the cell's own shapes; beside it the
    shapes of the parameters it was built for."""
    import jax
    import optax
    from benchmark.lib import cells
    from tepdist_tpu.parallel.sync_free import build_ga_step
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:                                    # kernels as on the chip
        cell = cells.load_cell(cell_name)
        builder, t = cells.builder_for(cell), cell.traffic
        loss = builder.program_loss_fn(cell.config)
        tx = builder.program_optimizer(cell.config)

        def apply_fn(p, s, g):
            updates, s = tx.update(g, s, p)
            return optax.apply_updates(p, updates), s

        params = jax.eval_shape(lambda: builder.to_program(
            builder.make_params(cell.config, 1), cell.config))
        one_chip = jax.sharding.SingleDeviceSharding(device)
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            (params, jax.eval_shape(tx.init, params), jax.ShapeDtypeStruct(
                (int(t["batch"]), int(t["seq"]) + 1), "int32")))
        step = build_ga_step(lambda p, b: jax.value_and_grad(loss)(p, b),
                             apply_fn, int(t["num_micro_batches"]),
                             loss_fn=loss)
        return (jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile(),
                params)
    finally:
        jax.default_backend = backend


def kernel(name: str) -> str:
    """A custom call's kernel: its instruction's name less XLA's numbering.
    A Pallas kernel's instruction is named from its place in the name stack
    (``jvp_tepdist_flash_fwd__c1__s0.125__h25_`` under one scope,
    ``tepdist_flash_fwd__c1__s0.125__h25`` under another), so of such a name
    the kernel's own part counts and not what stands round it."""
    own = re.search(r"tepdist_.*", re.sub(r"\.\d+$", "", name))
    return own[0].rstrip("_") if own else re.sub(r"[.\d]+$", "", name)


def histogram(text: str) -> dict:
    """"opcode shape [kernel]" -> count."""
    counts = collections.Counter()
    for m in filter(None, map(_INSTRUCTION.match, text.splitlines())):
        name = kernel(m["name"]) if m["op"] == "custom-call" else ""
        counts[" ".join(filter(None, (m["op"], m["shape"], name)))] += 1
    return dict(sorted(counts.items()))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("cell")
    ap.add_argument("--save", help="write the counts to this file")
    ap.add_argument("--against", help="compare with counts saved earlier")
    a = ap.parse_args()
    with described_v5e() as devices:
        compiled, _ = compiled_step(a.cell, devices[0])
    from tepdist_tpu.telemetry import traced
    counts = histogram(compiled.as_text())
    out = {"cell": a.cell, "gauges": {k: v for k, v in traced.values().items()
                                      if v},
           "instructions": sum(counts.values()),
           "kinds": len(counts), "digest": hashlib.sha256(
               json.dumps(counts).encode()).hexdigest()[:16],
           "peak_bytes": compiled.memory_analysis().peak_memory_in_bytes}
    if a.save:
        with open(a.save, "w") as f:
            json.dump({**out, "counts": counts}, f, indent=1)
    if a.against:
        with open(a.against) as f:
            theirs = json.load(f)["counts"]
        out["differs"] = {k: [theirs.get(k, 0), counts.get(k, 0)]
                          for k in sorted(set(theirs) | set(counts))
                          if theirs.get(k, 0) != counts.get(k, 0)}
        out["same"] = not out["differs"]
    print(json.dumps(out))
    return 0 if out.get("same", True) else 1


if __name__ == "__main__":
    sys.exit(main())
