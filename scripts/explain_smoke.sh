#!/usr/bin/env bash
# Exploration-observatory smoke: prove the PR-12 decision-forensics
# pipeline end to end.
#
#   1. LEDGER + SCOREBOARD: tools/plan_explain.py --fixture runs the
#      real two-worker in-proc fleet, and --check fails unless every
#      enumerated proposal is accounted (priced candidate or typed
#      prune) AND the executed candidate's predicted cost terms join
#      against the measured fidelity attribution.
#   2. PLAN DIFF: two identical explores diff empty (--check passes);
#      a seeded cost-model perturbation (tiny HBM makes full
#      replication infeasible) MUST flip the winner with a named
#      driver (--expect-flip).
#
# Override the per-pass bound with EXPLAIN_SMOKE_TIMEOUT (seconds).
set -euo pipefail
cd "$(dirname "$0")/.."

TIMEOUT="${EXPLAIN_SMOKE_TIMEOUT:-600}"
TMPDIR_SMOKE="$(mktemp -d)"
trap 'rm -rf "$TMPDIR_SMOKE"' EXIT
export JAX_PLATFORMS=cpu

echo "=== explain smoke 1/2: candidate ledger + cost scoreboard ==="
timeout -k 10 "$TIMEOUT" python tools/plan_explain.py --fixture --check

echo "=== explain smoke 2/2: plan diff — identical empty, seeded flip ==="
timeout -k 10 "$TIMEOUT" env \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - "$TMPDIR_SMOKE" <<'PY'
import json, os, sys

import jax
import jax.numpy as jnp

from tepdist_tpu.core.service_env import ServiceEnv
from tepdist_tpu.parallel.exploration import explore

out = sys.argv[1]

def loss(params, x, y):
    h = x
    for i in range(4):
        h = jnp.tanh(h @ params[f"w{i}"])
    return jnp.mean((h - y) ** 2)

params = {f"w{i}": jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
          for i in range(4)}
x = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
y = jax.ShapeDtypeStruct((8, 1024), jnp.float32)

def report(**env):
    try:
        if env:
            ServiceEnv.reset(env)
        return explore(loss, params, x, y, n_devices=8,
                       num_micro_batches=2)["report"]
    finally:
        if env:
            ServiceEnv.reset()

# base / again: identical fixture twice (determinism contract);
# perturbed: tight HBM makes the replicated-state SPMD winners
# memory-infeasible while a sharded pipeline candidate still fits.
# 0.024 GB sits in the flip window now that the evaluator charges
# OPT_STATE_FACTOR x grad bytes of optimizer state per device —
# starving further (e.g. 0.005) kills EVERY candidate and nothing
# flips.
for name, rep in (("base", report()), ("again", report()),
                  ("perturbed", report(HBM_GB=0.024))):
    with open(os.path.join(out, f"{name}.json"), "w") as f:
        json.dump(rep, f)
PY

timeout -k 10 "$TIMEOUT" python tools/plan_diff.py \
    "$TMPDIR_SMOKE/base.json" "$TMPDIR_SMOKE/again.json" --check
if timeout -k 10 "$TIMEOUT" python tools/plan_diff.py \
    "$TMPDIR_SMOKE/base.json" "$TMPDIR_SMOKE/perturbed.json" --check \
    > /dev/null 2>&1; then
    echo "explain smoke: FAIL (seeded flip did not fail plan_diff --check)"
    exit 1
fi
timeout -k 10 "$TIMEOUT" python tools/plan_diff.py \
    "$TMPDIR_SMOKE/base.json" "$TMPDIR_SMOKE/perturbed.json" --expect-flip

echo "explain smoke: PASS"
