#!/usr/bin/env bash
# Watchtower smoke (ISSUE 17): prove the live-monitoring pipeline end to
# end on the two-worker in-proc demo fleet.
#
#   1. NO-FLAP BASELINE: a clean run of the same length as the faulted
#      one must finish with ZERO active alerts (--check with no
#      --expect demands a quiet fleet).
#   2. INJECTED FAULTS: with an rpc_delay straggler on worker 1 and a
#      seeded loss spike, watch.py --once --check --expect must see BOTH
#      typed alerts through real GetTelemetryDelta polls.
#   3. NAN SENTINEL: a seeded NaN raises the page-severity nan alert.
#
# Override the per-pass bound with WATCH_SMOKE_TIMEOUT (seconds).
set -euo pipefail
cd "$(dirname "$0")/.."

TIMEOUT="${WATCH_SMOKE_TIMEOUT:-600}"

echo "=== watch smoke 1/3: no-flap clean baseline ==="
timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu python tools/watch.py \
    --demo --steps 8 --slo slo.toml --once --check

echo "=== watch smoke 2/3: straggler + loss spike raise typed alerts ==="
timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu python tools/watch.py \
    --demo --steps 8 --fault rpc_delay:ms=80,ti=1 --seed-spike 6 \
    --slo slo.toml --once --check --expect straggler,loss_spike

echo "=== watch smoke 3/3: NaN watchdog pages ==="
timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu python tools/watch.py \
    --demo --steps 6 --seed-nan 3 --once --check --expect nan

echo "watch smoke: PASS"
