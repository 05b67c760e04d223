#!/usr/bin/env bash
# Elastic smoke: prove the ISSUE-18 live-migration contract end to end
# on real worker subprocesses — run it locally or as a CI step.
#
#   KILL MID-RUN: tools/chaos_run.py --kill-worker SIGKILLs a gRPC
#   worker subprocess mid-run; the session must complete on the
#   reshaped mesh via exactly ONE live migration (no checkpoint
#   rollback) with the loss trajectory matching the undisturbed
#   reference, the watchtower migration alert lifecycle must fire
#   (migrations_started counter), and the run prints the
#   machine-readable migration_stall_ms= line.
#
# Override the per-pass bound with ELASTIC_SMOKE_TIMEOUT (seconds).
set -euo pipefail
cd "$(dirname "$0")/.."

TIMEOUT="${ELASTIC_SMOKE_TIMEOUT:-600}"
TMPDIR_SMOKE="$(mktemp -d)"
trap 'rm -rf "$TMPDIR_SMOKE"' EXIT

echo "=== elastic smoke: SIGKILL a worker mid-run, live-migrate ==="
OUT="$TMPDIR_SMOKE/chaos.log"
timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu python tools/chaos_run.py \
    --steps 6 --kill-worker 3 | tee "$OUT"

if ! grep -qE 'migrations_started\s+1' "$OUT"; then
    echo "elastic smoke: FAIL (watchtower migration alert never fired)"
    exit 1
fi
if ! grep -qE 'migration_stall_ms=[0-9.]+' "$OUT"; then
    echo "elastic smoke: FAIL (no migration_stall_ms line)"
    exit 1
fi

echo "elastic smoke: PASS"
