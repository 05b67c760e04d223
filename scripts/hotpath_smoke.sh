#!/usr/bin/env bash
# Hot-path smoke: prove the ISSUE-11 RPC hot path end to end on a fresh
# two-worker in-proc fleet fixture.
#
#   LEDGER EXACTNESS: tools/ledger_report.py runs the fixture with
#   batched dispatch + send overlap at their defaults (ON); --check
#   fails unless the gap-table buckets sum to each step's wall
#   exactly, coverage holds, and the serde bucket reconciles with the
#   independent fidelity attribution — i.e. the coalesced
#   ExecuteStepSlice framing path stays byte-accounted.
#
# Override the per-pass bound with HOTPATH_SMOKE_TIMEOUT (seconds).
set -euo pipefail
cd "$(dirname "$0")/.."

TIMEOUT="${HOTPATH_SMOKE_TIMEOUT:-600}"

echo "=== hotpath smoke: ledger byte-exactness under batched dispatch ==="
# Same coverage floor rationale as ledger_smoke.sh: loaded 1-core CI
# hosts land 93-95% occasionally; the bucket-sum identity stays exact.
timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu python tools/ledger_report.py \
    --steps 6 --check --min-coverage 0.93 \
    --json > /dev/null

echo "hotpath smoke: PASS"
