#!/usr/bin/env bash
# Comm-dtype compression smoke (ISSUE 13): prove the compressed-
# collective planner candidates + compressed wire end to end.
#
#   1. FLIP FIXTURE: the committed before/after ExplorationReports
#      (scripts/gen_flip_fixtures.py — GPT-2 graph at healthy vs starved
#      ICI bandwidth) MUST flip the winner to an @int8 mesh with coll_s
#      as the named driver (plan_diff --check fails, --expect-flip
#      passes).
#   2. LEDGER: tools/plan_explain.py --fixture --check still accounts
#      every proposal with compressed variants in the candidate space.
#   3. NUMERICS: fidelity comm_dtype is bit-identical; bf16/int8
#      gradient AR tracks the fidelity loss trajectory within the band;
#      the int8 wire round-trips in under 0.3 of the fidelity bytes.
#
# Override the per-pass bound with QUANT_SMOKE_TIMEOUT (seconds).
set -euo pipefail
cd "$(dirname "$0")/.."

TIMEOUT="${QUANT_SMOKE_TIMEOUT:-600}"
TMPDIR_SMOKE="$(mktemp -d)"
trap 'rm -rf "$TMPDIR_SMOKE"' EXIT
export JAX_PLATFORMS=cpu

BEFORE="tests/fixtures/coll_flip_before.json"
AFTER="tests/fixtures/coll_flip_after.json"

echo "=== quant smoke 1/3: committed winner-flip fixtures (driver coll_s) ==="
if timeout -k 10 "$TIMEOUT" python tools/plan_diff.py \
    "$BEFORE" "$AFTER" --check > /dev/null 2>&1; then
    echo "quant smoke: FAIL (fixture flip did not fail plan_diff --check)"
    exit 1
fi
timeout -k 10 "$TIMEOUT" python tools/plan_diff.py \
    "$BEFORE" "$AFTER" --expect-flip | tee "$TMPDIR_SMOKE/flip.txt"
grep -q "driver: coll_s" "$TMPDIR_SMOKE/flip.txt" || {
    echo "quant smoke: FAIL (flip driver is not coll_s)"; exit 1; }
grep -q "@int8" "$TMPDIR_SMOKE/flip.txt" || {
    echo "quant smoke: FAIL (new winner is not a compressed candidate)"
    exit 1; }

echo "=== quant smoke 2/3: candidate ledger + scoreboard (plan_explain) ==="
timeout -k 10 "$TIMEOUT" python tools/plan_explain.py --fixture --check

echo "=== quant smoke 3/3: compressed-gradient numerics ==="
timeout -k 10 "$TIMEOUT" python -m pytest tests/test_comm_dtype.py -q \
    -p no:cacheprovider -k "bit_identical or loss_band or roundtrip"

echo "quant smoke: PASS"
