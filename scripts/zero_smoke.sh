#!/usr/bin/env bash
# ZeRO weight-update sharding smoke (ISSUE 14): prove the optimizer-
# state-sharding planner modifier + sharded update paths end to end.
#
#   1. FLIP FIXTURE: the committed before/after ExplorationReports
#      (scripts/gen_flip_fixtures.py — GPT-2 graph at healthy vs starved
#      HBM, healthy wire in BOTH) MUST flip the winner to an @zero mesh
#      with memory_feasible as the named driver (plan_diff --check
#      fails, --expect-flip passes).
#   2. LEDGER: tools/plan_explain.py renders the fixture's candidate
#      table with the per-candidate opt_MB column and --check accounts
#      every proposal.
#   3. NUMERICS: ZeRO-DP tracks plain DP to accumulation tolerance; the
#      planner zero_invars path matches and halves per-device state.
#
# Override the per-pass bound with ZERO_SMOKE_TIMEOUT (seconds).
set -euo pipefail
cd "$(dirname "$0")/.."

TIMEOUT="${ZERO_SMOKE_TIMEOUT:-600}"
TMPDIR_SMOKE="$(mktemp -d)"
trap 'rm -rf "$TMPDIR_SMOKE"' EXIT
export JAX_PLATFORMS=cpu

BEFORE="tests/fixtures/zero_flip_before.json"
AFTER="tests/fixtures/zero_flip_after.json"

echo "=== zero smoke 1/3: committed winner-flip fixtures (driver memory_feasible) ==="
if timeout -k 10 "$TIMEOUT" python tools/plan_diff.py \
    "$BEFORE" "$AFTER" --check > /dev/null 2>&1; then
    echo "zero smoke: FAIL (fixture flip did not fail plan_diff --check)"
    exit 1
fi
timeout -k 10 "$TIMEOUT" python tools/plan_diff.py \
    "$BEFORE" "$AFTER" --expect-flip | tee "$TMPDIR_SMOKE/flip.txt"
grep -q "driver: memory_feasible" "$TMPDIR_SMOKE/flip.txt" || {
    echo "zero smoke: FAIL (flip driver is not memory_feasible)"; exit 1; }
grep -q "@zero" "$TMPDIR_SMOKE/flip.txt" || {
    echo "zero smoke: FAIL (new winner is not a ZeRO candidate)"
    exit 1; }

echo "=== zero smoke 2/3: candidate ledger + opt_MB column (plan_explain) ==="
timeout -k 10 "$TIMEOUT" python tools/plan_explain.py \
    "$AFTER" | tee "$TMPDIR_SMOKE/explain.txt"
grep -q "opt_MB" "$TMPDIR_SMOKE/explain.txt" || {
    echo "zero smoke: FAIL (plan_explain lacks the opt_MB column)"
    exit 1; }
grep -q "@zero" "$TMPDIR_SMOKE/explain.txt" || {
    echo "zero smoke: FAIL (plan_explain lacks @zero candidates)"
    exit 1; }
timeout -k 10 "$TIMEOUT" python tools/plan_explain.py --fixture --check

echo "=== zero smoke 3/3: ZeRO-DP numerics + planner path ==="
timeout -k 10 "$TIMEOUT" python -m pytest tests/test_zero.py -q \
    -p no:cacheprovider \
    -k "tracks_plain or composes_with_int8 or zero_invars"

echo "zero smoke: PASS"
