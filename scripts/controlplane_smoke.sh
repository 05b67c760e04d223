#!/usr/bin/env bash
# Control-plane smoke: prove the ISSUE-20 crash-safety contract end to
# end on real subprocesses — run it locally or as a CI step.
#
#   1. KILL THE MASTER: tools/chaos_run.py --kill-master SIGKILLs the
#      real master subprocess mid-run; a fresh master must readopt() the
#      still-live worker fleet from the durable WAL — same epoch-fenced
#      takeover an operator would run — and finish with the merged loss
#      trajectory matching the undisturbed reference (overlapping steps
#      bit-identical: the exactly-once evidence), exactly one takeover,
#      no checkpoint rollback, and the machine-readable
#      master_recover_ms= line.
#   2. FENCE + TORN TAIL: the targeted pytest half — a stale-epoch verb
#      is rejected with zero worker mutation, and a WAL torn mid-append
#      replays to at most one step early and still resumes bit-exactly.
#
# Override the per-pass bound with CONTROLPLANE_SMOKE_TIMEOUT (seconds).
set -euo pipefail
cd "$(dirname "$0")/.."

TIMEOUT="${CONTROLPLANE_SMOKE_TIMEOUT:-600}"
TMPDIR_SMOKE="$(mktemp -d)"
trap 'rm -rf "$TMPDIR_SMOKE"' EXIT

echo "=== controlplane smoke 1/2: SIGKILL the master, readopt the fleet ==="
OUT="$TMPDIR_SMOKE/chaos.log"
timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu python tools/chaos_run.py \
    --steps 8 --kill-master 3 | tee "$OUT"

if ! grep -qE 'master_recover_ms=[0-9.]+' "$OUT"; then
    echo "controlplane smoke: FAIL (no master_recover_ms line)"
    exit 1
fi

echo "=== controlplane smoke 2/2: epoch fence + torn WAL tail ==="
timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider \
    tests/test_controlplane_session.py::test_stale_epoch_rejected_without_mutation \
    tests/test_controlplane_session.py::test_readopt_tolerates_torn_wal_tail \
    tests/test_controlplane.py

echo "controlplane smoke: PASS"
