#!/usr/bin/env bash
# Disaggregated-serving smoke: prove the ISSUE-19 prefill/decode
# contract end to end — run it locally or as a CI step.
#
#   1. BIT-IDENTITY + ZERO LEAK: a 1-prefill/1-decode in-proc fleet
#      generates bit-identically to single-device sample() through the
#      ExportPages/AdoptPages paged-KV handoff, only the live pages
#      move (counter-checked against pages_for), and after draining
#      BOTH pools zero pages remain allocated.
#   2. LOAD + METRICS: tools/serve_load.py --disagg 1:1 completes a
#      request mix and emits disagg_ttft_ms / kv_handoff_ms in --out.
#
# Override the per-pass bound with DISAGG_SMOKE_TIMEOUT (seconds).
set -euo pipefail
cd "$(dirname "$0")/.."

TIMEOUT="${DISAGG_SMOKE_TIMEOUT:-600}"
TMPDIR_SMOKE="$(mktemp -d)"
trap 'rm -rf "$TMPDIR_SMOKE"' EXIT

echo "=== disagg smoke 1/2: 1P/1D handoff bit-identity + zero leak ==="
timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu python - <<'EOF'
import jax
import numpy as np

from tepdist_tpu.models import gpt2
from tepdist_tpu.models.sampling import sample
from tepdist_tpu.rpc.client import TepdistClient
from tepdist_tpu.rpc.inproc import close_inproc_cluster, make_inproc_cluster
from tepdist_tpu.serving import FleetRouter, pages_for
from tepdist_tpu.telemetry import metrics

cfg = gpt2.CONFIGS["test"]
params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
prompts = [np.random.RandomState(s).randint(
               1, cfg.vocab_size, size=t).astype(np.int32)
           for s, t in ((0, 5), (1, 17), (2, 33))]
cluster, servicers = make_inproc_cluster(2, jax.devices()[:2])
router = FleetRouter([TepdistClient(w.address) for w in cluster.workers],
                     prefill=1, decode=1)
before = dict(metrics().snapshot()["counters"])
try:
    router.load(params, cfg, max_len=64, name="smoke")
    outs = router.generate(prompts, max_new_tokens=6, greedy=True)
    for p, o in zip(prompts, outs):
        ref = np.asarray(sample(params, p[None], cfg,
                                max_new_tokens=6, greedy=True))[0]
        assert np.array_equal(o, ref), "disagg output != sample()"
    router.drain_all(wait_ms=5000.0)
    leaked = sum(int(e.stats().get("pages_used", 0))
                 for s in servicers for e in s.servables.values())
    assert leaked == 0, f"{leaked} pages leaked after drain"
finally:
    for s in servicers:
        s.close_servables()
    close_inproc_cluster(cluster)
d = dict(metrics().snapshot()["counters"])
live = sum(pages_for(len(p), router.page_size) for p in prompts)
moved = d.get("kv_pages_exported", 0) - before.get("kv_pages_exported", 0)
assert moved == live, f"shipped {moved} pages, live set is {live}"
print(f"disagg smoke: bit-identical x{len(prompts)}, "
      f"{moved} live pages moved, 0 leaked")
EOF

echo "=== disagg smoke 2/2: serve_load --disagg 1:1 ==="
SERVE="$TMPDIR_SMOKE/serve.json"
timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu python tools/serve_load.py \
    --disagg 1:1 --workers 2 --requests 8 --out "$SERVE"
python - "$SERVE" <<'EOF'
import json, sys
s = json.load(open(sys.argv[1]))
assert s["statuses"].get("done") == s["requests"], s["statuses"]
assert s["disagg_pages_leaked"] == 0, s["disagg_pages_leaked"]
for k in ("disagg_ttft_ms", "kv_handoff_ms"):
    assert isinstance(s[k], (int, float)), f"missing {k}"
print(f"serve_load: disagg_ttft_ms={s['disagg_ttft_ms']} "
      f"kv_handoff_ms={s['kv_handoff_ms']} leaked=0")
EOF

echo "disagg smoke: PASS"
