#!/bin/bash
# Tier-1 diagnostics smoke: run a few bench steps ON CPU with the full
# observability stack armed (memory ledger + 100ms metrics sampler +
# flight recorder), then validate every artifact with tools/trace_check
# and assert the BENCH json carries the memory/counters sections.
# No TPU — safe to run anywhere, cheap enough for CI.
# Exit 0 iff the whole pipeline (record -> export -> validate) is healthy.
set -u
cd "$(dirname "$0")/.." || exit 1

DIAG_DIR=${MXTPU_DIAG_DIR:-/tmp/mxtpu_diag_smoke}
OUT=${1:-/tmp/mxtpu_diag_smoke_bench.json}
rm -rf "$DIAG_DIR"; mkdir -p "$DIAG_DIR"

echo "diag_smoke: 3 lenet bench steps on CPU, sampler 100ms + flight on"
JAX_PLATFORMS=cpu BENCH_MODEL=lenet BENCH_BATCH=64 BENCH_STEPS=3 \
  BENCH_DTYPE=float32 BENCH_DIAG=1 BENCH_DIAG_INTERVAL_MS=100 \
  MXTPU_DIAG_DIR="$DIAG_DIR" \
  BENCH_TRACE_FILE="$DIAG_DIR/trace.json" \
  timeout -k 10 900 python bench.py > "$OUT" 2> "$DIAG_DIR/bench.log"
rc=$?
if [ "$rc" != "0" ]; then
  echo "diag_smoke: bench.py failed rc=$rc"; tail -30 "$DIAG_DIR/bench.log"
  exit 1
fi

python - "$OUT" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
if doc.get("error"):
    sys.exit(f"bench reported error: {doc['error']}")
extra = doc.get("extra") or {}
mem = extra.get("memory") or {}
assert mem.get("peak_bytes", 0) > 0, "no memory peak in BENCH json"
assert isinstance(extra.get("counters"), dict) and extra["counters"], \
    "no counters snapshot in BENCH json"
assert extra.get("flight_file"), "no flight dump recorded"
print(f"diag_smoke: bench OK ({doc['value']} {doc['unit']}, "
      f"peak {mem['peak_bytes']} bytes, "
      f"{len(extra['counters'])} counters)")
EOF

# validate every telemetry artifact; trace_check exits non-zero on any
# schema violation or non-monotonic counter
FLIGHT=$(python -c "import json,sys;print(json.load(open('$OUT'))['extra']['flight_file'])")
python tools/trace_check.py \
  "$DIAG_DIR/trace.json" "$FLIGHT" \
  "$DIAG_DIR/metrics.jsonl" "$DIAG_DIR/metrics.prom" || exit 1

# the dump must also be pretty-printable
python tools/mxdiag.py "$FLIGHT" --events 5 > /dev/null || exit 1
echo "diag_smoke: all telemetry artifacts validate"
