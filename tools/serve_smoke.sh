#!/bin/bash
# Tier-1 serving smoke: freeze a model_zoo network ON CPU, start the
# ModelServer, fire 64 concurrent single-sample predicts through the
# dynamic batcher, and assert the acceptance contract end to end:
#   * zero dropped requests (responses == submitted, no rejects),
#   * batching demonstrably coalesced (batch-fill ratio > 1.5x),
#   * p99 latency recorded (and sane) in the BENCH json,
#   * outputs bit-exact vs direct eager net(x) on each served batch,
#   * serving counters + latency histograms present in the Prometheus
#     text / metrics JSONL exports and in the flight-recorder dump.
# bench.py itself hard-fails on drops/divergence; this script re-checks
# the emitted artifacts with tools/trace_check so a broken exporter
# can't pass silently. No TPU — safe anywhere, CI-cheap.
set -u
cd "$(dirname "$0")/.." || exit 1

DIAG_DIR=${MXTPU_DIAG_DIR:-/tmp/mxtpu_serve_smoke}
OUT=${1:-/tmp/mxtpu_serve_smoke_bench.json}
rm -rf "$DIAG_DIR"; mkdir -p "$DIAG_DIR"

echo "serve_smoke: 64 concurrent lenet predicts on CPU, diag armed"
JAX_PLATFORMS=cpu BENCH_MODEL=serving BENCH_SERVING_MODEL=lenet \
  BENCH_SERVING_CLIENTS=64 BENCH_SERVING_REQS=1 \
  BENCH_DIAG=1 BENCH_DIAG_INTERVAL_MS=100 \
  MXTPU_DIAG_DIR="$DIAG_DIR" \
  BENCH_TRACE_FILE="$DIAG_DIR/trace.json" \
  timeout -k 10 900 python bench.py > "$OUT" 2> "$DIAG_DIR/bench.log"
rc=$?
if [ "$rc" != "0" ]; then
  echo "serve_smoke: bench.py failed rc=$rc"; tail -30 "$DIAG_DIR/bench.log"
  exit 1
fi

python - "$OUT" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
if doc.get("error"):
    sys.exit(f"bench reported error: {doc['error']}")
s = (doc.get("extra") or {}).get("serving") or {}
assert s, "no extra.serving section in BENCH json"
assert s["responses"] == s["requests"], \
    f"dropped requests: {s['requests'] - s['responses']}"
assert s.get("rejected_queue_full", 0) == 0 and \
    s.get("rejected_deadline", 0) == 0, f"rejections present: {s}"
assert s["batch_fill"] > 1.5, \
    f"batching did not coalesce: fill={s['batch_fill']}"
assert s["bit_exact"] is True, "serving outputs diverged from eager"
p99 = s["p99_ms"]
assert p99 and 0 < p99 < 30000, f"p99 insane: {p99}"
assert (s.get("latency_ms") or {}).get("count") == s["responses"], \
    "latency histogram lost observations"
print(f"serve_smoke: bench OK ({doc['value']} {doc['unit']}, "
      f"fill {s['batch_fill']}x over {s['batches']} batches, "
      f"p50/p95/p99 = {s['p50_ms']:.1f}/{s['p95_ms']:.1f}/"
      f"{p99:.1f} ms)")
EOF

# artifact validation: bench json (serving schema incl. histogram),
# chrome trace, flight dump, prometheus text, metrics jsonl
FLIGHT=$(python -c "import json,sys;print(json.load(open('$OUT'))['extra']['flight_file'])")
python tools/trace_check.py \
  "$OUT" "$DIAG_DIR/trace.json" "$FLIGHT" \
  "$DIAG_DIR/metrics.jsonl" "$DIAG_DIR/metrics.prom" || exit 1

# the serving traffic must be VISIBLE in the shared telemetry surfaces
python - "$FLIGHT" "$DIAG_DIR/metrics.prom" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
assert any(e["kind"] == "serving" for e in doc["events"]), \
    "no serving events in the flight dump"
assert doc["counter_kinds"].get("serving/serving.latency_ms") == \
    "histogram", "latency histogram missing from flight dump"
prom = open(sys.argv[2]).read()
assert "# TYPE serving_serving_latency_ms histogram" in prom, \
    "latency histogram missing from Prometheus export"
assert "serving_serving_responses" in prom, \
    "serving counters missing from Prometheus export"
print("serve_smoke: serving telemetry visible in flight + Prometheus")
EOF
echo "serve_smoke: all serving artifacts validate"
