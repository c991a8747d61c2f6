#!/bin/bash
# Tier-1 healthmon smoke — two parts, both CPU-only (no TPU):
#
#   1. tools/health_cluster.py — a REAL 2-process loopback cluster with
#      an injected slow rank (80 ms sleep on rank 1) and an injected NaN
#      loss (rank 0, step 7); asserts healthmon.collective_skew_ms
#      reports the skew with slowest-rank attribution on EVERY rank, the
#      NaN raises a watchdog alert (counter + flight event + structured
#      log record) within one step, and `mxdiag merge` interleaves the
#      per-rank events/flight artifacts into one validated cross-rank
#      timeline (tools/trace_check.py).
#
#   2. measured overhead — tools/health_overhead.py: 50 steps per side
#      of the CPU lenet bench step, healthmon off vs on at default
#      settings, INTERLEAVED in one process (paired-median verdict; two
#      sequential bench.py runs drift more than the effect). Budget:
#      < 5% (one retry absorbs scheduler noise on loaded CI).
#
#   3. pipeline validation — a short BENCH_HEALTHMON=1 bench.py run:
#      the BENCH json must carry the healthmon counters + events file,
#      and every artifact must pass tools/trace_check.py.
#
# Exit 0 iff all three hold.
set -u
cd "$(dirname "$0")/.." || exit 1

OUT_DIR=${MXTPU_HM_OUT:-/tmp/mxtpu_health_smoke}
rm -rf "$OUT_DIR"; mkdir -p "$OUT_DIR"

echo "health_smoke: part 1 — 2-process cluster (slow rank + NaN)"
MXTPU_HM_OUT="$OUT_DIR/cluster" \
  timeout -k 10 600 python tools/health_cluster.py || {
  echo "health_smoke: cluster exercise FAILED"; exit 1; }

echo "health_smoke: part 2 — measured overhead (interleaved 50-step lenet)"
MXTPU_HM_OUT="$OUT_DIR/overhead" \
  timeout -k 10 900 python tools/health_overhead.py | tee "$OUT_DIR/overhead.json"
rc=${PIPESTATUS[0]}
if [ "$rc" = "3" ]; then
  echo "health_smoke: overhead over budget; one retry (noise check)"
  MXTPU_HM_OUT="$OUT_DIR/overhead" \
    timeout -k 10 900 python tools/health_overhead.py | tee "$OUT_DIR/overhead.json"
  rc=${PIPESTATUS[0]}
fi
[ "$rc" != "0" ] && { echo "health_smoke: overhead check FAILED (rc=$rc)"; exit 1; }

echo "health_smoke: part 3 — BENCH_HEALTHMON pipeline validation"
JAX_PLATFORMS=cpu BENCH_MODEL=lenet BENCH_BATCH=64 BENCH_STEPS=3 \
  BENCH_DTYPE=float32 BENCH_TRACE=0 BENCH_HEALTHMON=1 \
  MXTPU_DIAG_DIR="$OUT_DIR/bench_diag" \
  timeout -k 10 900 python bench.py > "$OUT_DIR/bench.json" \
  2> "$OUT_DIR/bench.log" || {
  echo "health_smoke: healthmon bench failed"
  tail -20 "$OUT_DIR/bench.log"; exit 1; }

python - "$OUT_DIR/bench.json" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
if doc.get("error"):
    sys.exit(f"bench reported error: {doc['error']}")
hm = (doc.get("extra") or {}).get("healthmon") or {}
assert hm.get("steps") == 3, f"healthmon saw {hm.get('steps')} steps"
assert hm.get("events_file"), "no events file in BENCH json"
assert hm["counters"].get("healthmon/healthmon.steps") == 3, \
    f"healthmon counters missing/wrong: {hm.get('counters')}"
print(f"health_smoke: bench OK ({doc['value']} {doc['unit']}, "
      f"{len(hm['counters'])} healthmon counters)")
EOF

# the healthmon bench's event log must validate as mxtpu.events/1
EVENTS=$(python -c "import json,sys;print(json.load(open('$OUT_DIR/bench.json'))['extra']['healthmon']['events_file'])")
python tools/trace_check.py "$EVENTS" "$OUT_DIR/bench.json" || exit 1
echo "health_smoke: all healthmon artifacts validate"
