#!/bin/bash
# Resilience smoke: the chaos harness end to end on CPU, plus the
# telemetry/gate plumbing around it. Proves, without a TPU:
#
#  1. chaos_cluster.py — all four injected faults (poison-NaN batch,
#     torn checkpoint, frozen source -> stall -> restart, mid-step rank
#     SIGKILL + elastic re-join) recover with loss DECREASING and the
#     recovery visible on counters + flight + events (the harness
#     asserts the three-surface contract itself), the merged timeline
#     trace_check-valid, and `mxdiag.py recover` rendering it clean;
#  2. a BENCH_RESILIENCE=1 training bench emits a trace_check-valid
#     extra.resilience block (async checkpoint cadence + save-cost
#     percentiles) with ZERO recovery counters on a healthy run;
#  3. perf_regress accepts that artifact self-vs-self (a resilient run
#     is a usable perf number, not an env failure).
#
# Exit 0 = all good.
set -u
cd "$(dirname "$0")/.." || exit 1
export JAX_PLATFORMS=cpu
OUT=${MXTPU_SMOKE_OUT:-/tmp/mxtpu_resilience_smoke}
rm -rf "$OUT"; mkdir -p "$OUT"
fail() { echo "resilience_smoke: FAIL: $*" >&2; exit 1; }

echo "== resilience_smoke: chaos harness (nan + torn + freeze + kill) =="
MXTPU_CHAOS_OUT="$OUT/chaos" timeout 580 python tools/chaos_cluster.py \
  > "$OUT/chaos.log" 2>&1
rc=$?
tail -n 12 "$OUT/chaos.log"
[ $rc -eq 0 ] || fail "chaos_cluster rc=$rc (log: $OUT/chaos.log)"
grep -q "CHAOS_OK" "$OUT/chaos.log" || fail "no CHAOS_OK verdict"

echo "== resilience_smoke: BENCH_RESILIENCE training bench =="
BENCH_JSON="$OUT/BENCH_resilience.json"
BENCH_MODEL=lenet BENCH_BATCH=32 BENCH_STEPS=50 BENCH_DTYPE=float32 \
  BENCH_K1_CONTROL=0 BENCH_RESILIENCE=1 BENCH_RESILIENCE_EVERY=10 \
  BENCH_RESILIENCE_DIR="$OUT/bench_ckpt" \
  timeout -k 10 900 python bench.py > "$BENCH_JSON" 2> "$OUT/bench.log" \
  || { tail -n 30 "$OUT/bench.log"; fail "bench run failed"; }

python - "$BENCH_JSON" <<'EOF' || exit 1
import json, sys
sys.path.insert(0, "tools")
import trace_check as tc
path = sys.argv[1]
errs = tc.check_bench_json(path)
assert not errs, f"BENCH json invalid: {errs[:5]}"
doc = json.load(open(path))
rx = (doc.get("extra") or {}).get("resilience")
assert rx, "BENCH json carries no extra.resilience"
assert not tc.check_resilience_extra(rx), tc.check_resilience_extra(rx)
assert rx["checkpoints_saved"] >= 1, f"no checkpoints saved: {rx}"
assert rx["recoveries_total"] == 0, \
    f"healthy bench run recorded recoveries: {rx}"
assert rx["save"] and rx["save"]["count"] >= 1, f"no save costs: {rx}"
print(f"resilience extra OK: {rx['checkpoints_saved']} ckpt(s), "
      f"save p50 {rx['save']['p50_ms']:.0f} ms, 0 recoveries")
EOF
[ $? -eq 0 ] || fail "extra.resilience validation"

echo "== resilience_smoke: perf_regress accepts the resilient artifact =="
python tools/perf_regress.py "$BENCH_JSON" "$BENCH_JSON" \
  || fail "perf_regress rejected a resilient run self-vs-self"

echo "resilience_smoke: OK"
