#!/bin/bash
# Tier-1 autotune smoke: CPU lenet through bench.py with MXTPU_AUTOTUNE=1
# against a FRESH tuning cache, twice, asserting the subsystem's core
# contracts from the emitted BENCH json:
#   run 1 (cache miss): a bounded search runs (trials >= 1, within the
#     budget), every scored trial carries measured(profile) provenance
#     (the devicescope window measured the busy fraction — not a host
#     guess), the winner's measured busy fraction >= the stepwise
#     default's (the baseline is a candidate, so the searched config can
#     never lose to it), pruning reasons are present, and the winner is
#     persisted;
#   run 2 (cache hit): cache_hit=true with trials=0 (zero search cost),
#     and the run actually STARTS tuned (the resolved knobs equal the
#     winner);
#   both runs: extra.autotune + the autotune.* counter family validate
#     under trace_check, `mxdiag.py tune` renders, and perf_regress
#     reports the two runs' knob configs as identical context.
# No TPU — safe anywhere, cheap enough for CI.
set -u
cd "$(dirname "$0")/.." || exit 1

OUT1=${1:-/tmp/mxtpu_autotune_smoke_bench1.json}
OUT2=/tmp/mxtpu_autotune_smoke_bench2.json
LOG=/tmp/mxtpu_autotune_smoke.log
CACHE=/tmp/mxtpu_autotune_smoke_cache
DSDIR=/tmp/mxtpu_autotune_smoke_windows

rm -rf "$CACHE" "$DSDIR"
: > "$LOG"

run_bench() {
  JAX_PLATFORMS=cpu MXTPU_AUTOTUNE=1 MXTPU_AUTOTUNE_CACHE="$CACHE" \
    MXTPU_AUTOTUNE_BUDGET=3 MXTPU_AUTOTUNE_STEPS=8 \
    MXTPU_AUTOTUNE_TRIAL_TIMEOUT=420 \
    MXTPU_DEVICESCOPE_DIR="$DSDIR" \
    BENCH_MODEL=lenet BENCH_BATCH=64 BENCH_STEPS=24 \
    BENCH_DTYPE=float32 BENCH_K1_CONTROL=0 \
    BENCH_TRACE=0 BENCH_DEVICESCOPE=1 \
    timeout -k 10 1500 python bench.py > "$1" 2>> "$LOG"
}

echo "autotune_smoke: run 1 (fresh cache -> bounded search)"
run_bench "$OUT1"
rc=$?
if [ "$rc" != "0" ]; then
  echo "autotune_smoke: bench run 1 failed rc=$rc"; tail -30 "$LOG"
  exit 1
fi

python - "$OUT1" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
if doc.get("error"):
    sys.exit(f"bench reported error: {doc['error']}")
at = (doc.get("extra") or {}).get("autotune")
assert isinstance(at, dict) and at.get("enabled") is True, \
    f"no enabled extra.autotune: {at!r}"
assert at.get("error") is None, f"autotune errored: {at.get('error')}"
assert at.get("cache_hit") is False, "run 1 must be a cache MISS"
assert 1 <= at.get("trials", 0) <= 3, \
    f"trials {at.get('trials')!r} outside the budget [1, 3]"
sc, df = at.get("score") or {}, at.get("default") or {}
assert sc.get("provenance") == "measured(profile)", \
    f"winner scored without a measured window: {sc!r}"
b1, b0 = sc.get("busy_fraction"), df.get("busy_fraction")
assert isinstance(b1, (int, float)) and isinstance(b0, (int, float)), \
    f"busy fractions missing: winner={b1!r} default={b0!r}"
assert b1 >= b0, \
    f"searched config's measured busy {b1} < stepwise default's {b0}"
assert at.get("winner"), "no winner config"
assert at.get("pruned"), "no pruning reasons recorded"
assert at.get("diagnosis") in ("input_starved", "dispatch_bound",
                               "device_bound", "unknown"), at.get("diagnosis")
c = (doc.get("extra") or {}).get("counters") or {}
for name in ("autotune/autotune.searches", "autotune/autotune.trials",
             "autotune/autotune.cache_misses"):
    assert name in c, f"counter {name} missing from BENCH json"
print(f"autotune_smoke: search OK (diagnosis={at['diagnosis']}, "
      f"{at['trials']} trials, busy {b0:.1%} -> {b1:.1%}, "
      f"winner {at['winner']})")
EOF

echo "autotune_smoke: run 2 (same key -> cache hit, 0 trials)"
run_bench "$OUT2"
rc=$?
if [ "$rc" != "0" ]; then
  echo "autotune_smoke: bench run 2 failed rc=$rc"; tail -30 "$LOG"
  exit 1
fi

python - "$OUT1" "$OUT2" <<'EOF' || exit 1
import json, sys
d1 = json.load(open(sys.argv[1]))
d2 = json.load(open(sys.argv[2]))
at = (d2.get("extra") or {}).get("autotune")
assert isinstance(at, dict) and at.get("enabled") is True, at
assert at.get("cache_hit") is True, \
    f"run 2 must be a cache HIT, got {at.get('cache_hit')!r}"
assert at.get("trials") == 0, \
    f"cache hit must run 0 trials, got {at.get('trials')!r}"
win, resolved = at.get("winner") or {}, at.get("resolved") or {}
assert resolved == win, \
    f"run 2 did not START tuned: resolved {resolved} != winner {win}"
w1 = ((d1.get("extra") or {}).get("autotune") or {}).get("winner")
assert win == w1, f"cached winner drifted: {win} != {w1}"
print(f"autotune_smoke: cache hit OK (0 trials, started at {win})")
EOF

# schema-check both BENCH jsons (autotune section + counter families)
python tools/trace_check.py "$OUT1" "$OUT2" || exit 1

# the renderer must handle both shapes (search and cache-hit)
python tools/mxdiag.py tune "$OUT1" > /dev/null \
  || { echo "autotune_smoke: mxdiag tune failed on run 1"; exit 1; }
python tools/mxdiag.py tune "$OUT2" > /dev/null \
  || { echo "autotune_smoke: mxdiag tune failed on run 2"; exit 1; }

# perf_regress: the two runs ran the SAME tuned config — the knob
# context must say identical. Thresholds are opened wide: this step
# tests the knob-context plumbing, not throughput stability on a noisy
# 1-core CI box (the value/MFU gates have their own smoke).
REGOUT=$(python tools/perf_regress.py --threshold 0.9 \
           --busy-threshold 0.9 "$OUT1" "$OUT2") \
  || { echo "autotune_smoke: perf_regress failed on the tuned pair"; \
       echo "$REGOUT"; exit 1; }
echo "$REGOUT" | grep -q "knob config identical" \
  || { echo "autotune_smoke: knob-context note missing:"; \
       echo "$REGOUT"; exit 1; }

# and a knob DIFF must surface as context, never as a silent verdict:
# strip the tuning from a copy of run 2 so its resolved config reverts
# to the stepwise default, then expect the CONTEXT note naming the diff
python - "$OUT2" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
at = doc["extra"]["autotune"]
at["resolved"] = dict(at["resolved"], loop_chunk=0)
json.dump(doc, open("/tmp/mxtpu_autotune_smoke_diffknobs.json", "w"))
EOF
DIFFOUT=$(python tools/perf_regress.py --threshold 0.9 \
            --busy-threshold 0.9 "$OUT1" \
            /tmp/mxtpu_autotune_smoke_diffknobs.json)
echo "$DIFFOUT" | grep -q "CONTEXT: knob config differs" \
  || { echo "autotune_smoke: knob-diff context note missing:"; \
       echo "$DIFFOUT"; exit 1; }

echo "autotune_smoke: OK"
