#!/bin/bash
# Tier-1 memscope smoke: 50 lenet train steps ON CPU through bench.py
# with memory observability armed (BENCH_MEMSCOPE=1), then assert from
# the BENCH json that
#   * extra.memscope carries a static footprint for the fused train
#     step, JOINED to its perfscope roofline row, with the closed
#     provenance taxonomy (XLA:CPU reports memory_analysis but no peak
#     field, so the peak must be "derived"),
#   * the watermark ring sampled the steady loop and stayed BOUNDED
#     (ring <= ring_limit even though samples > ring_limit),
#   * the capacity/headroom verdict is decided (host RAM is the honest
#     capacity on XLA:CPU),
#   * the memscope.* counter families + extra.memscope schema validate
#     (trace_check), `mxdiag.py mem` renders, and perf_regress flags an
#     injected 30% peak-memory growth while skipping one-sided pairs,
# then prove the SPEND side: an autotune search with an injected
# over-capacity batch candidate (MXTPU_AUTOTUNE_BATCH_CANDIDATES +
# MXTPU_MEMSCOPE_CAPACITY) must record a counted reason=memory
# pre-trial prune with ZERO subprocess trials spent on it, and the
# winner must still install from cache on the second run.
# No TPU — safe anywhere, cheap enough for CI.
set -u
cd "$(dirname "$0")/.." || exit 1

OUT=${1:-/tmp/mxtpu_memscope_smoke_bench.json}
LOG=/tmp/mxtpu_memscope_smoke.log
TUNE1=/tmp/mxtpu_memscope_smoke_tune1.json
TUNE2=/tmp/mxtpu_memscope_smoke_tune2.json
CACHE=/tmp/mxtpu_memscope_smoke_cache
DSDIR=/tmp/mxtpu_memscope_smoke_windows

rm -rf "$CACHE" "$DSDIR"
: > "$LOG"

echo "memscope_smoke: 50 lenet steps on CPU with memscope armed"
JAX_PLATFORMS=cpu BENCH_MODEL=lenet BENCH_BATCH=64 BENCH_STEPS=50 \
  BENCH_DTYPE=float32 BENCH_K1_CONTROL=0 BENCH_TRACE=0 \
  BENCH_MEMSCOPE=1 MXTPU_MEMSCOPE_RING=16 \
  timeout -k 10 900 python bench.py > "$OUT" 2> "$LOG"
rc=$?
if [ "$rc" != "0" ]; then
  echo "memscope_smoke: bench.py failed rc=$rc"; tail -30 "$LOG"
  exit 1
fi

python - "$OUT" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
if doc.get("error"):
    sys.exit(f"bench reported error: {doc['error']}")
ms = (doc.get("extra") or {}).get("memscope")
assert isinstance(ms, dict), "no extra.memscope in BENCH json"
progs = {p.get("name"): p for p in ms.get("programs") or []}
fused = next((p for n, p in progs.items()
              if n and n.startswith("fused_step")), None)
assert fused is not None, \
    f"fused train step has no static footprint (programs: {sorted(progs)})"
assert fused.get("available") is True, fused
assert fused.get("provenance") == "derived", \
    f"XLA:CPU has no peak field, expected derived, got {fused!r}"
assert isinstance(fused.get("peak_bytes"), int) \
    and fused["peak_bytes"] > 0, fused
assert "roofline" in fused, "footprint not joined to the roofline table"
wm = ms.get("watermarks") or {}
assert wm.get("ring_limit") == 16, wm.get("ring_limit")
assert wm.get("ring") <= 16, f"ring unbounded: {wm.get('ring')}"
assert wm.get("samples") >= 50, \
    f"steady loop under-sampled: {wm.get('samples')} < 50 steps"
rss = wm.get("host_rss") or {}
assert rss.get("peak"), f"no host RSS watermark on CPU: {rss!r}"
hr = ms.get("headroom") or {}
assert hr.get("verdict") in ("ok", "tight"), \
    f"headroom verdict undecided on CPU: {hr!r}"
assert (ms.get("capacity") or {}).get("source") == "host_ram", \
    ms.get("capacity")
assert ms.get("oom") is None, f"phantom OOM post-mortem: {ms['oom']!r}"
c = (doc.get("extra") or {}).get("counters") or {}
for name in ("memscope/memscope.programs_captured",
             "memscope/memscope.samples"):
    assert name in c, f"counter {name} missing from BENCH json"
print(f"memscope_smoke: footprints OK "
      f"(fused peak {fused['peak_bytes']} B [{fused['provenance']}], "
      f"ring {wm['ring']}/{wm['ring_limit']} of {wm['samples']} samples, "
      f"headroom {hr.get('headroom_fraction')})")
EOF

# schema-check the BENCH json (memscope section + counter families)
python tools/trace_check.py "$OUT" || exit 1

# the renderer must handle a real artifact
python tools/mxdiag.py mem "$OUT" > /dev/null \
  || { echo "memscope_smoke: mxdiag mem failed"; exit 1; }

# the peak-memory regression gate: self-vs-self passes, a synthetic 30%
# peak growth fails, one-sided memscope pairs are skipped (both-sides)
python tools/perf_regress.py "$OUT" "$OUT" > /dev/null \
  || { echo "memscope_smoke: perf_regress failed self-vs-self"; exit 1; }
python - "$OUT" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
wm = doc["extra"]["memscope"]["watermarks"]
for sect in ("device", "host_rss"):
    if isinstance(wm.get(sect), dict) and wm[sect].get("peak"):
        wm[sect]["peak"] = int(wm[sect]["peak"] * 1.3)
json.dump(doc, open("/tmp/mxtpu_memscope_smoke_hungry.json", "w"))
doc["extra"].pop("memscope")
json.dump(doc, open("/tmp/mxtpu_memscope_smoke_noms.json", "w"))
EOF
python tools/perf_regress.py --threshold 0.9 --busy-threshold 0.9 \
  "$OUT" /tmp/mxtpu_memscope_smoke_hungry.json > /dev/null 2>&1
if [ "$?" = "0" ]; then
  echo "memscope_smoke: perf_regress missed a 30% peak-memory growth"
  exit 1
fi
python tools/perf_regress.py --threshold 0.9 --busy-threshold 0.9 \
  /tmp/mxtpu_memscope_smoke_noms.json "$OUT" > /dev/null \
  || { echo "memscope_smoke: one-sided memscope must be skipped, not gated"; \
       exit 1; }

# ---- the SPEND side: the autotuner's memory-feasibility pruner --------
# An injected batch candidate of 65536 (1024x the baseline's 64) cannot
# fit under an 8 GiB capacity override: the linear-batch prediction
# scales the baseline's measured RSS peak far past capacity x headroom,
# so the candidate must be rejected BEFORE any subprocess is spawned.
run_tuned() {
  JAX_PLATFORMS=cpu MXTPU_AUTOTUNE=1 MXTPU_AUTOTUNE_CACHE="$CACHE" \
    MXTPU_AUTOTUNE_BUDGET=2 MXTPU_AUTOTUNE_STEPS=8 \
    MXTPU_AUTOTUNE_TRIAL_TIMEOUT=420 \
    MXTPU_AUTOTUNE_BATCH_CANDIDATES=65536 \
    MXTPU_MEMSCOPE_CAPACITY=8589934592 \
    MXTPU_DEVICESCOPE_DIR="$DSDIR" \
    BENCH_MODEL=lenet BENCH_BATCH=64 BENCH_STEPS=24 \
    BENCH_DTYPE=float32 BENCH_K1_CONTROL=0 \
    BENCH_TRACE=0 BENCH_DEVICESCOPE=1 BENCH_MEMSCOPE=1 \
    timeout -k 10 1500 python bench.py > "$1" 2>> "$LOG"
}

echo "memscope_smoke: autotune run 1 (injected over-capacity batch)"
run_tuned "$TUNE1"
rc=$?
if [ "$rc" != "0" ]; then
  echo "memscope_smoke: tuned bench run 1 failed rc=$rc"; tail -30 "$LOG"
  exit 1
fi

python - "$TUNE1" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
at = (doc.get("extra") or {}).get("autotune")
assert isinstance(at, dict) and at.get("enabled") is True, at
assert at.get("cache_hit") is False, "run 1 must be a cache MISS"
pruned = at.get("pruned") or {}
reason = pruned.get("batch=65536")
assert isinstance(reason, str) and reason.startswith("memory:"), \
    f"over-capacity batch not pruned with reason=memory: {pruned!r}"
# zero subprocess spent: no trial row may carry the infeasible batch
for row in at.get("trial_table") or []:
    cfg = row.get("config") or {}
    assert cfg.get("batch") != 65536, \
        f"a subprocess WAS spent on the infeasible batch: {row!r}"
# counter == payload contract: the counted prunes include this one
tp = at.get("trials_pruned")
assert isinstance(tp, int) and tp >= 1, f"trials_pruned={tp!r}"
c = (doc.get("extra") or {}).get("counters") or {}
assert c.get("autotune/autotune.trials_pruned") == tp, \
    (c.get("autotune/autotune.trials_pruned"), tp)
assert "memscope/memscope.infeasible_candidates" in c, \
    "infeasible candidate not counted in the memscope family"
print(f"memscope_smoke: pruner OK (batch=65536 rejected pre-trial, "
      f"{tp} candidate(s) pruned, reason: {reason[:72]}...)")
EOF

echo "memscope_smoke: autotune run 2 (same key -> cache hit)"
run_tuned "$TUNE2"
rc=$?
if [ "$rc" != "0" ]; then
  echo "memscope_smoke: tuned bench run 2 failed rc=$rc"; tail -30 "$LOG"
  exit 1
fi

python - "$TUNE1" "$TUNE2" <<'EOF' || exit 1
import json, sys
d1 = json.load(open(sys.argv[1]))
d2 = json.load(open(sys.argv[2]))
at = (d2.get("extra") or {}).get("autotune")
assert isinstance(at, dict) and at.get("cache_hit") is True, \
    f"run 2 must be a cache HIT: {at and at.get('cache_hit')!r}"
assert at.get("trials") == 0, at.get("trials")
w1 = ((d1.get("extra") or {}).get("autotune") or {}).get("winner")
assert at.get("winner") == w1, (at.get("winner"), w1)
print("memscope_smoke: cache hit OK (winner installed, 0 trials)")
EOF

# both tuned artifacts must also validate (autotune + memscope sections)
python tools/trace_check.py "$TUNE1" "$TUNE2" || exit 1

echo "memscope_smoke: OK"
