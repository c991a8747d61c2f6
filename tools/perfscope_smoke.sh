#!/bin/bash
# Tier-1 perfscope smoke: 50 lenet train steps ON CPU through bench.py
# with roofline cost capture + step-time decomposition armed, then
# assert from the BENCH json that
#   * extra.perfscope is present: decomposition components all there and
#     summing to within 15% of measured step_ms (the acceptance bound),
#   * at least one compiled hot program carries a roofline verdict from
#     the known taxonomy (the fused train step must be among them),
#   * the perfscope.* counter families validate (trace_check),
# and that the regression gate behaves:
#   * perf_regress self-vs-self exits 0,
#   * perf_regress vs a synthetically 20%-degraded copy exits nonzero,
#   * perf_regress SKIPS an env_failure artifact instead of reading it
#     as a 100% regression.
# No TPU — safe anywhere, cheap enough for CI.
set -u
cd "$(dirname "$0")/.." || exit 1

OUT=${1:-/tmp/mxtpu_perfscope_smoke_bench.json}
LOG=/tmp/mxtpu_perfscope_smoke.log

echo "perfscope_smoke: 50 lenet steps on CPU with perfscope armed"
JAX_PLATFORMS=cpu BENCH_MODEL=lenet BENCH_BATCH=64 BENCH_STEPS=50 \
  BENCH_DTYPE=float32 BENCH_K1_CONTROL=0 \
  BENCH_TRACE_FILE=/tmp/mxtpu_perfscope_smoke_trace.json \
  timeout -k 10 900 python bench.py > "$OUT" 2> "$LOG"
rc=$?
if [ "$rc" != "0" ]; then
  echo "perfscope_smoke: bench.py failed rc=$rc"; tail -30 "$LOG"
  exit 1
fi

python - "$OUT" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
if doc.get("error"):
    sys.exit(f"bench reported error: {doc['error']}")
ps = (doc.get("extra") or {}).get("perfscope")
assert isinstance(ps, dict), "no extra.perfscope in BENCH json"
d = ps.get("decomposition")
assert isinstance(d, dict), "no step-time decomposition"
comps = ("device_compute_ms", "collective_ms", "input_wait_ms",
         "host_gap_ms", "other_ms")
for c in comps:
    assert isinstance(d.get(c), (int, float)) and d[c] >= 0, \
        f"component {c} missing/invalid: {d.get(c)!r}"
step = d["step_ms"]
total = sum(d[c] for c in comps)
off = abs(total - step) / step
assert off <= 0.15, \
    f"components sum {total:.3f} vs step_ms {step:.3f}: {off:.1%} > 15%"
progs = ps.get("programs") or []
verdicts = {p["name"]: p["verdict"] for p in progs}
assert any(n.startswith("fused_step") for n in verdicts), \
    f"no fused_step program analyzed (got {sorted(verdicts)})"
allowed = {"compute_bound", "hbm_bound", "trivial", "unknown"}
assert all(v in allowed for v in verdicts.values()), verdicts
c = (doc.get("extra") or {}).get("counters") or {}
for name in ("perfscope/perfscope.programs_analyzed",
             "perfscope/perfscope.step_ms",
             "perfscope/perfscope.device_compute_ms"):
    assert name in c, f"counter {name} missing from BENCH json"
print(f"perfscope_smoke: decomposition OK (step_ms={step:.2f}, "
      f"coverage={d.get('coverage')}, "
      f"verdicts={sorted(set(verdicts.values()))})")
EOF

# schema-check the BENCH json (perfscope section + counter families)
python tools/trace_check.py "$OUT" || exit 1

# regression gate: self-comparison must pass ...
python tools/perf_regress.py "$OUT" "$OUT" > /dev/null \
  || { echo "perfscope_smoke: perf_regress failed self-vs-self"; exit 1; }
# ... a 20% img/s+MFU degradation must fail ...
python - "$OUT" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
doc["value"] = round(doc["value"] * 0.8, 2)
extra = doc.setdefault("extra", {})
if isinstance(extra.get("mfu"), (int, float)):
    extra["mfu"] = round(extra["mfu"] * 0.8, 6)
json.dump(doc, open("/tmp/mxtpu_perfscope_degraded.json", "w"))
json.dump({"metric": doc["metric"], "value": 0.0, "unit": doc["unit"],
           "status": "env_failure", "error": "injected: no backend"},
          open("/tmp/mxtpu_perfscope_envfail.json", "w"))
EOF
if python tools/perf_regress.py "$OUT" /tmp/mxtpu_perfscope_degraded.json \
    > /dev/null; then
  echo "perfscope_smoke: perf_regress MISSED a 20% regression"; exit 1
fi
# ... and an env_failure candidate is SKIPPED (exit 0), not flagged.
python tools/perf_regress.py "$OUT" /tmp/mxtpu_perfscope_envfail.json \
  > /dev/null \
  || { echo "perfscope_smoke: perf_regress did not skip env_failure"; exit 1; }

echo "perfscope_smoke: attribution + regression gate validate"
