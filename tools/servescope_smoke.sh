#!/bin/bash
# Tier-1 servescope smoke: the closed-loop load harness on CPU lenet
# (64 clients at the top of the ramp), asserting the acceptance
# contract end to end:
#   * tools/serve_load.py produces a trace_check-valid BENCH json with
#     a saturation knee and the full tail-latency attribution,
#   * the per-component p99 attribution sums to the measured e2e p99
#     within 15% (the acceptance bound; the spans' accounting identity
#     makes this structural),
#   * every compiled bucket carries its roofline verdict AND its
#     commscope resharding verdict (clean on an unsharded CPU model),
#   * the mxtpu.events/1 request/batch correlation stream validates,
#   * mxdiag.py serve renders the report,
#   * perf_regress.py accepts the artifact self-vs-self and FLAGS an
#     injected 20% p99 degradation at the serving threshold (0.15).
# No TPU - safe anywhere, CI-cheap.
set -u
cd "$(dirname "$0")/.." || exit 1

OUT=${1:-/tmp/mxtpu_serve_load.json}
EVENTS="${OUT%.json}_events.jsonl"
LOG=${MXTPU_SERVESCOPE_SMOKE_LOG:-/tmp/mxtpu_servescope_smoke.log}

echo "servescope_smoke: ramped closed-loop sweep on CPU lenet (to 64 clients)"
JAX_PLATFORMS=cpu timeout -k 10 900 python tools/serve_load.py \
  --model lenet --ramp 4,8,16,32,64 --level-requests 96 \
  --out "$OUT" --events "$EVENTS" > "$LOG" 2>&1
rc=$?
if [ "$rc" != "0" ]; then
  echo "servescope_smoke: serve_load.py failed rc=$rc"; tail -30 "$LOG"
  exit 1
fi
tail -5 "$LOG"

python - "$OUT" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc.get("status") != "env_failure", f"env failure: {doc.get('error')}"
extra = doc.get("extra") or {}
sl = extra.get("serve_load") or {}
assert sl.get("levels"), "no sweep levels in extra.serve_load"
assert isinstance(sl.get("knee_index"), int), "no saturation knee found"
ss = extra.get("servescope") or {}
assert ss, "no extra.servescope attribution in the BENCH json"
assert ss.get("requests") > 0, "servescope traced no requests"

# acceptance bound: the p99 attribution's component sum must sit within
# 15% of the measured e2e p99 it attributes — overall AND per bucket
def check(group, where):
    att = (group.get("attribution") or {}).get("p99")
    assert att, f"{where}: no p99 attribution"
    s, q = att["sum_ms"], att["e2e_ms"]
    comp_sum = sum(att["components"].values())
    assert abs(comp_sum - s) < max(0.05, 0.01 * s), \
        f"{where}: sum_ms {s} != component sum {comp_sum}"
    off = abs(s - q) / q if q else 0.0
    assert off <= 0.15, \
        f"{where}: p99 attribution {s:.3f} ms vs e2e p99 {q:.3f} ms " \
        f"({off:.1%} > 15%)"
    return off

offs = [check(ss["overall"], "overall")]
for b, grp in (ss.get("per_bucket") or {}).items():
    offs.append(check(grp, f"bucket {b}"))
    # every compiled bucket that served traffic carries BOTH verdicts
    assert grp.get("verdict") is not None, f"bucket {b}: no roofline verdict"
    assert grp.get("resharding_collectives") is not None, \
        f"bucket {b}: no resharding verdict"
    assert grp.get("resharding_collectives") == 0, \
        f"bucket {b}: unexpected resharding on an unsharded CPU model"
assert ss.get("advice"), "no attribution advice line"
knee = sl["levels"][sl["knee_index"]]
print(f"servescope_smoke: attribution OK (max quantile gap "
      f"{max(offs):.1%} <= 15%) over {ss['requests']} traced requests; "
      f"knee at {knee['concurrency']} clients, "
      f"{knee['qps']} qps, p99 {knee['p99_ms']} ms")
print(f"servescope_smoke: advice: {ss['advice']}")
EOF

# artifact validation: the BENCH json (servescope + serve_load schema)
# and the request/batch correlation event stream
python tools/trace_check.py "$OUT" "$EVENTS" || exit 1

# the correlation contract: every sampled serving.request joins a
# serving.batch record through batch_id
python - "$EVENTS" <<'EOF' || exit 1
import json, sys
recs = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
reqs = [r for r in recs if r["name"] == "serving.request"]
batches = {(r.get("args") or {}).get("batch_id")
           for r in recs if r["name"] == "serving.batch"}
assert reqs, "no serving.request events emitted"
responded = [r for r in reqs if r["args"].get("status") == "responded"]
assert responded, "no responded serving.request events"
missing = [r for r in responded if r["args"].get("batch_id") not in batches]
assert not missing, \
    f"{len(missing)} request events with no matching serving.batch"
print(f"servescope_smoke: events OK ({len(responded)} request spans "
      f"joined to {len(batches)} batch records)")
EOF

# the report must render
python tools/mxdiag.py serve "$OUT" > /dev/null || {
  echo "servescope_smoke: mxdiag.py serve failed to render"; exit 1; }
echo "servescope_smoke: mxdiag serve renders"

# regression gate: self-vs-self must be clean; an injected 20% p99
# degradation must be FLAGGED at the serving threshold
BASE=/tmp/mxtpu_serve_load_base.json
BAD=/tmp/mxtpu_serve_load_bad.json
cp "$OUT" "$BASE"
python tools/perf_regress.py --p99-threshold 0.15 "$BASE" "$OUT" \
  > /dev/null || {
  echo "servescope_smoke: perf_regress flagged self-vs-self"; exit 1; }
python - "$OUT" "$BAD" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
sl = doc["extra"]["serve_load"]
k = sl["knee_index"]
sl["levels"][k]["p99_ms"] = round(sl["levels"][k]["p99_ms"] * 1.2, 3)
sl["p99_at_knee_ms"] = sl["levels"][k]["p99_ms"]
doc["extra"]["serving"]["p99_ms"] = sl["p99_at_knee_ms"]
json.dump(doc, open(sys.argv[2], "w"))
EOF
python tools/perf_regress.py --p99-threshold 0.15 "$BASE" "$BAD" \
  > /dev/null
if [ "$?" != "1" ]; then
  echo "servescope_smoke: injected 20% p99 degradation NOT flagged"
  exit 1
fi
echo "servescope_smoke: perf_regress clean self-vs-self, flags +20% p99"
echo "servescope_smoke: all servescope artifacts validate"
