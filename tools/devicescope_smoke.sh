#!/bin/bash
# Tier-1 devicescope smoke: 50 lenet train steps ON CPU through bench.py
# with a measured device-timeline capture window armed
# (BENCH_DEVICESCOPE=1), then assert from the BENCH json that
#   * extra.devicescope carries a COMPLETED window whose measured busy
#     fraction is in (0, 1],
#   * the top-K device-op table is nonempty and joined to perfscope's
#     program table (the fused train step must appear as a program),
#   * the reconciliation block is present: measured device_compute set
#     beside the probe-based analytic number, and the step budget's
#     provenance upgraded to measured(profile),
#   * the devicescope.* counter families + extra.devicescope schema
#     validate (trace_check),
#   * `mxdiag.py device` and `mxdiag.py perf` render it,
# and that the artifact-dir rotation bounds repeated runs.
# No TPU — safe anywhere, cheap enough for CI.
set -u
cd "$(dirname "$0")/.." || exit 1

OUT=${1:-/tmp/mxtpu_devicescope_smoke_bench.json}
LOG=/tmp/mxtpu_devicescope_smoke.log
DSDIR=/tmp/mxtpu_devicescope_smoke_windows

rm -rf "$DSDIR"
echo "devicescope_smoke: 50 lenet steps on CPU with a capture window"
JAX_PLATFORMS=cpu BENCH_MODEL=lenet BENCH_BATCH=64 BENCH_STEPS=50 \
  BENCH_DTYPE=float32 BENCH_K1_CONTROL=0 BENCH_DEVICESCOPE=1 \
  MXTPU_DEVICESCOPE_DIR="$DSDIR" \
  BENCH_TRACE_FILE=/tmp/mxtpu_devicescope_smoke_trace.json \
  timeout -k 10 900 python bench.py > "$OUT" 2> "$LOG"
rc=$?
if [ "$rc" != "0" ]; then
  echo "devicescope_smoke: bench.py failed rc=$rc"; tail -30 "$LOG"
  exit 1
fi

python - "$OUT" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
if doc.get("error"):
    sys.exit(f"bench reported error: {doc['error']}")
ds = (doc.get("extra") or {}).get("devicescope")
assert isinstance(ds, dict), "no extra.devicescope in BENCH json"
win = ds.get("window")
assert isinstance(win, dict), f"no completed capture window: {ds!r}"
assert win.get("complete") is True, f"window incomplete: {win!r}"
bf = ds.get("busy_fraction")
assert isinstance(bf, (int, float)) and 0.0 < bf <= 1.0, \
    f"busy fraction {bf!r} not in (0, 1]"
tops = ds.get("top_ops") or []
assert tops, "top-K device-op table is empty"
progs = {t.get("program") for t in tops}
assert any(p and p.startswith("fused_step") for p in progs), \
    f"top-K not joined to the fused train step (programs: {progs})"
gaps = ds.get("gaps") or {}
tax = gaps.get("taxonomy") or {}
assert all(isinstance(tax.get(k), (int, float))
           for k in ("input_starved_ms", "dispatch_serialized_ms",
                     "host_gap_ms")), f"gap taxonomy malformed: {tax!r}"
recon = ds.get("reconciliation")
assert isinstance(recon, dict), "no reconciliation block"
assert isinstance((recon.get("measured") or {}).get(
    "device_compute_ms"), (int, float)), recon
assert isinstance((recon.get("analytic") or {}).get(
    "device_compute_ms"), (int, float)), recon
d = ((doc.get("extra") or {}).get("perfscope") or {}).get(
    "decomposition") or {}
assert d.get("source") == "measured(profile)", \
    f"budget provenance not upgraded: {d.get('source')!r}"
c = (doc.get("extra") or {}).get("counters") or {}
for name in ("devicescope/devicescope.windows",
             "devicescope/devicescope.busy_fraction"):
    assert name in c, f"counter {name} missing from BENCH json"
print(f"devicescope_smoke: window OK (busy={bf:.1%}, "
      f"{len(tops)} top ops, drift_warning="
      f"{recon.get('drift_warning')})")
EOF

# schema-check the BENCH json (devicescope section + counter families)
python tools/trace_check.py "$OUT" || exit 1

# the renderers must handle a real artifact
python tools/mxdiag.py device "$OUT" > /dev/null \
  || { echo "devicescope_smoke: mxdiag device failed"; exit 1; }
python tools/mxdiag.py perf "$OUT" > /dev/null \
  || { echo "devicescope_smoke: mxdiag perf failed"; exit 1; }

# rotation: a second armed run must not grow the artifact dir past the
# keep bound (3 window dirs)
JAX_PLATFORMS=cpu BENCH_MODEL=lenet BENCH_BATCH=64 BENCH_STEPS=20 \
  BENCH_DTYPE=float32 BENCH_K1_CONTROL=0 BENCH_DEVICESCOPE=1 \
  MXTPU_DEVICESCOPE_DIR="$DSDIR" BENCH_TRACE=0 \
  timeout -k 10 900 python bench.py > /tmp/mxtpu_ds_smoke2.json 2>> "$LOG" \
  || { echo "devicescope_smoke: second bench run failed"; exit 1; }
NDIRS=$(find "$DSDIR" -maxdepth 1 -name 'win_*' -type d | wc -l)
if [ "$NDIRS" -gt 3 ]; then
  echo "devicescope_smoke: rotation failed ($NDIRS window dirs > 3)"
  exit 1
fi

# the busy-fraction regression gate: self-vs-self passes, a synthetic
# 30% busy drop fails, one-sided windows are skipped (both-sides rule)
python tools/perf_regress.py "$OUT" "$OUT" > /dev/null \
  || { echo "devicescope_smoke: perf_regress failed self-vs-self"; exit 1; }
python - "$OUT" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
ds = doc["extra"]["devicescope"]
ds["busy_fraction"] = round(ds["busy_fraction"] * 0.7, 6)
json.dump(doc, open("/tmp/mxtpu_ds_smoke_degraded.json", "w"))
del doc["extra"]["devicescope"]
json.dump(doc, open("/tmp/mxtpu_ds_smoke_nowin.json", "w"))
EOF
python tools/perf_regress.py "$OUT" /tmp/mxtpu_ds_smoke_degraded.json \
  > /dev/null 2>&1
if [ "$?" = "0" ]; then
  echo "devicescope_smoke: perf_regress missed a 30% busy-fraction drop"
  exit 1
fi
python tools/perf_regress.py /tmp/mxtpu_ds_smoke_nowin.json "$OUT" \
  > /dev/null \
  || { echo "devicescope_smoke: one-sided window must be skipped, not gated"; \
       exit 1; }

echo "devicescope_smoke: OK"
