#!/bin/bash
# Tier-1 fleetscope smoke (CPU-only, no TPU): proves the
# cross-process tracing claims end to end on a spawned 2-replica CPU
# lenet fleet driven by serve_load (every request carries a
# client-minted W3C traceparent, sample=1 so every request is a span):
#   (a) traces JOIN — >= 95% of router-observed successful forwards
#       have a replica-side span with the matching trace_id and a
#       parent_id equal to the router's span (one request = ONE trace);
#   (b) the accounting ADDS UP — per joined trace, router overhead
#       (e2e - forward) + wire gap (forward - replica e2e) + the
#       replica span's five-way attribution reconstruct the router's
#       e2e within 15% at the median (the wire gap is a difference of
#       perf_counter durations, so clock skew cannot enter it);
#   (c) the collector PULLED — every replica's diagnostics.export
#       endpooint answered at least once, with a finite offset bound;
#   (d) the views RENDER and the artifacts VALIDATE — mxdiag trace/pod
#       exit 0 on the real artifacts, trace_check accepts the BENCH
#       json, the harness + per-replica event logs, and the merged
#       mxtpu.events/2 timeline.
set -u
cd "$(dirname "$0")/.." || exit 1

SMOKE_DIR=${MXTPU_FLEETSCOPE_SMOKE_DIR:-/tmp/mxtpu_fleetscope_smoke}
rm -rf "$SMOKE_DIR"; mkdir -p "$SMOKE_DIR"
export JAX_PLATFORMS=cpu

OUT="$SMOKE_DIR/fleet2.json"
EVENTS="$SMOKE_DIR/events.jsonl"

echo "fleetscope_smoke: 2-replica spawned fleet under serve_load"
echo "fleetscope_smoke: (sample=1: every request minted AND spanned)"
timeout -k 10 900 python tools/serve_load.py --fleet 2 \
  --ramp 4,8 --level-requests 64 --sample 1 \
  --fleet-cache "$SMOKE_DIR/aot_cache" \
  --out "$OUT" --events "$EVENTS" > "$SMOKE_DIR/serve_load.log" 2>&1
rc=$?
if [ "$rc" != "0" ]; then
  echo "fleetscope_smoke: serve_load failed rc=$rc"
  tail -30 "$SMOKE_DIR/serve_load.log"; exit 1
fi

# every artifact must validate structurally: the BENCH json, the
# harness (router) events log, and each worker's own events log
python tools/trace_check.py "$OUT" "$EVENTS" \
  "$SMOKE_DIR"/events_replica_*.jsonl || exit 1

# (a)+(b)+(c): join rate, accounting identity, collector pulls
python - "$OUT" "$EVENTS" "$SMOKE_DIR" <<'EOF' || exit 1
import glob, json, os, sys

doc = json.load(open(sys.argv[1]))
events_path, smoke_dir = sys.argv[2], sys.argv[3]
fs = (doc.get("extra") or {}).get("fleetscope") or {}
assert fs, "serve_load wrote no extra.fleetscope"

# (a) >= 95% of sampled forwards joined
assert fs["sampled"] > 0, fs
rate = fs["join_rate"]
assert rate >= 0.95, \
    f"only {rate:.1%} of {fs['sampled']} traces joined " \
    f"({fs['unjoined_forwards']} unjoined)"
assert fs["client_minted"] >= fs["sampled"], fs
gap = fs.get("wire_gap_ms") or {}
assert gap.get("p50") is not None and gap["p50"] >= -1.0, gap
rows = fs.get("per_replica") or []
assert len(rows) == 2 and all(r["traces"] > 0 for r in rows), \
    f"a replica joined no traces: {rows}"

# (c) the collector pulled every replica at least once
coll = fs.get("collector") or {}
procs = coll.get("processes") or {}
assert len(procs) == 2, f"collector saw {len(procs)} processes"
for name, p in procs.items():
    assert p["pulls"] > 0, f"{name}: no successful pull ({p})"
    assert p["offset_bound_s"] is not None and \
        p["offset_bound_s"] >= 0, p

# (b) re-derive the accounting from the RAW event logs: router
# overhead + wire gap + the replica span's five components must
# reconstruct the router's e2e (the components sum to replica e2e by
# the servescope identity; the wire gap closes the rest)
def recs(path, name):
    out = []
    for ln in open(path):
        r = json.loads(ln)
        if r.get("name") == name:
            out.append(r)
    return out

rtr = {r["args"]["trace_id"]: r["args"]
       for r in recs(events_path, "fleetscope.request")
       if r["args"].get("status") == 200}
rep = {}
for p in glob.glob(os.path.join(smoke_dir, "events_replica_*.jsonl")):
    for r in recs(p, "serving.request"):
        tid = (r.get("args") or {}).get("trace_id")
        if tid:
            rep[tid] = r["args"]
COMPONENTS = ("queue_wait_ms", "coalesce_delay_ms", "pad_overhead_ms",
              "device_exec_ms", "respond_ms")
errs = []
for tid, ra in rtr.items():
    pa = rep.get(tid)
    if pa is None or "forward_ms" not in ra or "e2e_ms" not in pa:
        continue
    overhead = ra["e2e_ms"] - ra["forward_ms"]
    wire = ra["forward_ms"] - pa["e2e_ms"]
    comp = sum(pa.get(k, 0.0) for k in COMPONENTS)
    rebuilt = overhead + wire + comp
    errs.append(abs(rebuilt - ra["e2e_ms"]) / max(ra["e2e_ms"], 1e-9))
assert len(errs) >= 0.95 * len(rtr), \
    f"only {len(errs)}/{len(rtr)} traces fully reconstructible"
errs.sort()
med = errs[len(errs) // 2]
assert med <= 0.15, \
    f"median accounting error {med:.1%} > 15%: the spans do not add up"

# hand one joined trace id to the renderer step
tid = next(t for t in rtr if t in rep)
open(os.path.join(smoke_dir, "trace_id.txt"), "w").write(tid)
print(f"fleetscope_smoke: {fs['joined']}/{fs['sampled']} joined "
      f"({rate:.1%}), wire gap p50 {gap['p50']:.2f} ms, median "
      f"accounting error {med:.2%} over {len(errs)} traces, "
      f"{sum(p['pulls'] for p in procs.values())} collector pulls")
EOF

# (d) the views must tell the story from the artifacts alone
TID=$(cat "$SMOKE_DIR/trace_id.txt")
python tools/mxdiag.py trace "$TID" "$EVENTS" \
  "$SMOKE_DIR"/events_replica_*.jsonl > "$SMOKE_DIR/mxdiag_trace.txt" \
  || { echo "fleetscope_smoke: mxdiag trace failed"; exit 1; }
grep -q "wire gap" "$SMOKE_DIR/mxdiag_trace.txt" || {
  echo "fleetscope_smoke: mxdiag trace lost the wire gap"; exit 1; }
python tools/mxdiag.py pod "$OUT" > "$SMOKE_DIR/mxdiag_pod.txt" \
  || { echo "fleetscope_smoke: mxdiag pod failed"; exit 1; }
grep -q "replica0" "$SMOKE_DIR/mxdiag_pod.txt" || {
  echo "fleetscope_smoke: mxdiag pod lost the replica table"; exit 1; }

# the clock-aligned merge must produce a valid mxtpu.events/2 stream
python tools/mxdiag.py merge "$EVENTS" \
  "$SMOKE_DIR"/events_replica_*.jsonl -o "$SMOKE_DIR/merged.jsonl" \
  --tail 5 > /dev/null || exit 1
python tools/trace_check.py "$SMOKE_DIR/merged.jsonl" || exit 1
grep -q '"schema": "mxtpu.events/2"' "$SMOKE_DIR/merged.jsonl" || {
  echo "fleetscope_smoke: merge did not write mxtpu.events/2"; exit 1; }

# the join-rate context note must ride the perf_regress report
python tools/perf_regress.py "$OUT" "$OUT" \
  > "$SMOKE_DIR/perf_regress.txt" || {
  echo "fleetscope_smoke: perf_regress rejected the artifact"; exit 1; }
grep -q "fleetscope trace-join rate" "$SMOKE_DIR/perf_regress.txt" || {
  echo "fleetscope_smoke: perf_regress lost the join-rate context"
  exit 1; }

echo "fleetscope_smoke: all fleetscope artifacts validate"
