#!/bin/bash
# Tier-1 commscope smoke: 50 lenet train steps ON CPU through bench.py
# under BENCH_MESH=fsdp4 on 4 FAKE host devices (no TPU) with
# collective extraction armed, then assert from the BENCH json that
#   * extra.commscope is present with the steady train program captured,
#   * the collective inventory is NONZERO (fsdp must all-gather params
#     and reduce the grads — an empty inventory means extraction broke),
#   * every op kind is from the closed taxonomy and the payload bytes /
#     estimates are well-formed,
#   * the resharding detector found NOTHING (the bench net is correctly
#     annotated; a count here is a real finding or a detector bug),
#   * the step budget's collective component carries provenance
#     "estimated" (the kvstore counter is blind to in-program GSPMD
#     collectives — reporting a measured zero is the bug this layer
#     fixes),
#   * the artifact trace_check-validates (commscope.* counter family +
#     extra.commscope schema) and `mxdiag.py comms` renders it.
# No TPU — safe anywhere, cheap enough for CI.
set -u
cd "$(dirname "$0")/.." || exit 1

OUT=${1:-/tmp/mxtpu_comms_smoke_bench.json}
LOG=/tmp/mxtpu_comms_smoke.log

echo "comms_smoke: 50 lenet steps on a 4-fake-device fsdp mesh"
env XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
  BENCH_MODEL=lenet BENCH_BATCH=64 BENCH_STEPS=50 BENCH_DTYPE=float32 \
  BENCH_MESH=fsdp4 BENCH_K1_CONTROL=0 BENCH_PERFSCOPE_PROBE=2 \
  BENCH_TRACE_FILE=/tmp/mxtpu_comms_smoke_trace.json \
  timeout -k 10 900 python bench.py > "$OUT" 2> "$LOG"
rc=$?
if [ "$rc" != "0" ]; then
  echo "comms_smoke: bench.py failed rc=$rc"; tail -30 "$LOG"
  exit 1
fi

python - "$OUT" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
if doc.get("error"):
    sys.exit(f"bench reported error: {doc['error']}")
cs = (doc.get("extra") or {}).get("commscope")
assert isinstance(cs, dict), "no extra.commscope in BENCH json"
progs = {p["name"]: p for p in cs.get("programs") or []}
train = [p for n, p in progs.items() if n.startswith("fused_step")]
assert train, f"no fused_step program captured (got {sorted(progs)})"
rec = train[-1]
t = rec["totals"]
assert t["count"] > 0 and t["bytes"] > 0, \
    f"fsdp4 inventory empty: {t} (extraction broke)"
kinds = {c["kind"] for c in rec["collectives"]}
allowed = {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
           "collective-permute", "other"}
assert kinds <= allowed, f"kinds outside taxonomy: {kinds - allowed}"
assert "all-gather" in kinds, \
    f"fsdp4 shows no all-gather (kinds={sorted(kinds)}) — the mode's " \
    f"param gather is missing from the inventory"
assert rec["resharding_collectives"] == 0, \
    f"resharding detector fired on the correctly-annotated bench net: " \
    f"{rec['resharding']}"
step = cs.get("step")
assert isinstance(step, dict) and step.get("bytes", 0) > 0, \
    f"no steady-step collective summary: {step}"
d = ((doc.get("extra") or {}).get("perfscope") or {}).get("decomposition")
assert isinstance(d, dict), "no perfscope decomposition to carry provenance"
assert d.get("collective_source") == "estimated", \
    f"sharded-mode collective provenance is {d.get('collective_source')!r}," \
    f" expected 'estimated' (measured-zero is the mis-attribution bug)"
c = (doc.get("extra") or {}).get("counters") or {}
for name in ("commscope/commscope.programs_analyzed",
             "commscope/commscope.collectives",
             "commscope/commscope.payload_bytes",
             "commscope/commscope.step_collective_bytes"):
    assert name in c, f"counter {name} missing from BENCH json"
assert c.get("commscope/commscope.resharding_collectives", 0) == 0, \
    "resharding counter nonzero on a clean layout"
print(f"comms_smoke: inventory OK ({t['count']} collectives, "
      f"{t['bytes']} B, est {t['est_ms']:.4f} ms/step, "
      f"kinds={sorted(kinds)}, provenance=estimated)")
EOF

# schema-check the BENCH json (commscope counter family + extra schema)
python tools/trace_check.py "$OUT" || exit 1

# the comms renderer must read a real artifact end-to-end
python tools/mxdiag.py comms "$OUT" > /tmp/mxtpu_comms_smoke_render.txt \
  || { echo "comms_smoke: mxdiag.py comms failed on the artifact"; exit 1; }
grep -q "all-gather" /tmp/mxtpu_comms_smoke_render.txt \
  || { echo "comms_smoke: comms table missing the all-gather row"; exit 1; }

echo "comms_smoke: collective observability validates"
