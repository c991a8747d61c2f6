#!/bin/bash
# Tier-1 whole-loop-executor smoke: 50 lenet train steps ON CPU through
# mxtpu.trainloop (BENCH_LOOP_CHUNK chunks of 5 + the device prefetcher),
# then assert from the BENCH json that
#   * the loss went DOWN over the run (the executor actually trains),
#   * the io.* counter family is present (io.wait_ms — starvation is
#     measurable) and io.batches_prefetched advanced,
#   * trainer.dispatches_per_step < 1 (k micro-steps rode one dispatch),
#   * the trainloop.* family is present and consistent (steps == 50).
# No TPU — safe anywhere, cheap enough for CI.
set -u
cd "$(dirname "$0")/.." || exit 1

OUT=${1:-/tmp/mxtpu_trainloop_smoke_bench.json}
LOG=/tmp/mxtpu_trainloop_smoke.log

echo "trainloop_smoke: 50 lenet steps on CPU via the whole-loop executor"
JAX_PLATFORMS=cpu BENCH_MODEL=lenet BENCH_BATCH=64 BENCH_STEPS=50 \
  BENCH_DTYPE=float32 BENCH_LOOP_CHUNK=5 BENCH_K1_CONTROL=0 \
  BENCH_TRACE_FILE=/tmp/mxtpu_trainloop_smoke_trace.json \
  timeout -k 10 900 python bench.py > "$OUT" 2> "$LOG"
rc=$?
if [ "$rc" != "0" ]; then
  echo "trainloop_smoke: bench.py failed rc=$rc"; tail -30 "$LOG"
  exit 1
fi

python - "$OUT" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
if doc.get("error"):
    sys.exit(f"bench reported error: {doc['error']}")
extra = doc.get("extra") or {}
assert extra.get("loop_chunk") == 5, f"loop_chunk={extra.get('loop_chunk')}"
assert extra.get("steps") == 50, f"steps={extra.get('steps')}"
assert "mfu" in extra and extra["mfu"] is None, \
    "a CPU run must carry a null MFU (no peaks for this device)"
c = extra.get("counters") or {}
for name in ("io/io.wait_ms", "io/io.batches_prefetched", "io/io.depth",
             "trainloop/trainloop.chunks", "trainloop/trainloop.steps"):
    assert name in c, f"counter {name} missing from BENCH json"
assert c["io/io.batches_prefetched"] >= 50, c["io/io.batches_prefetched"]
# >= : the counter also covers the compile/warmup chunk before timing
assert c["trainloop/trainloop.steps"] >= 50, c["trainloop/trainloop.steps"]
dps = extra.get("dispatches_per_step")
assert dps is not None and dps < 1, \
    f"dispatches_per_step={dps} (whole-loop executor should be < 1)"
# loss must decrease: final vs the first compiled step's magnitude.
# lenet@64 starts near ln(10)≈2.3; after 50 sgd steps it must be lower.
final = extra.get("final_loss")
assert final is not None and final < 2.0, \
    f"final_loss={final} — loss did not decrease over 50 steps"
print(f"trainloop_smoke: OK ({doc['value']} {doc['unit']}, "
      f"final_loss={final}, dispatches_per_step={dps}, "
      f"io.wait_ms={round(c['io/io.wait_ms'], 1)})")
EOF

# schema-check the BENCH json itself (MFU field + counter families)
python tools/trace_check.py "$OUT" || exit 1
echo "trainloop_smoke: whole-loop executor pipeline validates"
