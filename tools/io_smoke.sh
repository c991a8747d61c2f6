#!/bin/bash
# Tier-1 ingest-pipeline smoke: lenet ON CPU through the whole-loop
# executor TWICE with an injected 700 ms/batch decode cost
# (BENCH_IO_SLOW_MS — a sleep in the decode pool's transform hook),
# then assert the pipelining claim from the two BENCH jsons:
#   serial    — io_workers=1, depth=1: decode wall lands on the
#               consumer's critical path, io.wait_ms is large and the
#               devicescope window shows input starvation whose split
#               is decode-dominated (mxdiag io must render the
#               "raise io_workers" triage line from it);
#   pipelined — io_workers=4, depth=2: the pool hides the same decode
#               cost behind compute, so io.wait_ms drops, throughput
#               rises, and the measured overlap inequality holds:
#               the pipelined run's whole steady WALL is smaller than
#               the serial run's cumulative decode+put attribution
#               (stages truly overlapped — they did not just move).
#   both runs — extra.io validates under trace_check (schema +
#               counter families), mxdiag io renders, and
#               perf_regress.py accepts the pair (the knob diff must
#               surface as context, not break the comparison).
# No TPU — safe anywhere, cheap enough for CI.
set -u
cd "$(dirname "$0")/.." || exit 1

OUT_SER=${1:-/tmp/mxtpu_io_smoke_serial.json}
OUT_PIPE=/tmp/mxtpu_io_smoke_pipelined.json
LOG=/tmp/mxtpu_io_smoke.log
: > "$LOG"

run_bench() {  # $1 = io_workers, $2 = prefetch depth, $3 = out json
  JAX_PLATFORMS=cpu BENCH_MODEL=lenet BENCH_BATCH=64 BENCH_STEPS=24 \
    BENCH_DTYPE=float32 BENCH_LOOP_CHUNK=4 BENCH_K1_CONTROL=0 \
    BENCH_TRACE=0 BENCH_DEVICESCOPE=1 \
    BENCH_IO_SLOW_MS=700 \
    BENCH_IO_WORKERS="$1" BENCH_PREFETCH_DEPTH="$2" \
    timeout -k 10 900 python bench.py > "$3" 2>> "$LOG"
}

echo "io_smoke: serial run (io_workers=1, depth=1, slow decode 700 ms)"
run_bench 1 1 "$OUT_SER"
rc=$?
if [ "$rc" != "0" ]; then
  echo "io_smoke: serial bench failed rc=$rc"; tail -30 "$LOG"; exit 1
fi

echo "io_smoke: pipelined run (io_workers=4, depth=2, same decode)"
run_bench 4 2 "$OUT_PIPE"
rc=$?
if [ "$rc" != "0" ]; then
  echo "io_smoke: pipelined bench failed rc=$rc"; tail -30 "$LOG"; exit 1
fi

python - "$OUT_SER" "$OUT_PIPE" <<'EOF' || exit 1
import json, sys
ser = json.load(open(sys.argv[1]))
pipe = json.load(open(sys.argv[2]))
for tag, doc in (("serial", ser), ("pipelined", pipe)):
    if doc.get("error"):
        sys.exit(f"{tag} bench reported error: {doc['error']}")
    io = (doc.get("extra") or {}).get("io")
    assert isinstance(io, dict), f"{tag}: no extra.io section"
s_io = ser["extra"]["io"]; p_io = pipe["extra"]["io"]
assert s_io["workers"] == 1 and s_io["depth"] == 1, s_io
assert p_io["workers"] == 4 and p_io["depth"] == 2, p_io
assert s_io["slow_ms"] == 700.0 and p_io["slow_ms"] == 700.0, \
    "injected decode cost missing from extra.io"
# the decode pool must CUT the consumer's empty-buffer wait: with one
# worker the 4x700 ms chunk decode serializes in front of every pop;
# with four it overlaps compute. 0.6 leaves CI-box noise headroom.
assert p_io["wait_ms"] < 0.6 * s_io["wait_ms"], \
    (f"pipelining did not cut the consumer wait: serial "
     f"{s_io['wait_ms']:.0f} ms vs pipelined {p_io['wait_ms']:.0f} ms")
# measured overlap inequality: the pipelined steady WALL must be
# smaller than the serial run's decode+put attribution — overlapped
# work, not relocated work. Walls derive from the headline throughput.
def wall_ms(doc):
    ex = doc["extra"]
    return ex["batch"] * ex["steps"] / doc["value"] * 1e3
assert wall_ms(pipe) < s_io["decode_ms"] + s_io["put_ms"], \
    (f"no overlap win: pipelined wall {wall_ms(pipe):.0f} ms vs serial "
     f"decode+put {s_io['decode_ms'] + s_io['put_ms']:.0f} ms")
# and the headline: same model, same injected cost, higher throughput
assert pipe["value"] > ser["value"], \
    f"pipelined {pipe['value']} <= serial {ser['value']} samples/s"
# devicescope attribution: the serial run starves on decode, and the
# split must say so (the signal autotune's prune_plan promotes
# io_workers on)
ds = (ser.get("extra") or {}).get("devicescope") or {}
split = (ds.get("gaps") or {}).get("input_starved_split")
assert isinstance(split, dict), "serial run has no input_starved_split"
assert split.get("dominant") == "decode", \
    f"serial starvation not decode-dominated: {split}"
# busy fraction: the pipelined chip does proportionally more work
sb = ds.get("busy_fraction")
pb = ((pipe.get("extra") or {}).get("devicescope") or {}).get(
    "busy_fraction")
assert sb is not None and pb is not None, "busy_fraction missing"
assert pb > sb, f"pipelined busy {pb} <= serial busy {sb}"
print(f"io_smoke: OK (serial {ser['value']} -> pipelined "
      f"{pipe['value']} samples/s; wait {s_io['wait_ms']:.0f} -> "
      f"{p_io['wait_ms']:.0f} ms; serial starve split {split})")
EOF

# schema-check both BENCH jsons (extra.io + counter families)
python tools/trace_check.py "$OUT_SER" "$OUT_PIPE" || exit 1

# the renderer must handle both shapes, and the serial run's triage
# line must point at the decode pool, not at prefetch depth
python tools/mxdiag.py io "$OUT_PIPE" > /dev/null \
  || { echo "io_smoke: mxdiag io failed on pipelined run"; exit 1; }
IODIAG=$(python tools/mxdiag.py io "$OUT_SER") \
  || { echo "io_smoke: mxdiag io failed on serial run"; exit 1; }
echo "$IODIAG" | grep -q "raise io_workers" \
  || { echo "io_smoke: serial triage line missing 'raise io_workers':";
       echo "$IODIAG"; exit 1; }

# perf_regress must accept the pair; the io_workers diff is CONTEXT
REGOUT=$(python tools/perf_regress.py --threshold 0.9 \
           --busy-threshold 0.9 "$OUT_PIPE" "$OUT_SER" 2>&1)
rc=$?
if [ "$rc" != "0" ]; then
  # serial IS slower — a flagged regression is acceptable, a crash or
  # schema rejection is not
  echo "$REGOUT" | grep -qi "regress" \
    || { echo "io_smoke: perf_regress rejected the pair:";
         echo "$REGOUT"; exit 1; }
fi

echo "io_smoke: OK"
