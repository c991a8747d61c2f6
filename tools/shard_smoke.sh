#!/bin/bash
# Tier-1 sharding smoke: the CPU-mesh matrix on 4 FAKE host devices
# (XLA_FLAGS=--xla_force_host_platform_device_count=4 — no TPU).
# Four 50-step lenet bench runs:
#   baseline  (no mesh)          -> the reference loss
#   dp4       BENCH_MESH=dp4     -> pure data parallel
#   dp2mp2    BENCH_MESH=dp2mp2  -> 2x2 (dp, mp): Dense kernels on mp
#   fsdp4     BENCH_MESH=fsdp4   -> zero-style param+state sharding
# and from the BENCH jsons assert that
#   * every sharded run's final loss matches the unsharded run within
#     tolerance (dp/mp layouts are bit-identical on XLA:CPU; fsdp is
#     ~1 ulp/step from collective reduction order),
#   * the sharding.* counter family and extra.sharding are present and
#     describe the requested mesh (trace_check-schema-validated),
#   * dp2mp2 actually put params on the mp axis,
#   * FSDP per-device param+state bytes < the replicated runs' (the
#     memory reduction is the point of the mode).
set -u
cd "$(dirname "$0")/.." || exit 1

OUTDIR=${1:-/tmp/mxtpu_shard_smoke}
mkdir -p "$OUTDIR"
LOG="$OUTDIR/shard_smoke.log"
: > "$LOG"

run_one() {
  name=$1; mesh=$2
  echo "shard_smoke: $name (BENCH_MESH='${mesh}')"
  env XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
    BENCH_MODEL=lenet BENCH_BATCH=64 BENCH_STEPS=50 BENCH_DTYPE=float32 \
    BENCH_MESH="$mesh" BENCH_K1_CONTROL=0 BENCH_PERFSCOPE_PROBE=2 \
    BENCH_TRACE_FILE="$OUTDIR/trace_$name.json" \
    timeout -k 10 900 python bench.py > "$OUTDIR/bench_$name.json" 2>> "$LOG"
  rc=$?
  if [ "$rc" != "0" ]; then
    echo "shard_smoke: bench ($name) failed rc=$rc"; tail -30 "$LOG"
    exit 1
  fi
}

run_one baseline ""
run_one dp4 dp4
run_one dp2mp2 dp2mp2
run_one fsdp4 fsdp4

python - "$OUTDIR" <<'EOF' || exit 1
import json, os, sys
outdir = sys.argv[1]
docs = {n: json.load(open(os.path.join(outdir, f"bench_{n}.json")))
        for n in ("baseline", "dp4", "dp2mp2", "fsdp4")}
for n, d in docs.items():
    assert not d.get("error"), f"{n}: bench reported error: {d.get('error')}"
ref = docs["baseline"]["extra"]["final_loss"]
for n in ("dp4", "dp2mp2", "fsdp4"):
    d = docs[n]
    loss = d["extra"]["final_loss"]
    # bench rounds final_loss to 4 decimals; dp/mp are bit-identical and
    # fsdp drifts ~1 ulp/step, so 5e-3 is generous while still catching
    # any real divergence (wrong batch split, double-applied grads, ...)
    assert abs(loss - ref) < 5e-3, \
        f"{n}: final_loss {loss} vs unsharded {ref} — sharded math diverged"
    sh = d["extra"].get("sharding")
    assert sh, f"{n}: no extra.sharding in BENCH json"
    c = d["extra"]["counters"]
    for fam in ("sharding/sharding.resolves",
                "sharding/sharding.mesh_devices",
                "sharding/sharding.params_total",
                "sharding/sharding.param_bytes_per_device"):
        assert fam in c, f"{n}: counter {fam} missing from BENCH json"
    assert c["sharding/sharding.mesh_devices"] == 4, \
        f"{n}: mesh_devices={c['sharding/sharding.mesh_devices']}"

assert docs["dp4"]["extra"]["sharding"]["mesh"] == {"dp": 4}
assert docs["dp2mp2"]["extra"]["sharding"]["mesh"] == {"dp": 2, "mp": 2}
n_mp = docs["dp2mp2"]["extra"]["sharding"]["params_model_sharded"]
assert n_mp > 0, "dp2mp2: no params landed on the mp axis"

fsdp = docs["fsdp4"]["extra"]["sharding"]
repl = docs["dp4"]["extra"]["sharding"]
assert fsdp["fsdp"] and fsdp["params_data_sharded"] > 0, fsdp
for key in ("param_bytes_per_device", "state_bytes_per_device"):
    assert fsdp[key] < repl[key], \
        (f"fsdp {key}={fsdp[key]} not below replicated {repl[key]} — "
         f"FSDP saved no memory")
red = repl["param_bytes_per_device"] / fsdp["param_bytes_per_device"]
print(f"shard_smoke: OK (loss ref={ref}, dp4/dp2mp2/fsdp4 within tol; "
      f"{n_mp} params on mp; fsdp per-device param bytes "
      f"{fsdp['param_bytes_per_device']} vs {repl['param_bytes_per_device']}"
      f" = {red:.2f}x reduction)")
EOF

# schema-check every artifact (sharding counter family + extra.sharding)
python tools/trace_check.py "$OUTDIR"/bench_*.json || exit 1
echo "shard_smoke: CPU-mesh matrix validates"
