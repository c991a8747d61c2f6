#!/bin/bash
# Tier-1 fleet smoke (CPU-only, no TPU): proves the three
# mxtpu.fleet acceptance claims end to end on a 2-replica CPU lenet:
#   (a) continuous batching is LIVE under load — requests admitted
#       while a dispatch is in flight carry the `slotted` servescope
#       mark in the mxtpu.events/1 stream, and
#       serving.slotted_admissions counts them;
#   (b) a draining hot-swap deploy (drain -> swap -> readmit, every
#       replica) drops or errors ZERO requests under concurrent load;
#   (c) a 2-replica spawned fleet behind the Router sustains a
#       serve_load ramp, emits a trace_check-valid BENCH json with a
#       populated extra.fleet section, replica N+1's warmup hits the
#       shared on-disk AOT compile cache, and perf_regress.py accepts
#       the artifact (both the real fleet-vs-fleet gates and the
#       metric-mismatch path vs a differently-sized fleet).
# Replica SCALING is a multi-core claim: on a multi-core host this
# script asserts fleet-2 beats fleet-1 outright; on a 1..3-core host
# (where two replicas time-slice one core and batch fission makes the
# fleet structurally slower) it asserts the fleet stays within budget
# of the single-replica baseline and explains why — see docs/serving.md.
set -u
cd "$(dirname "$0")/.." || exit 1

SMOKE_DIR=${MXTPU_FLEET_SMOKE_DIR:-/tmp/mxtpu_fleet_smoke}
rm -rf "$SMOKE_DIR"; mkdir -p "$SMOKE_DIR"
export JAX_PLATFORMS=cpu

# ---- part 1: continuous batching + zero-drop deploy (in-process) ----
echo "fleet_smoke: in-process 2-replica lenet — slotted admissions +"
echo "fleet_smoke: draining hot-swap under concurrent load"
MXTPU_FLEET_SMOKE_DIR="$SMOKE_DIR" \
timeout -k 10 900 python - <<'EOF' || exit 1
import json, os, threading, time, urllib.request

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import profiler as prof
from incubator_mxnet_tpu import servescope
from incubator_mxnet_tpu.fleet import CompileCache, ReplicaSet, Router
from incubator_mxnet_tpu.healthmon import events as hm_events
from incubator_mxnet_tpu.models import get_model

smoke_dir = os.environ["MXTPU_FLEET_SMOKE_DIR"]
events_path = os.path.join(smoke_dir, "inproc_events.jsonl")
servescope.enable()
hm_events.open_log(events_path, run_id="fleet-smoke-inproc", rank=0)


def factory(compile_cache=None):
    net = get_model("lenet", classes=10)
    net.initialize(init=mx.init.Xavier())
    return net.freeze(input_shape=(1, 28, 28), batch_buckets=(1, 4, 8),
                      compile_cache=compile_cache)


cache = CompileCache(os.path.join(smoke_dir, "inproc_cache"))
rset = ReplicaSet(factory, n=2, batcher="continuous", compile_cache=cache)
rset.start()
router = Router(rset, poll_interval_s=10.0)
host, port = router.start()
url = f"http://{host}:{port}/predict"
body = json.dumps({"data": np.zeros((1, 28, 28)).tolist()}).encode()

stop = threading.Event()
ok, failures = [], []


def client():
    while not stop.is_set():
        try:
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                doc = json.loads(r.read())
                (ok if r.status == 200 else failures).append(doc)
        except Exception as e:  # noqa: BLE001
            failures.append(repr(e))


threads = [threading.Thread(target=client) for _ in range(6)]
for t in threads:
    t.start()
time.sleep(1.0)                       # sustained load before the deploy
router.deploy(factory, compile_cache=cache, timeout=60.0)
time.sleep(0.5)                       # and after it
stop.set()
for t in threads:
    t.join()
router.stop()
rset.stop(drain=True)
hm_events.close_log()
servescope.disable()

c = prof.counters()
assert not failures, f"deploy dropped/errored requests: {failures[:3]}"
assert len(ok) > 50, f"load never ramped: {len(ok)} responses"
slotted = c.get("serving/serving.slotted_admissions", 0)
assert slotted > 0, "no mid-flight admissions under sustained load"
assert c.get("fleet/fleet.drains", 0) == 2, c
assert c.get("fleet/fleet.swaps", 0) == 2, c
assert c.get("fleet/fleet.readmits", 0) == 2, c
hits = c.get("fleet/fleet.compile_cache_hits", 0)
assert hits > 0, "replica/deploy warmups never hit the shared cache"

# the slotted mark must be visible PER REQUEST in the event stream
with open(events_path) as f:
    recs = [json.loads(ln) for ln in f if ln.strip()]
span_recs = [r for r in recs if r.get("name") == "serving.request"]
tagged = [r for r in span_recs
          if (r.get("args") or {}).get("slotted") is True]
assert tagged, "no serving.request event carries the slotted mark"
print(f"fleet_smoke: in-process OK — {len(ok)} responses, 0 drops, "
      f"{slotted} slotted admissions ({len(tagged)} tagged events), "
      f"2 drains/swaps/readmits, {hits} cache hits")
EOF

# the in-process event log must be a valid mxtpu.events/1 stream
python tools/trace_check.py "$SMOKE_DIR/inproc_events.jsonl" || exit 1

# ---- part 2: spawned 2-replica fleet ramp vs 1-replica baseline ----
echo "fleet_smoke: spawned-worker serve_load ramp (fleet 1 then fleet 2)"
FLEET1="$SMOKE_DIR/fleet1.json"
FLEET2="$SMOKE_DIR/fleet2.json"
CACHE="$SMOKE_DIR/aot_cache"

timeout -k 10 900 python tools/serve_load.py --fleet 1 \
  --ramp 4,8,16 --level-requests 96 --fleet-cache "$CACHE" \
  --out "$FLEET1" --events "$SMOKE_DIR/fleet1_events.jsonl" \
  > "$SMOKE_DIR/fleet1.log" 2>&1
rc=$?
if [ "$rc" != "0" ]; then
  echo "fleet_smoke: fleet-1 serve_load failed rc=$rc"
  tail -30 "$SMOKE_DIR/fleet1.log"; exit 1
fi
timeout -k 10 900 python tools/serve_load.py --fleet 2 \
  --ramp 4,8,16 --level-requests 96 --fleet-cache "$CACHE" \
  --out "$FLEET2" --events "$SMOKE_DIR/fleet2_events.jsonl" \
  > "$SMOKE_DIR/fleet2.log" 2>&1
rc=$?
if [ "$rc" != "0" ]; then
  echo "fleet_smoke: fleet-2 serve_load failed rc=$rc"
  tail -30 "$SMOKE_DIR/fleet2.log"; exit 1
fi

# both artifacts + both event logs must validate structurally
python tools/trace_check.py "$FLEET1" "$FLEET2" \
  "$SMOKE_DIR/fleet1_events.jsonl" "$SMOKE_DIR/fleet2_events.jsonl" \
  || exit 1

# fleet semantics: balanced dispatch, clean router accounting, shared
# cache hit on replica N+1's warmup, live continuous batching, and the
# core-aware throughput claim
python - "$FLEET1" "$FLEET2" <<'EOF' || exit 1
import json, os, sys

f1 = json.load(open(sys.argv[1]))
f2 = json.load(open(sys.argv[2]))
q1, q2 = f1["value"], f2["value"]
fl = (f2.get("extra") or {}).get("fleet") or {}
assert fl.get("replicas") == 2, f"extra.fleet broken: {fl}"
rows = fl["per_replica"]
assert all(r["requests"] > 0 for r in rows), \
    f"a replica never served: {rows}"
assert fl.get("routed_errors", 0) == 0, fl
assert fl.get("no_replica_available", 0) == 0, fl
cc = fl.get("compile_cache") or {}
assert cc.get("hits", 0) > 0, \
    f"replica N+1 warmup missed the shared AOT cache: {cc}"
sv = (f2.get("extra") or {}).get("serving") or {}
assert sv.get("slotted_admissions", 0) > 0, \
    "continuous batching idle: no slotted admissions in the fleet"
cores = os.cpu_count() or 1
if cores >= 4:
    assert q2 > q1, \
        f"{cores} cores but fleet-2 knee {q2} <= fleet-1 knee {q1}"
    print(f"fleet_smoke: fleet-2 out-scales fleet-1 "
          f"({q2:.0f} > {q1:.0f} qps at knee, {cores} cores)")
else:
    # two replicas time-slicing <4 cores cannot win (batch fission:
    # each replica sees half the arrival rate, so batches shrink and
    # per-batch overhead doubles) — assert the fleet machinery itself
    # costs a bounded amount instead of a throughput win it cannot
    # physically deliver here
    assert q2 >= 0.55 * q1, \
        f"fleet-2 knee {q2} < 55% of fleet-1 knee {q1}: routing " \
        f"overhead regression"
    print(f"fleet_smoke: {cores} core(s) — scaling unprovable here; "
          f"fleet-2 within budget ({q2:.0f} vs {q1:.0f} qps at knee)")
print(f"fleet_smoke: fleet artifacts OK — dispatch "
      f"{fl['dispatch_counts']}, imbalance "
      f"{fl['dispatch_imbalance']:.2f}, {cc.get('hits')} cache hits, "
      f"{sv.get('slotted_admissions')} slotted admissions")
EOF

# regression gates: fleet-vs-fleet exercises the real value/p99 gates;
# fleet-1 vs fleet-2 carry DIFFERENT metric names by design, so the
# both-sides contract must conclude "nothing comparable" (exit 0), not
# invent a 2x-replicas "regression"
python tools/perf_regress.py "$FLEET2" "$FLEET2" || {
  echo "fleet_smoke: perf_regress rejected fleet-2 vs itself"; exit 1; }
python tools/perf_regress.py "$FLEET1" "$FLEET2" || {
  echo "fleet_smoke: perf_regress must accept a fleet-size change as"
  echo "fleet_smoke: incomparable (distinct metric), not a regression"
  exit 1; }

# the renderer must be able to tell the story from the artifact alone
python tools/mxdiag.py fleet "$FLEET2" > "$SMOKE_DIR/mxdiag_fleet.txt" \
  || exit 1
grep -q "replica1" "$SMOKE_DIR/mxdiag_fleet.txt" || {
  echo "fleet_smoke: mxdiag fleet lost the replica table"; exit 1; }

echo "fleet_smoke: all fleet artifacts validate"
