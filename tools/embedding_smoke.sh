#!/bin/bash
# Tier-1 embedding-subsystem smoke: 50 recsys (DLRM) steps ON CPU over
# a 4-fake-device model-axis mesh (BENCH_MESH=mp4) through the sharded
# one-jit executor with the row-sparse AdaGrad path, then assert the
# subsystem's whole contract from the one BENCH json:
#   learning   — final_loss < first_loss (the label rides the table
#                rows, so a flat loss means the lookup/update path is
#                broken, not the model);
#   sharding   — extra.embedding.table_bytes_per_device strictly below
#                table_bytes_logical (the vocab axis really split) and
#                extra.sharding shows model-sharded params on an mp
#                mesh in auto mode;
#   dedup      — a real dedup rate in (0, 1] with rows_touched <= ids
#                (zipf ids make it ~0.9+; 0 means the unique/inverse
#                path fell out of the program);
#   comms      — commscope attributes at least one steady-train
#                collective to the mp axis (the sharded lookup's
#                all-reduce / all-to-all spelling), and the resharding
#                detector stays QUIET (0 flagged) — the annotated
#                layout matches the computation;
#   schema     — the artifact validates under tools/trace_check.py
#                (extra.embedding + counter families included).
# No TPU — safe anywhere, cheap enough for CI.
set -u
cd "$(dirname "$0")/.." || exit 1

OUT=${1:-/tmp/mxtpu_embedding_smoke.json}
LOG=/tmp/mxtpu_embedding_smoke.log
: > "$LOG"

echo "embedding_smoke: 50-step recsys run on a CPU mp4 mesh"
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  BENCH_MODEL=recsys BENCH_MESH=mp4 BENCH_BATCH=256 BENCH_STEPS=50 \
  BENCH_DTYPE=float32 BENCH_TRACE=0 \
  timeout -k 10 900 python bench.py > "$OUT" 2>> "$LOG"
rc=$?
if [ "$rc" != "0" ]; then
  echo "embedding_smoke: recsys bench failed rc=$rc"; tail -30 "$LOG"
  exit 1
fi

python - "$OUT" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
if doc.get("error"):
    sys.exit(f"recsys bench reported error: {doc['error']}")
ex = doc.get("extra") or {}

# learning: the synthetic labels are a function of the table rows used,
# so the loss only moves if lookup, backward, and row update all work
fl, ll = ex.get("first_loss"), ex.get("final_loss")
assert isinstance(fl, (int, float)) and isinstance(ll, (int, float)), \
    f"first/final loss missing: {fl} {ll}"
assert ll < fl, f"loss did not decrease: first {fl} -> final {ll}"

# embedding census: the table really lives split on the vocab axis
em = ex.get("embedding")
assert isinstance(em, dict), "no extra.embedding section"
assert em["tables"] > 0, em
assert 0 < em["table_bytes_per_device"] < em["table_bytes_logical"], \
    (f"table not sharded: {em['table_bytes_per_device']} B/device vs "
     f"{em['table_bytes_logical']} B replicated")
assert 0.0 < em["dedup_rate"] <= 1.0, f"dedup rate: {em['dedup_rate']}"
assert em["rows_touched_per_step"] <= em["ids_per_step"], em

# sharding summary: auto mode on an mp mesh, model-sharded params > 0
sh = ex.get("sharding")
assert isinstance(sh, dict), "no extra.sharding section"
assert sh.get("mesh", {}).get("mp") == 4, sh
assert sh.get("params_model_sharded", 0) > 0, sh

# commscope: the sharded lookup's collective is attributed to the mp
# axis somewhere in the captured programs, and the resharding detector
# is quiet — the annotated layout matches what XLA compiled
cs = ex.get("commscope")
assert isinstance(cs, dict) and cs.get("programs"), "no commscope data"
mp_colls = [c for p in cs["programs"] for c in (p.get("collectives") or [])
            if c.get("axis") == "mp"]
assert mp_colls, "no collective attributed to the mp axis"
flagged = sum(p.get("resharding_collectives", 0) for p in cs["programs"])
assert flagged == 0, f"resharding detector flagged {flagged} collective(s)"

print(f"embedding_smoke: OK (loss {fl} -> {ll}; "
      f"{em['table_bytes_per_device']} B/device of "
      f"{em['table_bytes_logical']} B tables; dedup "
      f"{em['dedup_rate']:.3f}; {len(mp_colls)} mp-axis collective "
      f"kind(s); resharding 0)")
EOF

# schema-check the artifact (extra.embedding + counter families)
python tools/trace_check.py "$OUT" || exit 1

echo "embedding_smoke: OK"
