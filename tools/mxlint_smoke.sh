#!/bin/bash
# mxlint smoke (CPU-only): the PR 14 acceptance gate.
#
# 1. static: `tools/mxlint.py --check` must exit 0 on the tree (zero
#    findings — every knob read routed/allowlisted, no counter drift,
#    never-raise modules clean), and the bad fixtures must still FIRE
#    (a linter that stopped seeing violations is worse than none).
# 2. strict-mode runtime: a 50-step CPU lenet bench under MXTPU_STRICT=1
#    completes with ZERO transfer-guard trips, ZERO steady-state
#    recompiles and ZERO donation violations, every steady dispatch
#    guarded, validated by trace_check's check_mxlint_extra.
# 3. renderers: `mxdiag.py lint` renders the findings report.
set -u
cd "$(dirname "$0")/.." || exit 1
export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}
OUT=${MXLINT_SMOKE_OUT:-/tmp/mxtpu_mxlint_smoke}
rm -rf "$OUT"; mkdir -p "$OUT"
fail() { echo "mxlint_smoke: FAIL: $*" >&2; exit 1; }

echo "== mxlint smoke: static gate =="
python tools/mxlint.py --check > "$OUT/lint.txt" 2>&1 \
  || { cat "$OUT/lint.txt"; fail "tree has mxlint findings"; }
grep -q "0 findings" "$OUT/lint.txt" || fail "gate output malformed"

# the linter must still catch the bad fixtures (tier-1 runs the full
# matrix; the smoke spot-checks one rule end-to-end through the CLI)
mkdir -p "$OUT/pkg/incubator_mxnet_tpu"
cp tests/fixtures/mxlint/raw_env_read_bad.py "$OUT/pkg/incubator_mxnet_tpu/"
python tools/mxlint.py --check "$OUT/pkg" > "$OUT/fixture.txt" 2>&1
[ $? -eq 1 ] || fail "bad fixture not caught by the CLI"
grep -q "raw-env-read" "$OUT/fixture.txt" || fail "rule id missing"

echo "== mxlint smoke: strict-mode lenet (MXTPU_STRICT=1) =="
MXTPU_STRICT=1 BENCH_MODEL=lenet BENCH_STEPS=50 BENCH_DTYPE=float32 \
  timeout 600 python bench.py > "$OUT/bench_raw.txt" 2> "$OUT/bench.err" \
  || { tail -5 "$OUT/bench.err"; fail "strict bench run failed"; }
tail -1 "$OUT/bench_raw.txt" > "$OUT/bench.json"

python - "$OUT/bench.json" <<'EOF' || exit 1
import json, sys
doc = json.load(open(sys.argv[1]))
mx = doc.get("extra", {}).get("mxlint")
assert isinstance(mx, dict), f"no extra.mxlint in strict bench: {doc.keys()}"
assert mx.get("strict") is True, mx
assert mx["transfer_guard_trips"] == 0, f"host syncs leaked into the steady loop: {mx}"
assert mx["recompiles"] == 0, f"steady-state recompiles: {mx['recompiled_programs']}"
assert mx["donation_violations"] == 0, mx
assert mx["findings"] == 0, mx
assert mx["guarded_dispatches"] >= 50, f"steady loop not guarded: {mx}"
assert doc.get("value", 0) > 0, "no throughput measured"
print(f"strict lenet OK: {mx['guarded_dispatches']} guarded dispatches, "
      f"0 findings, {doc['value']} img/s")
EOF

# the artifact must validate under trace_check (incl. check_mxlint_extra)
python tools/trace_check.py "$OUT/bench.json" || fail "trace_check rejects strict artifact"

echo "== mxlint smoke: renderers =="
python tools/mxdiag.py lint > "$OUT/mxdiag_lint.txt" 2>&1 \
  || fail "mxdiag lint nonzero on a clean tree"
grep -q "tree is clean" "$OUT/mxdiag_lint.txt" || fail "mxdiag lint output malformed"

echo "mxlint_smoke: OK"
