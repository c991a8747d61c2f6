#!/usr/bin/env python
"""BENCH regression gate: compare bench artifacts with noise-aware
thresholds and an environment-failure filter.

Why this exists: a perf history can mix real measurements with
artifacts of a backend that never answered (value 0.0). A naive
comparator reads those as 100% regressions and either cries wolf or — worse — adopts 0 img/s as a
baseline every later run "beats". This tool:

* **skips env-failure artifacts** — anything carrying
  ``"status": "env_failure"`` (tools/serve_load.py's dead-server
  artifact), an ``error`` field, a null ``parsed`` wrapper, or a non-positive
  value. They describe the environment, not the code;
* **compares the metrics that matter** — headline throughput
  (``value`` — for ``tools/serve_load.py`` sweeps that IS the QPS at
  the saturation knee), ``extra.mfu`` (ROADMAP item 1's regression
  metric), serving ``p99_ms`` (at the knee for serve_load artifacts,
  with the knee's position reported as context — on a discrete ramp it
  moves in whole levels, so a shift alone is a note, not a verdict),
  the per-step collective payload
  (``extra.commscope.step.bytes`` — a LAYOUT regression: a new
  accidental reshard inflates in-program collective bytes even when
  the CPU-bench wall time barely moves), and the MEASURED device busy
  fraction (``extra.devicescope.busy_fraction`` — the ground-truth
  utilization a devicescope capture window measured; a drop means the
  chip got idler even if wall-clock noise hides it) — relative, per
  metric, only when both sides carry the number. The busy gate follows
  the same both-sides contract as the collective-bytes gate: a run
  whose baseline carried no devicescope window (the 0→nonzero window
  transition) is noted, never indicted;
* **is noise-aware** — in trajectory mode (``--dir``) the baseline is
  the MEDIAN of all usable prior artifacts and the effective threshold
  is ``max(--threshold, --noise-mult × observed relative spread)``, so
  a comparison across a noisy history demands a drop larger than the
  history's own scatter before it indicts a PR.

Usage:
    python tools/perf_regress.py BASELINE.json CANDIDATE.json
    python tools/perf_regress.py --dir REPO_DIR [--candidate FILE]

Accepted artifact shapes: direct tools/serve_load.py output
(``{"metric", "value", ...}``) and the driver wrapper
(``{"n", "cmd", "rc", "parsed": {...}}``).

Exit status: 0 = no regression (or nothing comparable — every baseline
was an env failure), 1 = regression, 2 = usage/IO error.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

__all__ = ["load_artifact", "compare", "trajectory", "main"]

DEFAULT_THRESHOLD = 0.05       # 5% relative drop on value / MFU
DEFAULT_P99_THRESHOLD = 0.25   # 25% relative increase on p99
# collective payload is DETERMINISTIC for a fixed model+layout (static
# HLO inventory, no timing noise), so the gate is tight: a real layout
# change moves it by integer factors, measurement scatter by zero
DEFAULT_COLL_THRESHOLD = 0.10  # 10% relative increase on bytes/step
# measured device busy fraction (devicescope window): a >10% relative
# drop means the chip spent measurably more of the window idle
DEFAULT_BUSY_THRESHOLD = 0.10
# measured peak memory (memscope watermark ring, static footprint
# fallback): >10% growth is a memory regression — the number that eats
# the batch headroom and ends runs in RESOURCE_EXHAUSTED
DEFAULT_PEAK_THRESHOLD = 0.10
# dedup rate (extra.embedding.dedup_rate, recsys artifacts): for a
# fixed record stream the id distribution is deterministic, so like the
# collective inventory this has no timing scatter — and a drop is a
# silent comms blowup (the sharded gather's payload scales with
# 1 - dedup_rate)
DEFAULT_DEDUP_THRESHOLD = 0.10
DEFAULT_NOISE_MULT = 2.0


def load_artifact(path):
    """Load one BENCH artifact → (record | None, skip_reason | None).

    The record is {path, metric, value, unit, mfu, p99_ms}; None means
    the artifact is unusable as a perf number (the reason says why —
    env failure, error, unparseable)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return None, f"unreadable/invalid JSON ({e})"
    if not isinstance(doc, dict):
        return None, "not a JSON object"
    if "parsed" in doc and "metric" not in doc:
        # driver wrapper: the bench's own JSON line lives under `parsed`
        doc = doc["parsed"]
        if not isinstance(doc, dict):
            return None, "driver wrapper with no parsed bench line " \
                         "(the run produced no usable output)"
    if doc.get("status") == "env_failure":
        return None, f"env_failure: {str(doc.get('error', ''))[:80]}"
    if doc.get("error"):
        # pre-perfscope artifacts (BENCH_r02–r05) carry only `error`;
        # value 0 + error is an environment/run failure either way
        return None, f"errored run: {str(doc['error'])[:80]}"
    value = doc.get("value")
    if not isinstance(value, (int, float)) or isinstance(value, bool) \
            or value <= 0:
        return None, f"non-positive value {value!r}"
    extra = doc.get("extra") or {}
    serving = extra.get("serving") or {}
    commscope = extra.get("commscope") or {}
    step = commscope.get("step") if isinstance(commscope.get("step"),
                                               dict) else {}
    coll = step.get("bytes")
    rec = {
        "path": path,
        "metric": doc.get("metric"),
        "value": float(value),
        "unit": doc.get("unit"),
        "mfu": extra.get("mfu") if isinstance(extra.get("mfu"),
                                              (int, float)) else None,
        "p99_ms": serving.get("p99_ms") if isinstance(
            serving.get("p99_ms"), (int, float)) else None,
        # per-step in-program collective payload (commscope static-HLO
        # inventory of the steady train program) — the layout-regression
        # metric; None when the run carried no commscope step summary
        "coll_bytes": float(coll) if isinstance(coll, (int, float))
                      and not isinstance(coll, bool) else None,
        "resharding": step.get("resharding_collectives")
                      if isinstance(step.get("resharding_collectives"),
                                    int) else None,
    }
    # measured device busy fraction from a devicescope capture window —
    # None when the run carried no window (gate skipped: both-sides
    # contract, same as the commscope bytes gate)
    dsc = extra.get("devicescope") or {}
    bf = dsc.get("busy_fraction") if isinstance(dsc, dict) else None
    rec["busy_fraction"] = (float(bf)
                            if isinstance(bf, (int, float))
                            and not isinstance(bf, bool) else None)
    # measured peak memory from memscope's watermark ring (host RSS on
    # backends whose devices report no allocator stats), falling back
    # to the largest static per-program footprint; None when the run
    # didn't arm memscope (gate skipped: both-sides contract)
    msc = extra.get("memscope") or {}
    peak, src = None, None
    wm = msc.get("watermarks") if isinstance(msc, dict) else None
    for sect in ("device", "host_rss"):
        blk = (wm or {}).get(sect) if isinstance(wm, dict) else None
        pv = blk.get("peak") if isinstance(blk, dict) else None
        if isinstance(pv, (int, float)) and not isinstance(pv, bool) \
                and pv > 0:
            peak, src = float(pv), f"watermark {sect}"
            break
    if peak is None and isinstance(msc, dict):
        static = [p.get("peak_bytes") for p in (msc.get("programs") or [])
                  if isinstance(p, dict)
                  and isinstance(p.get("peak_bytes"), (int, float))
                  and not isinstance(p.get("peak_bytes"), bool)
                  and p["peak_bytes"] > 0]
        if static:
            peak, src = float(max(static)), "static footprint"
    rec["peak_bytes"] = peak
    rec["peak_source"] = src
    # serve_load sweep: the saturation knee (tools/serve_load.py). The
    # real gates are value (= QPS at the knee) and p99_ms (= p99 at the
    # knee, already in extra.serving); the knee's position itself is
    # reported as context — on a discrete ramp it can only move in
    # whole levels, so wobble is a note, never an indictment on its own
    sl = extra.get("serve_load") or {}
    kc = sl.get("knee_concurrency") if isinstance(sl, dict) else None
    rec["knee_concurrency"] = (int(kc) if isinstance(kc, int)
                               and not isinstance(kc, bool) else None)
    # embedding dedup rate (recsys artifacts) — None when the run
    # carried no extra.embedding (gate skipped: both-sides contract)
    emb = extra.get("embedding") or {}
    dr = emb.get("dedup_rate") if isinstance(emb, dict) else None
    rec["dedup_rate"] = (float(dr) if isinstance(dr, (int, float))
                         and not isinstance(dr, bool) else None)
    # resilience accounting (extra.resilience): a RECOVERED run's BENCH
    # is USABLE — the measured throughput is real — but the recovery
    # cost (steps lost to rollbacks) must be reported, never hidden;
    # compare() notes it alongside the perf verdicts
    rx = extra.get("resilience") or {}
    rv = rx.get("recoveries_total") if isinstance(rx, dict) else None
    rec["recoveries"] = (int(rv) if isinstance(rv, (int, float))
                         and not isinstance(rv, bool) else None)
    sl_tot = rx.get("steps_lost_total") if isinstance(rx, dict) else None
    rec["steps_lost"] = (int(sl_tot)
                         if isinstance(sl_tot, (int, float))
                         and not isinstance(sl_tot, bool) else None)
    # fleetscope trace-join rate (extra.fleetscope): observability
    # coverage, NOT performance — a drop means spans stopped joining
    # (sampling change, a propagation break), so compare() reports it
    # as context under the both-sides contract, never as a perf verdict
    fsc = extra.get("fleetscope") or {}
    jr = fsc.get("join_rate") if isinstance(fsc, dict) else None
    rec["trace_join_rate"] = (float(jr)
                              if isinstance(jr, (int, float))
                              and not isinstance(jr, bool) else None)
    return rec, None


def _rel_spread(values):
    """Max relative deviation from the median — the trajectory's own
    noise band."""
    if len(values) < 2:
        return 0.0
    med = sorted(values)[len(values) // 2]
    if med <= 0:
        return 0.0
    return max(abs(v - med) / med for v in values)


def compare(baseline, candidate, threshold=DEFAULT_THRESHOLD,
            p99_threshold=DEFAULT_P99_THRESHOLD, noise=0.0,
            noise_mult=DEFAULT_NOISE_MULT,
            coll_threshold=DEFAULT_COLL_THRESHOLD,
            busy_threshold=DEFAULT_BUSY_THRESHOLD,
            peak_threshold=DEFAULT_PEAK_THRESHOLD,
            dedup_threshold=DEFAULT_DEDUP_THRESHOLD):
    """Compare two loaded records → (regressions, notes): lists of
    human-readable strings. Lower-is-worse metrics (value, mfu) regress
    on a relative DROP beyond the effective threshold; p99 and the
    per-step collective payload regress on a relative INCREASE — with
    collectives appearing where the baseline had NONE always flagged
    (0 → anything is the accidental-reshard signature, and a relative
    threshold on a zero baseline would wave it through)."""
    regressions, notes = [], []
    if baseline["metric"] != candidate["metric"]:
        notes.append(f"metric mismatch ({baseline['metric']!r} vs "
                     f"{candidate['metric']!r}) — nothing comparable")
        return regressions, notes
    eff = max(threshold, noise_mult * noise)
    if noise:
        notes.append(f"noise band {noise:.1%} -> effective threshold "
                     f"{eff:.1%}")
    for key, label in (("value", f"{candidate['unit'] or 'value'}"),
                       ("mfu", "MFU")):
        b, c = baseline.get(key), candidate.get(key)
        if b is None or c is None or b <= 0:
            continue
        drop = (b - c) / b
        line = (f"{label}: {b:.4g} -> {c:.4g} "
                f"({-drop:+.2%} vs threshold -{eff:.1%})")
        if drop > eff:
            regressions.append("REGRESSION " + line)
        else:
            notes.append("ok " + line)
    b99, c99 = baseline.get("p99_ms"), candidate.get("p99_ms")
    if b99 and c99 and b99 > 0:
        rise = (c99 - b99) / b99
        eff99 = max(p99_threshold, noise_mult * noise)
        line = (f"p99_ms: {b99:.4g} -> {c99:.4g} "
                f"({rise:+.2%} vs threshold +{eff99:.1%})")
        if rise > eff99:
            regressions.append("REGRESSION " + line)
        else:
            notes.append("ok " + line)
    bcb, ccb = baseline.get("coll_bytes"), candidate.get("coll_bytes")
    if bcb is not None and ccb is not None:
        if bcb <= 0:
            if ccb > 0:
                regressions.append(
                    f"REGRESSION collective bytes/step: 0 -> {ccb:.0f} "
                    f"(in-program collectives appeared where the "
                    f"baseline layout had none — accidental reshard?)")
            else:
                notes.append("ok collective bytes/step: 0 -> 0")
        else:
            rise = (ccb - bcb) / bcb
            line = (f"collective bytes/step: {bcb:.0f} -> {ccb:.0f} "
                    f"({rise:+.2%} vs threshold +{coll_threshold:.1%})")
            # no noise widening: the static inventory has no scatter
            if rise > coll_threshold:
                regressions.append("REGRESSION " + line)
            else:
                notes.append("ok " + line)
    bbf, cbf = baseline.get("busy_fraction"), candidate.get("busy_fraction")
    if bbf is not None and cbf is not None and bbf > 0:
        drop = (bbf - cbf) / bbf
        effbf = max(busy_threshold, noise_mult * noise)
        line = (f"busy fraction: {bbf:.4f} -> {cbf:.4f} "
                f"({-drop:+.2%} vs threshold -{effbf:.1%})")
        if drop > effbf:
            regressions.append(
                "REGRESSION " + line + " (the chip measurably idler — "
                "see mxdiag.py device for the gap taxonomy)")
        else:
            notes.append("ok " + line)
    elif (bbf is None) != (cbf is None):
        # 0→nonzero (or nonzero→0) window transition: only one side ran
        # a devicescope capture window — there is no measured pair to
        # gate on, and inventing one would indict the act of measuring
        side = "candidate" if bbf is None else "baseline"
        notes.append(f"note: only the {side} carries a devicescope "
                     f"busy fraction — busy gate skipped (needs a "
                     f"window on both sides)")
    bpk, cpk = baseline.get("peak_bytes"), candidate.get("peak_bytes")
    if bpk is not None and cpk is not None and bpk > 0:
        if baseline.get("peak_source") != candidate.get("peak_source"):
            # a watermark peak and a static footprint are different
            # instruments — comparing them would manufacture a verdict
            notes.append(
                f"note: peak memory sources differ "
                f"({baseline.get('peak_source')} vs "
                f"{candidate.get('peak_source')}) — peak gate skipped "
                f"(needs the same instrument on both sides)")
        else:
            rise = (cpk - bpk) / bpk
            line = (f"peak memory ({candidate.get('peak_source')}): "
                    f"{bpk:.4g} -> {cpk:.4g} B "
                    f"({rise:+.2%} vs threshold +{peak_threshold:.1%})")
            if rise > peak_threshold:
                regressions.append(
                    "REGRESSION " + line + " (the run got hungrier — "
                    "see mxdiag.py mem for the footprint table)")
            else:
                notes.append("ok " + line)
    elif (bpk is None) != (cpk is None):
        # 0→nonzero memscope transition: only one side armed memscope —
        # a note, never an indictment (both-sides contract, same as the
        # devicescope busy gate)
        side = "candidate" if bpk is None else "baseline"
        notes.append(f"note: only the {side} carries a memscope peak — "
                     f"peak-memory gate skipped (needs memscope armed "
                     f"on both sides)")
    bkc, ckc = baseline.get("knee_concurrency"), \
        candidate.get("knee_concurrency")
    if bkc is not None and ckc is not None:
        if ckc < bkc:
            notes.append(f"note: saturation knee moved down "
                         f"({bkc} -> {ckc} clients) — the server "
                         f"saturates earlier; the QPS/p99-at-knee gates "
                         f"above carry the verdict")
        elif ckc > bkc:
            notes.append(f"note: saturation knee moved up "
                         f"({bkc} -> {ckc} clients)")
        else:
            notes.append(f"ok saturation knee: {bkc} clients (unchanged)")
    elif (bkc is None) != (ckc is None):
        side = "candidate" if bkc is None else "baseline"
        notes.append(f"note: only the {side} carries a serve_load knee "
                     f"— knee context skipped (needs a sweep on both "
                     f"sides)")
    # fleetscope trace-join rate: observability COVERAGE context, never
    # a perf verdict — the QPS/p99 gates above own the perf claim, this
    # says whether the cross-process spans behind them still join
    bjr, cjr = baseline.get("trace_join_rate"), \
        candidate.get("trace_join_rate")
    if bjr is not None and cjr is not None:
        if cjr < bjr - 0.05:
            notes.append(f"note: fleetscope trace-join rate dropped "
                         f"({bjr:.1%} -> {cjr:.1%}) — spans stopped "
                         f"joining (sampling change or a propagation "
                         f"break); coverage context, not a perf verdict")
        else:
            notes.append(f"ok fleetscope trace-join rate: {cjr:.1%} "
                         f"(baseline {bjr:.1%})")
    elif (bjr is None) != (cjr is None):
        side = "candidate" if bjr is None else "baseline"
        notes.append(f"note: only the {side} carries a fleetscope "
                     f"join rate — trace-coverage context skipped "
                     f"(needs fleetscope armed on both sides)")
    bdr, cdr = baseline.get("dedup_rate"), candidate.get("dedup_rate")
    if bdr is not None and cdr is not None and bdr > 0:
        drop = (bdr - cdr) / bdr
        # no noise widening: for a fixed record stream the dedup rate is
        # deterministic — any drop is a code change, not run-to-run jitter
        line = (f"dedup rate: {bdr:.4f} -> {cdr:.4f} "
                f"({-drop:+.2%} vs threshold -{dedup_threshold:.1%})")
        if drop > dedup_threshold:
            regressions.append(
                "REGRESSION " + line + " (the lookup dedup stopped "
                "compressing the sharded gather — the per-step "
                "collective bytes blow up with it; see docs/embedding.md)")
        else:
            notes.append("ok " + line)
    elif (bdr is None) != (cdr is None):
        side = "candidate" if bdr is None else "baseline"
        notes.append(f"note: only the {side} carries an embedding dedup "
                     f"rate — dedup gate skipped (needs extra.embedding "
                     f"on both sides)")
    cr = candidate.get("resharding")
    if cr:
        br = baseline.get("resharding")
        if br is None:
            # same contract as the bytes gate: a baseline that carried
            # no commscope data cannot indict a pre-existing count
            notes.append(f"note: candidate carries {cr} resharding "
                         f"collective(s); baseline has no commscope "
                         f"data — nothing to gate")
        elif cr > br:
            regressions.append(
                f"REGRESSION resharding collectives: {br} -> {cr} "
                f"(an annotation/axis-rule no longer matches the "
                f"computation — see mxdiag.py comms)")
        else:
            notes.append(f"note: candidate carries {cr} resharding "
                         f"collective(s) (not new vs baseline)")
    for side, rec in (("candidate", candidate), ("baseline", baseline)):
        recov = rec.get("recoveries")
        if recov:
            lost = rec.get("steps_lost")
            notes.append(
                f"note: {side} RECOVERED {recov} time(s)"
                + (f", {lost} step(s) lost to rollbacks" if lost else "")
                + " — run usable (throughput is real), recovery cost "
                  "tracked here so it is never hidden")
    return regressions, notes


def _natural_key(path):
    return [int(t) if t.isdigit() else t
            for t in re.split(r"(\d+)", os.path.basename(path))]


def trajectory(paths, threshold, p99_threshold, noise_mult,
               candidate_path=None,
               coll_threshold=DEFAULT_COLL_THRESHOLD,
               busy_threshold=DEFAULT_BUSY_THRESHOLD,
               peak_threshold=DEFAULT_PEAK_THRESHOLD,
               dedup_threshold=DEFAULT_DEDUP_THRESHOLD):
    """Directory mode: newest usable artifact vs the median of all
    earlier usable ones, thresholds widened by the observed spread.
    Returns (exit_code, lines)."""
    lines = []
    loaded = []
    for p in sorted(paths, key=_natural_key):
        rec, why = load_artifact(p)
        if rec is None:
            lines.append(f"skip {p}: {why}")
        else:
            loaded.append(rec)
    if candidate_path:
        cand, why = load_artifact(candidate_path)
        if cand is None:
            lines.append(f"candidate {candidate_path} unusable ({why}) — "
                         f"no perf verdict possible")
            return 0, lines
        base_pool = [r for r in loaded if r["path"] != candidate_path]
    else:
        if not loaded:
            lines.append("no usable artifacts at all — nothing to gate")
            return 0, lines
        cand = loaded[-1]
        base_pool = loaded[:-1]
    base_pool = [r for r in base_pool if r["metric"] == cand["metric"]]
    if not base_pool:
        lines.append(f"no usable baseline for metric {cand['metric']!r} "
                     f"(every prior artifact skipped) — nothing to gate")
        return 0, lines
    values = [r["value"] for r in base_pool]
    values_sorted = sorted(values)
    med_val = values_sorted[len(values_sorted) // 2]
    base = dict(min(base_pool, key=lambda r: abs(r["value"] - med_val)))
    base["path"] = f"median of {len(base_pool)} artifacts"
    noise = _rel_spread(values)
    lines.append(f"candidate: {cand['path']} ({cand['value']:.4g} "
                 f"{cand['unit']})")
    lines.append(f"baseline: {base['path']} "
                 f"(median value {base['value']:.4g})")
    regs, notes = compare(base, cand, threshold=threshold,
                          p99_threshold=p99_threshold, noise=noise,
                          noise_mult=noise_mult,
                          coll_threshold=coll_threshold,
                          busy_threshold=busy_threshold,
                          peak_threshold=peak_threshold,
                          dedup_threshold=dedup_threshold)
    lines.extend(notes + regs)
    return (1 if regs else 0), lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="BENCH regression gate (env-failure-aware, "
                    "noise-aware)")
    ap.add_argument("files", nargs="*",
                    help="BASELINE.json CANDIDATE.json (pairwise mode)")
    ap.add_argument("--dir", default=None,
                    help="trajectory mode: gate the newest usable "
                         "BENCH_*.json in DIR against the median of the "
                         "earlier ones")
    ap.add_argument("--candidate", default=None,
                    help="with --dir: explicit candidate artifact")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="relative drop threshold for value/MFU "
                         "(default 0.05)")
    ap.add_argument("--p99-threshold", type=float,
                    default=DEFAULT_P99_THRESHOLD,
                    help="relative increase threshold for p99 "
                         "(default 0.25)")
    ap.add_argument("--noise-mult", type=float, default=DEFAULT_NOISE_MULT,
                    help="noise-band multiplier in trajectory mode "
                         "(default 2.0)")
    ap.add_argument("--coll-threshold", type=float,
                    default=DEFAULT_COLL_THRESHOLD,
                    help="relative increase threshold for per-step "
                         "collective bytes (default 0.10; a zero "
                         "baseline flags ANY appearance)")
    ap.add_argument("--busy-threshold", type=float,
                    default=DEFAULT_BUSY_THRESHOLD,
                    help="relative drop threshold for the measured "
                         "device busy fraction (default 0.10; skipped "
                         "unless BOTH sides carry a devicescope window)")
    ap.add_argument("--peak-threshold", type=float,
                    default=DEFAULT_PEAK_THRESHOLD,
                    help="relative increase threshold for measured peak "
                         "memory bytes (default 0.10; skipped unless "
                         "BOTH sides carry memscope data from the same "
                         "instrument)")
    ap.add_argument("--dedup-threshold", type=float,
                    default=DEFAULT_DEDUP_THRESHOLD,
                    help="relative drop threshold for the embedding "
                         "lookup dedup rate (default 0.10; skipped "
                         "unless BOTH sides carry extra.embedding)")
    args = ap.parse_args(argv)

    if args.dir:
        paths = glob.glob(os.path.join(args.dir, "BENCH_*.json"))
        if not paths:
            print(f"perf_regress: no BENCH_*.json under {args.dir}",
                  file=sys.stderr)
            return 2
        rc, lines = trajectory(paths, args.threshold, args.p99_threshold,
                               args.noise_mult,
                               candidate_path=args.candidate,
                               coll_threshold=args.coll_threshold,
                               busy_threshold=args.busy_threshold,
                               peak_threshold=args.peak_threshold,
                               dedup_threshold=args.dedup_threshold)
        for ln in lines:
            print(ln)
        print("perf_regress: " + ("REGRESSION" if rc else "OK"))
        return rc

    if len(args.files) != 2:
        ap.print_usage(sys.stderr)
        print("perf_regress: pairwise mode takes exactly BASELINE and "
              "CANDIDATE", file=sys.stderr)
        return 2
    base, why_b = load_artifact(args.files[0])
    cand, why_c = load_artifact(args.files[1])
    if base is None:
        print(f"skip baseline {args.files[0]}: {why_b} — nothing to gate")
        return 0
    if cand is None:
        print(f"skip candidate {args.files[1]}: {why_c} — no perf verdict "
              f"possible")
        return 0
    regs, notes = compare(base, cand, threshold=args.threshold,
                          p99_threshold=args.p99_threshold,
                          coll_threshold=args.coll_threshold,
                          busy_threshold=args.busy_threshold,
                          peak_threshold=args.peak_threshold,
                          dedup_threshold=args.dedup_threshold)
    for ln in notes + regs:
        print(ln)
    print("perf_regress: " + ("REGRESSION" if regs else "OK"))
    return 1 if regs else 0


if __name__ == "__main__":
    sys.exit(main())
