#!/usr/bin/env python
"""Pretty-print mxtpu diagnostics artifacts.

Flight-recorder dumps (`diagnostics.flight` / `mxtpu_flight_*.json`):
header, env/config snapshot, exception (when the dump came from the crash
path), counter table, and the tail of the event ring with relative
timestamps — the "what happened in the seconds before the crash" view.

Sampler time series (`metrics.jsonl`): first/last sample, counter deltas
and rates over the covered window.

`merge`: interleave SEVERAL ranks' flight dumps and/or structured event
logs (`mxtpu.events/` JSONL) into one time-ordered cross-rank timeline,
each line tagged with its rank — the post-mortem view for distributed
failures ("rank 1 went quiet 40 s before rank 0's collective timed
out"). `-o merged.jsonl` additionally writes the merged timeline as
`mxtpu.events/2` records (validated by tools/trace_check.py), carrying
each record's `mono` companion through when present.

`perf`: the MFU-decomposition report from a BENCH json
(`extra.perfscope`) — step budget with per-component shares (the
`collective` row carries its provenance: measured / estimated /
unavailable), counterfactual MFU table, per-program roofline verdicts.

`comms`: the collective-inventory report from a BENCH json
(`extra.commscope`) — per compiled program, one row per (op kind, mesh
axis) with count / payload MiB / analytic ICI estimate, plus any
resharding findings with the offending operand shapes.

`device`: the measured device-timeline report from a BENCH json
(`extra.devicescope`) — busy fraction, top-K device ops joined to their
roofline verdicts, measured collective lanes, idle-gap taxonomy, and
the analytic-vs-measured reconciliation.

`serve`: the tail-latency attribution report from a BENCH json
(`extra.servescope` / `extra.serve_load`) — the ramp sweep with its
saturation knee, per-bucket p99 cohort attribution (queue_wait /
coalesce_delay / pad_overhead / device_exec / respond) with roofline +
resharding verdicts, and the one-line advice ("p99 is 83% queue_wait
at bucket 128 - raise max_batch, not the kernel").

`fleet`: the replica-fleet report from a serve_load ``--fleet`` BENCH
json (`extra.fleet`) — per-replica dispatch table with client-observed
tails, the dispatch-imbalance ratio, the shared compile-cache verdict
(replica N+1's warmup: hit or recompile?), and the drain/swap/readmit
deploy timeline from the events log.

`mem`: the memory report from a BENCH json (`extra.memscope`) — the
static per-program footprint table joined to the roofline verdicts
(largest peak flagged), the watermark ring's p50/p95/peak with a tail
sparkline, the capacity/headroom verdict, the FSDP
analytic-vs-measured reconciliation, and the OOM post-mortem when the
run died of RESOURCE_EXHAUSTED.

`io`: the ingest-pipeline report from a BENCH json (`extra.io`) —
pipeline geometry (decode workers, buffer depth), cumulative per-stage
walls (read / decode / reorder / put), the consumer's empty-buffer
wait, and devicescope's measured input-starvation split with the
one-line triage ("starved 31% of idle: 80% decode → raise io_workers,
not prefetch depth").

`trace`: ONE request's cross-process span tree, joined on the
fleetscope `trace_id` across event logs from different processes — the
router's `fleetscope.request` record (admit → forward → respond) over
the replica's `serving.request` span (queue_wait / coalesce_delay /
pad_overhead / device_exec / respond), with the **wire gap** (router
forward wall minus replica e2e — a difference of perf_counter
durations, so clock skew cannot enter it) explicit between them, and
the `serving.batch` record the request coalesced into.

`pod`: the fleet-wide trace aggregate from a serve_load --fleet BENCH
json (`extra.fleetscope`) — join accounting (client-minted / sampled /
joined, unjoined forwards counted), wire-gap percentiles, the
per-replica trace table with straggler flags (report-only context for
the router's least-loaded score), and the collector's per-process
clock-offset estimates ± rtt/2.

Usage:
    python tools/mxdiag.py DUMP.json [--events N]
    python tools/mxdiag.py metrics.jsonl
    python tools/mxdiag.py perf BENCH.json
    python tools/mxdiag.py comms BENCH.json
    python tools/mxdiag.py device BENCH.json
    python tools/mxdiag.py mem BENCH.json
    python tools/mxdiag.py io BENCH.json
    python tools/mxdiag.py serve BENCH.json
    python tools/mxdiag.py fleet BENCH.json [--events EVENTS.jsonl]
    python tools/mxdiag.py trace TRACE_ID events.jsonl \\
        events_replica_*.jsonl
    python tools/mxdiag.py pod BENCH.json
    python tools/mxdiag.py merge events_rank0.jsonl events_rank1.jsonl \\
        mxtpu_flight_123.json [-o merged.jsonl] [--tail N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _fmt_bytes(n) -> str:
    try:
        n = float(n)
    except (TypeError, ValueError):
        return str(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:,.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0


def _fmt_ts(epoch) -> str:
    try:
        return time.strftime("%Y-%m-%d %H:%M:%S",
                             time.localtime(float(epoch)))
    except (TypeError, ValueError):
        return str(epoch)


def _fmt_flops(n) -> str:
    try:
        n = float(n)
    except (TypeError, ValueError):
        return str(n)
    for unit in ("", "K", "M", "G", "T", "P"):
        if abs(n) < 1000 or unit == "P":
            return f"{n:,.2f} {unit}FLOP"
        n /= 1000.0


def _fmt_cost_args(args: dict) -> str:
    """Human rendering of a perfscope-enriched compile span's cost
    fields (flops / bytes_accessed / roofline / ai)."""
    parts = []
    if args.get("flops") is not None:
        parts.append(_fmt_flops(args["flops"]))
    if args.get("bytes_accessed") is not None:
        parts.append(_fmt_bytes(args["bytes_accessed"]))
    if args.get("ai") is not None:
        parts.append(f"AI {args['ai']:.2f}")
    if args.get("roofline"):
        parts.append(f"-> {args['roofline'].upper()}")
    rest = {k: v for k, v in args.items()
            if k not in ("flops", "bytes_accessed", "ai", "roofline",
                         "est_compute_ms", "est_memory_ms")}
    out = "  " + "  ".join(parts)
    if rest:
        out += "  " + json.dumps(rest)
    return out


def print_flight(doc: dict, n_events: int) -> None:
    print(f"flight dump  schema={doc.get('schema')}  "
          f"reason={doc.get('reason')!r}")
    print(f"  dumped at {_fmt_ts(doc.get('dumped_at'))}  "
          f"(recorder started {_fmt_ts(doc.get('started_at'))})")
    env = doc.get("env") or {}
    print(f"  pid {env.get('pid')}  python {env.get('python')}  "
          f"jax backend {env.get('jax_backend')} "
          f"x{env.get('jax_device_count')}  "
          f"mxtpu {env.get('mxtpu_version')}")
    if env.get("argv"):
        print(f"  argv: {' '.join(env['argv'])}")
    for k, v in sorted((env.get("env") or {}).items()):
        print(f"    {k}={v}")
    cfg = doc.get("config") or {}
    if cfg:
        print("  config: " + ", ".join(f"{k}={v}"
                                       for k, v in sorted(cfg.items())))
    exc = doc.get("exception")
    if exc:
        print(f"\n  EXCEPTION: {exc.get('type')}: {exc.get('message')}")
        for frame in exc.get("traceback") or []:
            for ln in frame.rstrip().splitlines():
                print("    " + ln)
    counters = doc.get("counters") or {}
    kinds = doc.get("counter_kinds") or {}
    if counters:
        print(f"\n  counters ({len(counters)}):")
        width = max(len(k) for k in counters)
        for k in sorted(counters):
            v = counters[k]
            tag = kinds.get(k, "?")[0]
            shown = _fmt_bytes(v) if k.endswith("_bytes") or \
                k.endswith("/current_bytes") or "bytes" in k else v
            print(f"    [{tag}] {k:<{width}}  {shown}")
    events = doc.get("events") or []
    tail = events[-n_events:]
    t_end = doc.get("dumped_at") or (tail[-1]["ts"] if tail else 0)
    print(f"\n  events: {len(events)} in ring "
          f"(capacity {doc.get('capacity')}), last {len(tail)}:")
    for ev in tail:
        dt = ev.get("ts", 0) - t_end
        args = ev.get("args")
        if args and ev.get("kind") == "compile" and \
                ("flops" in args or "roofline" in args):
            extra = _fmt_cost_args(args)   # perfscope-enriched span
        else:
            extra = "  " + json.dumps(args) if args else ""
        print(f"    {dt:>+9.3f}s  {ev.get('kind', '?'):<10} "
              f"{ev.get('name', '?')}{extra}")


def print_metrics(path: str) -> None:
    samples = []
    with open(path) as f:
        for ln in f:
            ln = ln.strip()
            if ln:
                samples.append(json.loads(ln))
    if not samples:
        print(f"{path}: no samples")
        return
    first, last = samples[0], samples[-1]
    span = last["ts"] - first["ts"]
    print(f"metrics series: {len(samples)} samples over {span:.2f}s "
          f"({_fmt_ts(first['ts'])} .. {_fmt_ts(last['ts'])})")
    kinds = last.get("kinds") or {}
    names = sorted(set(first.get("counters", {})) |
                   set(last.get("counters", {})))
    width = max((len(n) for n in names), default=4)
    for name in names:
        a = first.get("counters", {}).get(name)
        b = last.get("counters", {}).get(name)
        kind = kinds.get(name, "?")
        if kind == "counter" and isinstance(a, (int, float)) \
                and isinstance(b, (int, float)):
            rate = (b - a) / span if span > 0 else 0.0
            print(f"  [c] {name:<{width}}  {a} -> {b}  "
                  f"(+{b - a}, {rate:.2f}/s)")
        else:
            print(f"  [{kind[0]}] {name:<{width}}  {b}")
    mem = last.get("memory")
    if mem:
        print(f"  memory: current {_fmt_bytes(mem.get('current_bytes'))}  "
              f"peak {_fmt_bytes(mem.get('peak_bytes'))}  "
              f"live {mem.get('live_arrays')}")


# ---------------------------------------------------------------------------
# perf: MFU-decomposition report from a BENCH json (extra.perfscope)
# ---------------------------------------------------------------------------

def _print_reconciliation(recon: dict, indent: str = "  ") -> None:
    """The analytic-vs-measured table a devicescope window produced —
    shared by `perf` and `device` so the two reports can't drift apart
    on the reconciliation schema."""
    ana, mea = recon.get("analytic") or {}, recon.get("measured") or {}
    thr = recon.get("threshold")
    drift = recon.get("drift") or {}
    print(f"\n{indent}analytic vs measured (devicescope window"
          + (f", drift threshold {thr:.0%}" if thr else "") + "):")
    for comp in ("device_compute", "collective"):
        a, m = ana.get(comp + "_ms"), mea.get(comp + "_ms")
        if a is None or m is None:
            continue
        dr = drift.get(comp)
        src = (f"analytic({ana.get('source')})"
               if comp == "device_compute"
               else f"analytic({ana.get('collective_source')})")
        line = (f"{indent}  {comp:<15} measured {m:>10.3f} ms   "
                f"{src} {a:>10.3f} ms")
        if dr is not None:
            line += f"   delta {dr:>6.1%}"
            if thr is not None and dr > thr:
                line += "  << DRIFT"
        print(line)
    if recon.get("drift_warning"):
        print(f"{indent}  DRIFT WARNING: analytic and measured disagree "
              f"beyond the threshold — an estimate (probe / ring model "
              f"/ peak table) has gone stale; trust the measured window "
              f"(docs/devicescope.md)")

def _load_bench(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "parsed" in doc and "metric" not in doc:
        doc = doc["parsed"] or {}
    if not isinstance(doc, dict) or "metric" not in doc:
        raise ValueError(f"{path}: not a bench result "
                         f"(no 'metric'/'parsed' key)")
    return doc


def print_perf(doc: dict) -> int:
    """The "why is my MFU low" report: step budget with per-component
    shares, the counterfactual MFU table, and per-program roofline
    verdicts."""
    extra = doc.get("extra") or {}
    print(f"bench: {doc.get('metric')} = {doc.get('value')} "
          f"{doc.get('unit')}  (model {extra.get('model')}, batch "
          f"{extra.get('batch')}, {extra.get('dtype')})")
    if doc.get("status") == "env_failure" or doc.get("error"):
        print(f"  run failed ({doc.get('status') or 'error'}): "
              f"{doc.get('error')}")
        return 1
    ps = extra.get("perfscope")
    if not isinstance(ps, dict):
        print("  no extra.perfscope section (perfscope was off)")
        return 1
    peaks = ps.get("peaks") or {}
    print(f"  peaks: {peaks.get('device_kind')} "
          f"(table row {peaks.get('table_row')})  "
          f"bf16 {_fmt_flops(peaks.get('peak_flops_bf16'))}/s  "
          f"f32 {_fmt_flops(peaks.get('peak_flops_f32'))}/s  "
          f"HBM {_fmt_bytes(peaks.get('hbm_bytes_per_s'))}/s")
    d = ps.get("decomposition")
    if isinstance(d, dict) and d.get("step_ms"):
        step = d["step_ms"]
        recon = d.get("reconciliation") \
            if isinstance(d.get("reconciliation"), dict) else None
        print(f"\n  step budget ({d.get('steps')} steps, source="
              f"{d.get('source')}):  step_ms = {step:.3f}")
        for comp in ("device_compute", "collective", "input_wait",
                     "host_gap", "other"):
            ms = d.get(comp + "_ms")
            if ms is None:
                continue
            share = ms / step if step else 0.0
            bar = "#" * int(round(share * 40))
            tag = ""
            if comp == "collective":
                src = d.get("collective_source")
                if src == "estimated":
                    tag = "  [estimated: commscope static-HLO]"
                elif src == "measured(profile)":
                    tag = "  [measured: devicescope window]"
                elif src == "unavailable":
                    tag = ("  [UNAVAILABLE: in-program collectives, "
                           "commscope off — not a measured zero]")
            print(f"    {comp:<15} {ms:>10.3f} ms  {share:>6.1%}  "
                  f"{bar}{tag}")
        print(f"    {'(coverage':<15} {d.get('coverage')})")
        if recon:
            # BOTH sources exist: show the analytic numbers (probe /
            # ring estimate) beside the measured window, with the delta
            # — never only one source when the run carried both
            _print_reconciliation(recon)
        if d.get("mfu") is not None:
            print(f"\n  MFU decomposition:  achieved {d['mfu']:.4f}")
            if d.get("mfu_device_only") is not None:
                print(f"    device-compute-bound ceiling  "
                      f"{d['mfu_device_only']:.4f}")
            for comp, v in (d.get("mfu_if_removed") or {}).items():
                if v is not None and d["mfu"]:
                    print(f"    if {comp + ' were free:':<22} {v:.4f}  "
                          f"({v / d['mfu']:.2f}x)")
    else:
        print("  no step-time decomposition in this artifact")
    progs = ps.get("programs") or []
    if progs:
        print(f"\n  compiled programs ({len(progs)}):")
        width = max(len(p.get("name", "?")) for p in progs)
        for p in progs:
            f = _fmt_flops(p.get("flops")) if p.get("flops") is not None \
                else "-"
            b = _fmt_bytes(p.get("bytes_accessed")) \
                if p.get("bytes_accessed") is not None else "-"
            ai = f"AI {p['ai']:.2f}" if p.get("ai") is not None else ""
            print(f"    {p.get('name', '?'):<{width}}  "
                  f"{p.get('verdict', '?'):<14} {f:>14}  {b:>12}  {ai}")
    return 0


def _perf_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="mxdiag.py perf",
        description="MFU-decomposition report from a BENCH json "
                    "(extra.perfscope)")
    ap.add_argument("path", help="artifact json (serve_load.py output or the "
                                 "driver wrapper)")
    args = ap.parse_args(argv)
    try:
        doc = _load_bench(args.path)
    except (OSError, ValueError) as e:
        print(f"perf: {e}", file=sys.stderr)
        return 1
    return print_perf(doc)


# ---------------------------------------------------------------------------
# comms: per-program collective tables from a BENCH json (extra.commscope)
# ---------------------------------------------------------------------------

def print_comms(doc: dict) -> int:
    """The "what collectives does my layout run" report: per compiled
    program, one row per (op kind, mesh axis) with count / payload /
    analytic ICI estimate, plus any resharding findings — the evidence
    behind the step budget's estimated `collective` component."""
    extra = doc.get("extra") or {}
    print(f"bench: {doc.get('metric')} = {doc.get('value')} "
          f"{doc.get('unit')}  (model {extra.get('model')}, batch "
          f"{extra.get('batch')}, {extra.get('dtype')})")
    if doc.get("status") == "env_failure" or doc.get("error"):
        print(f"  run failed ({doc.get('status') or 'error'}): "
              f"{doc.get('error')}")
        return 1
    cs = extra.get("commscope")
    if not isinstance(cs, dict):
        print("  no extra.commscope section (commscope was off, or the "
              "run had no mesh)")
        return 1
    peaks = cs.get("peaks") or {}
    print(f"  ICI peaks: {peaks.get('device_kind')} "
          f"(table row {peaks.get('table_row')})  "
          f"{_fmt_bytes(peaks.get('ici_bytes_per_s'))}/s  "
          f"(estimates are analytic ring lower bounds, not measurements)")
    step = cs.get("step")
    if isinstance(step, dict):
        est = step.get("est_ms")
        line = f"  steady train program: {step.get('program')}"
        if _is_numlike(est):
            line += (f"  {_fmt_bytes(step.get('bytes'))}/step  "
                     f"est {est:.4f} ms/step")
        print(line)
    progs = cs.get("programs") or []
    if not progs:
        print("  no programs captured")
        return 0
    for p in progs:
        mesh = p.get("mesh")
        mesh_s = "x".join(f"{k}{v}" for k, v in (mesh or {}).items()) \
            or "no mesh"
        t = p.get("totals") or {}
        flag = ""
        if p.get("resharding_collectives"):
            flag = (f"  !! {p['resharding_collectives']} RESHARDING "
                    f"collective(s)")
        print(f"\n  {p.get('name')}  (mode={p.get('mode')}, {mesh_s})  "
              f"{t.get('count', 0)} collectives, "
              f"{_fmt_bytes(t.get('bytes', 0))}, "
              f"est {t.get('est_ms', 0):.4f} ms{flag}")
        rows = p.get("collectives") or []
        if not rows and p.get("hlo_available") is False:
            print("      (optimized HLO unavailable — inventory unknown)")
        for c in rows:
            print(f"      {c.get('kind', '?'):<19} x{c.get('count', 0):<4} "
                  f"{_fmt_bytes(c.get('bytes', 0)):>12}  "
                  f"est {c.get('est_ms', 0):.4f} ms  "
                  f"axis {c.get('axis') or '?'}")
        for r in p.get("resharding") or []:
            print(f"      RESHARD {r.get('kind')} ({r.get('reason')}): "
                  f"result {r.get('result_shape')}  operands "
                  f"{r.get('operand_shapes')}")
    return 0


def _is_numlike(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def _comms_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="mxdiag.py comms",
        description="per-program collective tables from a BENCH json "
                    "(extra.commscope)")
    ap.add_argument("path", help="artifact json (serve_load.py output or the "
                                 "driver wrapper)")
    args = ap.parse_args(argv)
    try:
        doc = _load_bench(args.path)
    except (OSError, ValueError) as e:
        print(f"comms: {e}", file=sys.stderr)
        return 1
    return print_comms(doc)


# ---------------------------------------------------------------------------
# device: measured device-timeline report from a BENCH json
# (extra.devicescope)
# ---------------------------------------------------------------------------

def print_device(doc: dict) -> int:
    """The "what did the chip actually do" report: measured busy
    fraction, top-K device ops joined to their roofline verdicts,
    measured collective lanes, the idle-gap taxonomy, and the
    analytic-vs-measured reconciliation — everything a devicescope
    capture window ingested (docs/devicescope.md)."""
    extra = doc.get("extra") or {}
    print(f"bench: {doc.get('metric')} = {doc.get('value')} "
          f"{doc.get('unit')}  (model {extra.get('model')}, batch "
          f"{extra.get('batch')}, {extra.get('dtype')})")
    if doc.get("status") == "env_failure" or doc.get("error"):
        print(f"  run failed ({doc.get('status') or 'error'}): "
              f"{doc.get('error')}")
        return 1
    ds = extra.get("devicescope")
    if not isinstance(ds, dict):
        print("  no extra.devicescope section (devicescope was off)")
        return 1
    win = ds.get("window")
    if not isinstance(win, dict):
        print("  devicescope was armed but no capture window completed "
              "(profiler busy, or the run ended before the window)")
        return 1
    wall = win.get("wall_ms")
    wall_s = f"{wall:.1f} ms" if _is_numlike(wall) else str(wall)
    print(f"  window: {win.get('steps')} steps over {wall_s}  "
          f"(requested {win.get('requested_steps')}, "
          f"complete={win.get('complete')})")
    print(f"    artifact: {win.get('path')}")
    if ds.get("error"):
        print(f"    INGEST ERROR: {ds['error']}")
    bf = ds.get("busy_fraction")
    if bf is not None:
        bar = "#" * int(round(bf * 40))
        print(f"\n  device busy fraction: {bf:.1%}  {bar}")
    per = ds.get("per_step") or {}
    if per:
        print(f"    per step: busy {per.get('device_busy_ms')} ms  "
              f"collective {per.get('collective_ms')} ms  "
              f"idle {per.get('idle_ms')} ms  "
              f"(over {ds.get('device_events')} device events, "
              f"{len(ds.get('lanes') or [])} lanes)")
    tops = ds.get("top_ops") or []
    if tops:
        print(f"\n  top device ops ({len(tops)}):")
        width = max(len(t.get("op", "?")) for t in tops)
        for t in tops:
            prog = t.get("program") or t.get("module") or "?"
            verdict = f"  [{t['verdict']}]" if t.get("verdict") else ""
            print(f"    {t.get('op', '?'):<{width}}  "
                  f"{t.get('total_ms', 0):>10.3f} ms  "
                  f"x{t.get('count', 0):<5} "
                  f"{prog}{verdict}")
    colls = ds.get("collectives") or {}
    rows = colls.get("by_kind") or []
    if rows:
        print(f"\n  measured collectives (union "
              f"{colls.get('union_ms')} ms):")
        for r in rows:
            print(f"    {r.get('kind', '?'):<19} x{r.get('count', 0):<5} "
                  f"{r.get('total_ms', 0):>10.3f} ms  "
                  f"axis {r.get('axis') or '?'}")
    gaps = ds.get("gaps")
    if isinstance(gaps, dict):
        tax = gaps.get("taxonomy") or {}
        print(f"\n  idle gaps: {gaps.get('count')} gaps, "
              f"{gaps.get('total_ms')} ms total, "
              f"max {gaps.get('max_ms')} ms")
        hist = gaps.get("histogram_ms") or {}
        if hist:
            print("    duration histogram (ms): "
                  + "  ".join(f"<={k}: {v}" for k, v in hist.items()))
        idle = sum(v for v in tax.values()
                   if isinstance(v, (int, float))) or None
        for key, label in (("input_starved_ms", "input-starved"),
                           ("dispatch_serialized_ms",
                            "dispatch-serialized"),
                           ("host_gap_ms", "host-gap")):
            v = tax.get(key)
            if v is None:
                continue
            share = f"  {v / idle:>6.1%}" if idle else ""
            print(f"    {label:<20} {v:>10.3f} ms{share}")
    recon = ds.get("reconciliation")
    if isinstance(recon, dict):
        _print_reconciliation(recon)
    elif bf is not None:
        print("\n  no reconciliation block (the step budget settled "
              "without this window — was perfscope off?)")
    return 0


def _device_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="mxdiag.py device",
        description="measured device-timeline report from a BENCH json "
                    "(extra.devicescope)")
    ap.add_argument("path", help="artifact json (serve_load.py output or the "
                                 "driver wrapper)")
    args = ap.parse_args(argv)
    try:
        doc = _load_bench(args.path)
    except (OSError, ValueError) as e:
        print(f"device: {e}", file=sys.stderr)
        return 1
    return print_device(doc)


# ---------------------------------------------------------------------------
# mem: memory report from a BENCH json (extra.memscope)
# ---------------------------------------------------------------------------

_SPARK_LEVELS = ".:-=+*#%@"


def _sparkline(values) -> str:
    """ASCII sparkline over a small series (the watermark tail)."""
    vals = [float(v) for v in values
            if isinstance(v, (int, float)) and not isinstance(v, bool)]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _SPARK_LEVELS[0] * len(vals)
    out = []
    for v in vals:
        i = int((v - lo) / (hi - lo) * (len(_SPARK_LEVELS) - 1))
        out.append(_SPARK_LEVELS[i])
    return "".join(out)


def print_mem(doc: dict) -> int:
    """The "where does the memory go" report: the static per-program
    footprint table joined to the roofline verdicts (the largest peak
    flagged << PEAK), the watermark ring's p50/p95/peak with a tail
    sparkline, the capacity/headroom verdict, the analytic-vs-measured
    reconciliation, and — when the run died — the OOM post-mortem
    (docs/memscope.md)."""
    extra = doc.get("extra") or {}
    print(f"bench: {doc.get('metric')} = {doc.get('value')} "
          f"{doc.get('unit')}  (model {extra.get('model')}, batch "
          f"{extra.get('batch')}, {extra.get('dtype')})")
    if doc.get("status") == "env_failure":
        print(f"  run failed (env_failure): {doc.get('error')}")
        return 1
    ms = extra.get("memscope")
    if not isinstance(ms, dict):
        print("  no extra.memscope section (memscope was off)")
        return 1
    progs = [p for p in (ms.get("programs") or []) if isinstance(p, dict)]
    if progs:
        peaks = [p.get("peak_bytes") for p in progs]
        maxpeak = max((p for p in peaks
                       if isinstance(p, (int, float))
                       and not isinstance(p, bool)), default=None)
        print(f"\n  static program footprints ({len(progs)}):")
        width = max(len(p.get("name") or "?") for p in progs)
        for p in progs:
            name = p.get("name") or "?"
            if not p.get("available"):
                print(f"    {name:<{width}}  (no memory_analysis on "
                      f"this backend)")
                continue
            verdict = f"  [{p['roofline']}]" if p.get("roofline") else ""
            mark = "  << PEAK" if maxpeak is not None \
                and p.get("peak_bytes") == maxpeak else ""
            print(f"    {name:<{width}}  peak {_fmt_bytes(p.get('peak_bytes')):>11}  "
                  f"(args {_fmt_bytes(p.get('argument_bytes'))}, "
                  f"out {_fmt_bytes(p.get('output_bytes'))}, "
                  f"temp {_fmt_bytes(p.get('temp_bytes'))}, "
                  f"{p.get('provenance')})"
                  f"{verdict}{mark}")
    else:
        print("\n  no static footprints captured (no compile crossed "
              "the perfscope funnel while armed)")
    wm = ms.get("watermarks")
    if isinstance(wm, dict):
        print(f"\n  watermark ring: {wm.get('ring')}/"
              f"{wm.get('ring_limit')} samples held "
              f"({wm.get('samples')} taken)")
        for sect, label in (("device", "device bytes_in_use"),
                            ("host_rss", "host RSS")):
            blk = wm.get(sect)
            if not isinstance(blk, dict):
                if sect == "device":
                    print("    device allocator: unavailable on this "
                          "backend (host RSS carries the watermark)")
                continue
            print(f"    {label}: p50 {_fmt_bytes(blk.get('p50'))}  "
                  f"p95 {_fmt_bytes(blk.get('p95'))}  "
                  f"peak {_fmt_bytes(blk.get('peak'))}  "
                  f"latest {_fmt_bytes(blk.get('latest'))}")
        tail = wm.get("tail") or []
        key = "host_rss_bytes"
        series = [t.get(key) for t in tail if isinstance(t, dict)]
        spark = _sparkline(series)
        if spark:
            print(f"    tail ({len(spark)} samples, host RSS): "
                  f"[{spark}]")
    hr = ms.get("headroom")
    if isinstance(hr, dict):
        cap, frac = hr.get("capacity_bytes"), hr.get("headroom_fraction")
        verdict = hr.get("verdict")
        decor = {"ok": "OK", "tight": "!! TIGHT"}.get(verdict, verdict)
        line = (f"\n  headroom: {decor}")
        if frac is not None:
            line += f"  {frac:.1%} of capacity free"
        if cap:
            line += (f"  (in use {_fmt_bytes(hr.get('in_use_bytes'))} "
                     f"of {_fmt_bytes(cap)} "
                     f"[{hr.get('capacity_source')}], target "
                     f"{hr.get('target')})")
        print(line)
    recon = ms.get("reconciliation")
    if isinstance(recon, dict) and recon.get("analytic"):
        a, m = recon["analytic"], recon.get("measured") or {}
        print(f"\n  reconciliation ({a.get('source')}):")
        print(f"    analytic per-device: "
              f"{_fmt_bytes(a.get('total_per_device'))} "
              f"(params {_fmt_bytes(a.get('param_bytes_per_device'))}, "
              f"states {_fmt_bytes(a.get('state_bytes_per_device'))}, "
              f"claimed reduction x{a.get('reduction')})")
        print(f"    measured: {_fmt_bytes(m.get('peak_bytes_in_use'))} "
              f"({m.get('source')})")
        drift = (recon.get("drift") or {}).get("per_device_bytes")
        if drift is not None:
            flag = "  !! STALE ESTIMATE" if recon.get("drift_warning") \
                else ""
            print(f"    drift: {drift:.1%} "
                  f"(threshold {recon.get('threshold'):.0%}){flag}")
    oom = ms.get("oom")
    if isinstance(oom, dict):
        print(f"\n  OOM POST-MORTEM (step {oom.get('step')}, program "
              f"{oom.get('program')!r}):")
        print(f"    error: {str(oom.get('error'))[:160]}")
        fp = oom.get("footprint")
        if isinstance(fp, dict) and fp.get("available"):
            print(f"    offending program's static peak: "
                  f"{_fmt_bytes(fp.get('peak_bytes'))} "
                  f"({fp.get('provenance')})")
        tail = oom.get("watermark_tail") or []
        series = [t.get("host_rss_bytes") for t in tail
                  if isinstance(t, dict)]
        spark = _sparkline(series)
        if spark:
            print(f"    memory in the steps before death: [{spark}]")
        bufs = oom.get("top_buffers") or []
        if bufs:
            print("    top live buffers at death:")
            for b in bufs[:8]:
                if isinstance(b, dict):
                    print(f"      {b.get('block', '?'):<28} "
                          f"{_fmt_bytes(b.get('bytes', 0)):>12}")
        knobs = oom.get("knobs")
        if isinstance(knobs, dict):
            set_knobs = {k: v for k, v in knobs.items() if v is not None}
            print(f"    resolved knobs: {set_knobs or '(all defaults)'}")
    elif ms.get("oom") is None:
        print("\n  no OOM recorded (good)")
    return 0


def _mem_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="mxdiag.py mem",
        description="memory report from a BENCH json (extra.memscope)")
    ap.add_argument("path", help="artifact json (serve_load.py output or the "
                                 "driver wrapper)")
    args = ap.parse_args(argv)
    try:
        doc = _load_bench(args.path)
    except (OSError, ValueError) as e:
        print(f"mem: {e}", file=sys.stderr)
        return 1
    return print_mem(doc)


# ---------------------------------------------------------------------------
# io: ingest-pipeline report from a BENCH json (extra.io +
# extra.devicescope's input_starved_split)
# ---------------------------------------------------------------------------

def print_io(doc: dict) -> int:
    """The "is the chip input-starved, and whose fault is it" report:
    the ingest pipeline's geometry and cumulative per-stage walls from
    extra.io, joined to devicescope's measured starvation split —
    ending in the one-line advice ("starved 31% of idle: 80% decode →
    raise io_workers, not prefetch depth")."""
    extra = doc.get("extra") or {}
    print(f"bench: {doc.get('metric')} = {doc.get('value')} "
          f"{doc.get('unit')}  (model {extra.get('model')})")
    if doc.get("status") == "env_failure" or doc.get("error"):
        print(f"  run failed ({doc.get('status') or 'error'}): "
              f"{doc.get('error')}")
        return 1
    io = extra.get("io")
    if not isinstance(io, dict):
        print("\n  no extra.io section (the run had no ingest pipeline "
              "— synthetic single-step mode, or a pre-PR-17 artifact)")
        return 1
    print(f"\n  pipeline: {io.get('workers')} decode worker(s), "
          f"depth {io.get('depth')}, "
          f"{io.get('batches_prefetched')} batches staged"
          + (f", {io.get('batches_skipped')} skipped (resume cursor)"
             if io.get("batches_skipped") else "")
          + (f", {io.get('records_read')} records read"
             if io.get("records_read") else "")
          + (f", injected slow-decode {io.get('slow_ms')} ms/batch"
             if io.get("slow_ms") else ""))
    stages = [("read (source next)", io.get("read_ms")),
              ("decode pool", io.get("decode_ms")),
              ("stage (reorder wait)", io.get("stage_ms")),
              ("put (host->device)", io.get("put_ms"))]
    total = sum(v for _, v in stages if isinstance(v, (int, float)))
    print("  cumulative stage walls (threads overlap — attribution, "
          "not a span):")
    for name, v in stages:
        v = float(v or 0.0)
        share = v / total if total else 0.0
        bar = "#" * int(round(share * 30))
        print(f"    {name:<22} {v:>10.1f} ms  {share:>6.1%}  {bar}")
    print(f"  consumer wait (io.wait_ms): {float(io.get('wait_ms') or 0):.1f} ms "
          f"— time next() sat on an empty buffer")
    ds = extra.get("devicescope") or {}
    gaps = ds.get("gaps") or {}
    starved = (gaps.get("taxonomy") or {}).get("input_starved_ms")
    split = gaps.get("input_starved_split")
    if not isinstance(split, dict):
        if starved in (None, 0):
            print("\n  device window: no input starvation measured — "
                  "the buffer kept ahead of the chip")
        else:
            print(f"\n  device window: input_starved {starved} ms, but "
                  f"no stage split (no stage walls in the window)")
        return 0
    idle = ds.get("idle_ms") or 0
    dom = split.get("dominant")
    parts = {"read": split.get("read_ms"),
             "decode": split.get("decode_ms"),
             "transfer": split.get("transfer_ms")}
    tot = sum(float(v or 0) for v in parts.values())
    dom_share = (float(parts.get(dom) or 0) / tot) if tot else 0.0
    starved_share = (float(starved or 0) / float(idle)) if idle else 0.0
    print(f"\n  device window: input_starved {starved} ms of "
          f"{idle} ms idle — split:")
    for k, v in parts.items():
        v = float(v or 0)
        share = v / tot if tot else 0.0
        tag = "  << DOMINANT" if k == dom else ""
        print(f"    {k:<10} {v:>9.1f} ms  {share:>6.1%}{tag}")
    knob = {"read": "shard wider / faster storage, not prefetch depth",
            "decode": "raise io_workers, not prefetch depth",
            "transfer": "raise prefetch_depth (deeper overlap), "
                        "not io_workers"}.get(dom, "")
    if knob:
        print(f"\n  ADVICE: starved {starved_share:.0%} of idle: "
              f"{dom_share:.0%} {dom} -> {knob}")
    return 0


def _io_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="mxdiag.py io",
        description="ingest-pipeline report from a BENCH json "
                    "(extra.io + devicescope starvation split)")
    ap.add_argument("path", help="artifact json (serve_load.py output or the "
                                 "driver wrapper)")
    args = ap.parse_args(argv)
    try:
        doc = _load_bench(args.path)
    except (OSError, ValueError) as e:
        print(f"io: {e}", file=sys.stderr)
        return 1
    return print_io(doc)


# ---------------------------------------------------------------------------
# serve: tail-latency attribution report from a BENCH json
# (extra.servescope / extra.serve_load / extra.serving)
# ---------------------------------------------------------------------------

def _print_attr_group(grp: dict, indent: str = "    ") -> None:
    """One attribution group (overall or a bucket): the p99 cohort's
    component split with share bars, plus the independent component
    p99s underneath."""
    att = (grp.get("attribution") or {}).get("p99")
    e2e = grp.get("e2e_ms") or {}
    if not att:
        print(f"{indent}(no attribution — too few traced requests)")
        return
    print(f"{indent}e2e p50/p95/p99: {e2e.get('p50')}/{e2e.get('p95')}/"
          f"{e2e.get('p99')} ms  ({grp.get('count')} traced)")
    total = att.get("sum_ms") or 0.0
    print(f"{indent}p99 cohort ({att.get('cohort')} request(s) at "
          f"{att.get('e2e_ms')} ms):")
    for key, v in (att.get("components") or {}).items():
        share = v / total if total else 0.0
        bar = "#" * int(round(share * 30))
        tag = "  << TAIL" if key == att.get("top_component") else ""
        print(f"{indent}  {key.replace('_ms', ''):<15} {v:>9.3f} ms  "
              f"{share:>6.1%}  {bar}{tag}")


def print_serve(doc: dict) -> int:
    """The "why is my p99 what it is" report: the serve_load sweep
    table with its saturation knee, and servescope's per-bucket
    tail-latency attribution with roofline + resharding verdicts —
    ending in the one-line advice ("p99 is 83% queue_wait at bucket
    128 - raise max_batch, not the kernel")."""
    extra = doc.get("extra") or {}
    print(f"bench: {doc.get('metric')} = {doc.get('value')} "
          f"{doc.get('unit')}  (model {extra.get('model')})")
    if doc.get("status") == "env_failure" or doc.get("error"):
        print(f"  run failed ({doc.get('status') or 'error'}): "
              f"{doc.get('error')}")
        return 1
    sl = extra.get("serve_load")
    if isinstance(sl, dict) and sl.get("levels"):
        print(f"\n  ramp sweep ({len(sl['levels'])} levels, knee: "
              f"{sl.get('knee_reason')}):")
        for i, lv in enumerate(sl["levels"]):
            knee = "  << KNEE" if i == sl.get("knee_index") else ""
            print(f"    {lv.get('concurrency'):>5} clients  "
                  f"{lv.get('qps'):>9.1f} qps  p50/p95/p99 "
                  f"{lv.get('p50_ms')}/{lv.get('p95_ms')}/"
                  f"{lv.get('p99_ms')} ms  errors "
                  f"{lv.get('errors', 0)}{knee}")
    sv = extra.get("serving")
    if isinstance(sv, dict):
        print(f"\n  serving totals: {sv.get('responses')}/"
              f"{sv.get('requests')} responded over "
              f"{sv.get('batches')} batches (fill "
              f"{sv.get('batch_fill')}x); rejects: queue_full "
              f"{sv.get('rejected_queue_full', 0)}, deadline "
              f"{sv.get('rejected_deadline', 0)} (+"
              f"{sv.get('rejected_deadline_post_batch', 0)} post-batch), "
              f"invalid {sv.get('rejected_invalid', 0)}")
    ss = extra.get("servescope")
    if not isinstance(ss, dict):
        print("\n  no extra.servescope section (servescope was off)")
        return 1
    src = ss.get("device_exec_source")
    tag = ""
    if src == "measured(profile)":
        w = ss.get("device_window") or {}
        tag = (f"  [device_exec measured: devicescope window over "
               f"{w.get('dispatches')} dispatches"
               + (", DRIFT vs host wall" if w.get("drift_warning")
                  else "") + "]")
    elif src == "host_wall":
        tag = "  [device_exec: host wall around the executable]"
    print(f"\n  tail-latency attribution (sampled 1/"
          f"{ss.get('sample_every', 1)}, {ss.get('requests')} traced)"
          f"{tag}")
    print("\n  overall:")
    _print_attr_group(ss.get("overall") or {})
    for key, grp in sorted((ss.get("per_bucket") or {}).items(),
                           key=lambda kv: int(kv[0])
                           if kv[0].isdigit() else 0):
        verdict = grp.get("verdict")
        reshard = grp.get("resharding_collectives")
        flags = []
        if verdict:
            flags.append(verdict)
        if reshard:
            flags.append(f"!! {reshard} RESHARDING collective(s)")
        elif reshard == 0:
            flags.append("resharding-clean")
        fill = grp.get("fill")
        fill_s = f", fill {fill:.0%}" if isinstance(fill, float) else ""
        print(f"\n  bucket {key} ({', '.join(flags) or 'no verdicts'}"
              f"{fill_s}):")
        _print_attr_group(grp)
    advice = ss.get("advice")
    if advice:
        print(f"\n  ADVICE: {advice}")
    return 0


def _serve_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="mxdiag.py serve",
        description="tail-latency attribution report from a BENCH json "
                    "(extra.servescope / extra.serve_load)")
    ap.add_argument("path", help="artifact json (serve_load.py "
                                 "output or the driver wrapper)")
    args = ap.parse_args(argv)
    try:
        doc = _load_bench(args.path)
    except (OSError, ValueError) as e:
        print(f"serve: {e}", file=sys.stderr)
        return 1
    return print_serve(doc)


def print_fleet(doc: dict, events_path=None) -> int:
    """The fleet report from a serve_load ``--fleet`` BENCH json
    (`extra.fleet`): the per-replica dispatch table with
    client-observed tails, the imbalance ratio, the shared
    compile-cache verdict (did replica N+1's warmup hit?), and — when
    the events log is reachable — the drain/swap/readmit deploy
    timeline."""
    extra = doc.get("extra") or {}
    print(f"bench: {doc.get('metric')} = {doc.get('value')} "
          f"{doc.get('unit')}  (model {extra.get('model')})")
    if doc.get("status") == "env_failure" or doc.get("error"):
        print(f"  run failed ({doc.get('status') or 'error'}): "
              f"{doc.get('error')}")
        return 1
    fl = extra.get("fleet")
    if not isinstance(fl, dict):
        print("\n  no extra.fleet section — this BENCH json is not a "
              "serve_load --fleet run (try `mxdiag.py serve` instead)")
        return 1
    print(f"\n  fleet: {fl.get('replicas')} replicas "
          f"({fl.get('batcher')} batcher), dispatch imbalance "
          f"{fl.get('dispatch_imbalance')} (max/mean; 1.0 = perfectly "
          f"balanced)")
    print(f"  router: {fl.get('routed')} routed, "
          f"{fl.get('routed_errors', 0)} forward errors, "
          f"{fl.get('no_replica_available', 0)} x no-replica-available")
    rows = fl.get("per_replica") or []
    if rows:
        print("\n  replica        requests  dispatched        qps  "
              "p50/p95/p99 ms")
        for row in rows:
            pcts = "/".join(str(row.get(k, "-"))
                            for k in ("p50_ms", "p95_ms", "p99_ms"))
            print(f"    {row.get('name', '?'):<12} {row.get('requests', 0):>9}"
                  f"  {row.get('dispatched', 0):>10}  {row.get('qps', 0):>9}"
                  f"  {pcts}")
    cache = fl.get("compile_cache")
    if isinstance(cache, dict):
        hits = cache.get("hits", 0)
        misses = cache.get("misses", 0)
        verdict = ("replica warmups were cache hits (no duplicate XLA "
                   "compiles)" if hits else
                   "NO cache hits — every replica recompiled from "
                   "scratch (cold or unshared cache dir?)")
        print(f"\n  shared AOT cache ({fl.get('cache_dir')}): "
              f"{hits} hits / {misses} misses / "
              f"{cache.get('stores', 0)} stores — {verdict}")
    # deploy timeline: fleet.drain / fleet.swap / fleet.readmit events
    path = events_path or extra.get("events_file")
    deploys = []
    if path and os.path.exists(path):
        try:
            with open(path) as f:
                for ln in f:
                    try:
                        rec = json.loads(ln)
                    except ValueError:
                        continue
                    if isinstance(rec, dict) and rec.get("kind") == "fleet":
                        deploys.append(rec)
        except OSError:
            pass
    if deploys:
        t0 = deploys[0].get("ts") or 0
        print(f"\n  deploy timeline ({len(deploys)} fleet events):")
        for rec in deploys:
            args = rec.get("args") or {}
            dt = (rec.get("ts") or 0) - t0
            detail = ", ".join(f"{k}={v}" for k, v in sorted(args.items()))
            print(f"    +{dt:8.3f}s  {rec.get('name'):<14} {detail}")
    elif path:
        print(f"\n  no fleet drain/swap/readmit events in {path} "
              f"(no deploy happened during this run)")
    return 0


def _fleet_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="mxdiag.py fleet",
        description="replica-fleet report from a serve_load --fleet "
                    "BENCH json (extra.fleet)")
    ap.add_argument("path", help="BENCH json (serve_load.py --fleet "
                                 "output or the driver wrapper)")
    ap.add_argument("--events", default=None,
                    help="mxtpu.events/1 log for the deploy timeline "
                         "(default: the json's extra.events_file)")
    args = ap.parse_args(argv)
    try:
        doc = _load_bench(args.path)
    except (OSError, ValueError) as e:
        print(f"fleet: {e}", file=sys.stderr)
        return 1
    return print_fleet(doc, events_path=args.events)


# ---------------------------------------------------------------------------
# merge: cross-rank timeline from per-rank flight dumps / event logs
# ---------------------------------------------------------------------------

def _load_timeline(path: str, fallback_rank: int):
    """Normalize one artifact into (rank, run_id, [records]); records are
    {ts, rank, step, kind, name, args?, src}. Event logs carry their own
    rank/run_id per record; flight dumps are tagged from their env
    snapshot (rank recorded at enable time) or, failing that, the
    file's position on the command line."""
    records = []
    if path.endswith(".jsonl"):
        rank, run_id = fallback_rank, None
        with open(path) as f:
            for ln in f:
                ln = ln.strip()
                if not ln:
                    continue
                rec = json.loads(ln)
                if not str(rec.get("schema", "")).startswith(
                        "mxtpu.events/"):
                    raise ValueError(
                        f"{path}: not an mxtpu.events/ log (merge takes "
                        f"event logs and flight dumps, not metrics "
                        f"series)")
                rank = rec.get("rank", fallback_rank)
                run_id = rec.get("run_id", run_id)
                records.append({
                    "ts": rec["ts"], "rank": rank,
                    "run_id": rec.get("run_id"),
                    "step": rec.get("step"), "kind": rec.get("kind", "?"),
                    "name": rec.get("name", "?"),
                    "args": rec.get("args"), "src": path,
                    "mono": rec.get("mono")})
        return rank, run_id, records
    with open(path) as f:
        doc = json.load(f)
    if not (isinstance(doc, dict) and str(doc.get("schema", "")).startswith(
            "mxtpu.flight/")):
        raise ValueError(f"{path}: neither an event log nor a flight dump")
    env = doc.get("env") or {}
    rank = env.get("rank", fallback_rank)
    for ev in doc.get("events") or []:
        records.append({"ts": ev.get("ts", 0), "rank": rank, "step": None,
                        "kind": ev.get("kind", "?"),
                        "name": ev.get("name", "?"),
                        "args": ev.get("args"), "src": path,
                        "mono": ev.get("mono")})
    return rank, None, records


def merge_timelines(paths, out_path=None):
    """Merge-sort the artifacts by timestamp; returns the merged record
    list (and optionally writes it as mxtpu.events/1 JSONL)."""
    merged = []
    run_ids = set()
    for i, p in enumerate(paths):
        _, rid, recs = _load_timeline(p, fallback_rank=i)
        if rid:
            run_ids.add(rid)
        merged.extend(recs)
    merged.sort(key=lambda r: r["ts"])
    if len(run_ids) > 1:
        print(f"merge: WARNING: inputs span {len(run_ids)} run_ids "
              f"({sorted(run_ids)[:3]}...) — these are different runs",
              file=sys.stderr)
    # run_id for records that carry none (flight dumps): the inputs'
    # consensus when they agree, else an explicit unknown — NEVER a
    # run_id borrowed from an unrelated file (the correlation id must
    # stay honest in the validated merged output)
    fallback_rid = next(iter(run_ids)) if len(run_ids) == 1 else "unknown"
    if out_path:
        with open(out_path, "w") as f:
            last_ts = 0.0
            for r in merged:
                ts = max(float(r["ts"]), last_ts)   # keep the schema's
                last_ts = ts                        # monotonic-ts contract
                rec = {"schema": "mxtpu.events/2", "ts": ts,
                       "run_id": r.get("run_id") or fallback_rid,
                       "rank": int(r["rank"]), "step": r["step"],
                       "kind": r["kind"], "name": r["name"]}
                if isinstance(r.get("mono"), (int, float)):
                    # mono is only meaningful WITHIN its source process;
                    # carried through so a re-merge can still use it
                    rec["mono"] = r["mono"]
                if r.get("args"):
                    rec["args"] = r["args"]
                f.write(json.dumps(rec) + "\n")
    return merged


def print_merged(merged, tail=0) -> None:
    ranks = sorted({r["rank"] for r in merged})
    if not merged:
        print("merge: no records")
        return
    t0, t_end = merged[0]["ts"], merged[-1]["ts"]
    print(f"merged timeline: {len(merged)} records from "
          f"{len(ranks)} rank(s) {ranks} over {t_end - t0:.3f}s "
          f"({_fmt_ts(t0)} .. {_fmt_ts(t_end)})")
    show = merged[-tail:] if tail else merged
    if tail and len(merged) > tail:
        print(f"  ... {len(merged) - tail} earlier records elided ...")
    for r in show:
        step = f" step={r['step']}" if r.get("step") is not None else ""
        args = f"  {json.dumps(r['args'])}" if r.get("args") else ""
        print(f"  {r['ts'] - t0:>9.3f}s  [rank {r['rank']}] "
              f"{r['kind']:<10} {r['name']}{step}{args}")


# event names that ARE faults (detection) vs recovery ACTIONS — the
# join mxdiag recover renders: healthmon detects, resilience acts
# (docs/observability.md's "who acts on which verdict" column)
_RECOVER_FAULTS = ("healthmon.nan_loss", "healthmon.nan_grad_norm",
                   "healthmon.stall", "healthmon.step_time_regression",
                   "resilience.corrupt_checkpoint",
                   "resilience.save_error", "resilience.escalation")
_RECOVER_ACTIONS = ("resilience.rollback", "resilience.resume",
                    "resilience.restart_requested",
                    "resilience.rank_departed", "resilience.rank_joined")


def print_recover(merged) -> int:
    """Render the recovery timeline from a merged (or single-rank)
    mxtpu.events/1 stream: fault detected → rollback/restart → steps
    replayed → converged, healthmon alerts joined to resilience actions
    by run_id/step."""
    if not merged:
        print("recover: no records")
        return 1
    t0 = merged[0]["ts"]
    faults = [r for r in merged if r["name"] in _RECOVER_FAULTS]
    actions = [r for r in merged if r["name"] in _RECOVER_ACTIONS]
    saves = [r for r in merged
             if r["name"] == "resilience.checkpoint_saved"]
    steps = [r["step"] for r in merged
             if r["kind"] == "trainer" and r.get("step") is not None]
    run_ids = sorted({r.get("run_id") for r in merged if r.get("run_id")})
    print(f"recovery timeline: run_id={run_ids or ['?']}  "
          f"{len(faults)} fault(s), {len(actions)} recovery action(s), "
          f"{len(saves)} checkpoint(s)")
    if not faults and not actions:
        print("  clean run: no faults detected, no recoveries "
              "(checkpoints below are pure insurance)")
    rows = sorted(faults + actions + saves, key=lambda r: r["ts"])
    for r in rows:
        a = r.get("args") or {}
        if r["name"] in _RECOVER_FAULTS:
            tag = "FAULT "
            detail = json.dumps(a) if a else ""
        elif r["name"] == "resilience.checkpoint_saved":
            tag = "ckpt  "
            detail = (f"step {r.get('step')} "
                      f"({a.get('save_ms', '?')} ms async)")
        else:
            tag = "ACTION"
            if r["name"] == "resilience.rollback":
                detail = (f"step {a.get('from_step')} -> "
                          f"{a.get('to_step')} "
                          f"({a.get('steps_lost')} step(s) replayed, "
                          f"attempt {a.get('attempt')}, "
                          f"reason={a.get('reason')})")
            elif r["name"] == "resilience.resume":
                detail = (f"restored step {a.get('restored_step')}, "
                          f"cursor {a.get('cursor')} (restart-from-"
                          f"last-good)")
            elif r["name"] == "resilience.rank_departed":
                detail = (f"departed={a.get('departed')} -> members "
                          f"{a.get('members')} (re-formed at smaller "
                          f"world)")
            elif r["name"] == "resilience.rank_joined":
                detail = (f"joined={a.get('joined') or [a.get('rank')]} "
                          f"-> members {a.get('members')}")
            else:
                detail = json.dumps(a) if a else ""
        step = f" step={r['step']}" if r.get("step") is not None else ""
        print(f"  {r['ts'] - t0:>9.3f}s  [rank {r['rank']}] {tag} "
              f"{r['name']}{step}  {detail}")
    # fault -> first following action join, restricted to action kinds
    # that plausibly ANSWER that fault class (an unrelated later
    # rank_joined must not mark an un-acted-on NaN as handled)
    fault_answers = {
        "healthmon.nan_loss": ("resilience.rollback", "resilience.resume"),
        "healthmon.nan_grad_norm": ("resilience.rollback",
                                    "resilience.resume"),
        "healthmon.stall": ("resilience.restart_requested",
                            "resilience.resume"),
        "resilience.corrupt_checkpoint": ("resilience.resume",
                                          "resilience.rollback"),
        # retries exhausted: only a later process-level resume counts
        "resilience.escalation": ("resilience.resume",),
    }
    unhandled = []
    for fz in faults:
        answers = fault_answers.get(fz["name"])
        nxt = next((az for az in actions if az["ts"] >= fz["ts"]
                    and (answers is None or az["name"] in answers)), None)
        # regressions are advisory, and a failed ASYNC save is tolerated
        # by design (degraded durability, training continues) — neither
        # demands a recovery action after it
        if nxt is None and fz["name"] not in (
                "healthmon.step_time_regression",
                "resilience.save_error"):
            unhandled.append(fz)
    last_action_ts = max((az["ts"] for az in actions), default=None)
    tail_steps = [s for r in merged
                  if r["kind"] == "trainer" and r.get("step") is not None
                  and (last_action_ts is None or r["ts"] > last_action_ts)
                  for s in [r["step"]]]
    lost = sum(int((r.get("args") or {}).get("steps_lost") or 0)
               for r in actions if r["name"] == "resilience.rollback")
    print(f"summary: rollbacks="
          f"{sum(r['name'] == 'resilience.rollback' for r in actions)} "
          f"resumes={sum(r['name'] == 'resilience.resume' for r in actions)} "
          f"departures="
          f"{sum(r['name'] == 'resilience.rank_departed' for r in actions)} "
          f"joins="
          f"{sum(r['name'] == 'resilience.rank_joined' for r in actions)} "
          f"steps_replayed={lost}")
    if steps:
        post = (f", {len(tail_steps)} step(s) after the last recovery"
                if last_action_ts is not None else "")
        print(f"  progress: trained to step {max(steps)}{post} — "
              f"the run OUTLIVED its faults" if actions else
              f"  progress: trained to step {max(steps)}")
    if unhandled:
        print(f"  << UNHANDLED: {len(unhandled)} fault(s) with no "
              f"recovery action after them: "
              f"{[r['name'] for r in unhandled][:4]}")
        return 1
    return 0


def _recover_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="mxdiag.py recover",
        description="render the fault -> recovery timeline from "
                    "mxtpu.events/1 logs (per-rank or merged)")
    ap.add_argument("paths", nargs="+",
                    help="event-log .jsonl files (and/or flight dumps)")
    args = ap.parse_args(argv)
    try:
        merged = merge_timelines(args.paths)
    except (OSError, ValueError, KeyError) as e:
        print(f"recover: {e}", file=sys.stderr)
        return 1
    return print_recover(merged)


def _lint_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="mxdiag.py lint",
        description="render the mxlint findings report (rule ids + "
                    "fix-it hints) for the repo or specific paths")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the package "
                         "and tools/)")
    ap.add_argument("--rule", action="append", default=None,
                    help="run only these rule ids (repeatable)")
    args = ap.parse_args(argv)
    import importlib.util
    ml_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "mxlint.py")
    spec = importlib.util.spec_from_file_location("mxlint_cli", ml_path)
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    rules = None
    if args.rule:
        mxl = cli._load_mxlint()
        rules = [mxl.rules.rule_by_id(r) for r in args.rule]
    findings, root = cli.run_lint(args.paths or None, rules=rules)
    print("== mxlint findings ==")
    if not findings:
        print("  tree is clean (0 findings)")
        return 0
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    for rule in sorted(by_rule):
        fs = by_rule[rule]
        print(f"  [{rule}]  {len(fs)} finding{'s' if len(fs) != 1 else ''}")
        for f in fs:
            rel = os.path.relpath(f.path, root)
            print(f"    {rel}:{f.line}: {f.message}")
        if fs[0].hint:
            print(f"    fix: {fs[0].hint}")
    print(f"  {len(findings)} total — suppress only with "
          f"'# mxlint: disable=<rule> -- <reason>' (docs/mxlint.md)")
    return 1


def _merge_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="mxdiag.py merge",
        description="interleave per-rank flight dumps / event logs into "
                    "one cross-rank timeline")
    ap.add_argument("paths", nargs="+",
                    help="event-log .jsonl and/or flight-dump .json files")
    ap.add_argument("-o", "--out", default=None,
                    help="also write the merged timeline as "
                         "mxtpu.events/1 JSONL")
    ap.add_argument("--tail", type=int, default=0,
                    help="print only the last N merged records")
    args = ap.parse_args(argv)
    try:
        merged = merge_timelines(args.paths, out_path=args.out)
    except (OSError, ValueError, KeyError) as e:
        print(f"merge: {e}", file=sys.stderr)
        return 1
    print_merged(merged, tail=args.tail)
    if args.out:
        print(f"merged timeline written: {args.out}")
    return 0


# ---------------------------------------------------------------------------
# trace / pod: the fleetscope cross-process views
# ---------------------------------------------------------------------------

_SPAN_COMPONENTS = ("queue_wait_ms", "coalesce_delay_ms",
                    "pad_overhead_ms", "device_exec_ms", "respond_ms")


def print_trace(trace_id: str, records) -> int:
    """Render ONE request's cross-process span tree from merged event
    records: the router's ``fleetscope.request`` hop over the replica's
    ``serving.request`` span, the wire gap between them explicit, and
    the ``serving.batch`` dispatch the request coalesced into."""
    def _args(r):
        return r.get("args") or {}

    routers = [r for r in records if r.get("name") == "fleetscope.request"
               and _args(r).get("trace_id") == trace_id]
    replicas = [r for r in records if r.get("name") == "serving.request"
                and _args(r).get("trace_id") == trace_id]
    batches = [r for r in records if r.get("name") == "serving.batch"
               and trace_id in (_args(r).get("traces") or [])]
    if not routers and not replicas:
        print(f"trace: no records carry trace_id {trace_id!r} "
              f"(is fleetscope armed on both sides?)", file=sys.stderr)
        return 1
    srcs = sorted({r.get("src", "?") for r in routers + replicas + batches})
    print(f"== trace {trace_id} ==")
    print(f"  {len(routers)} router + {len(replicas)} replica + "
          f"{len(batches)} batch record(s) across {len(srcs)} file(s)")
    rc = 0
    for rr in routers:
        a = _args(rr)
        fw = a.get("forward_ms")
        fw_s = f", forward {fw:.2f} ms" if isinstance(fw, (int, float)) \
            else ""
        print(f"  router span {a.get('span_id', '?')}  "
              f"replica={a.get('replica')}  status={a.get('status')}  "
              f"e2e {a.get('e2e_ms', 0.0):.2f} ms{fw_s}   "
              f"[{rr.get('src', '?')}]")
        # the replica-side child(ren) of THIS hop: parent == router span
        children = [pr for pr in replicas
                    if _args(pr).get("parent_id") == a.get("span_id")]
        orphans = [pr for pr in replicas if pr not in children]
        for pr in children:
            p = _args(pr)
            e2e = p.get("e2e_ms")
            if isinstance(fw, (int, float)) and isinstance(e2e,
                                                           (int, float)):
                print(f"    |- wire gap {fw - e2e:.2f} ms  (router "
                      f"forward - replica e2e: duration difference, "
                      f"clock-skew free)")
            comp = " | ".join(
                f"{k[:-3]} {p[k]:.2f}" for k in _SPAN_COMPONENTS
                if isinstance(p.get(k), (int, float)))
            e2e_s = f"e2e {e2e:.2f} ms" if isinstance(e2e, (int, float)) \
                else f"status={p.get('status')}"
            print(f"    `- replica span {p.get('span_id', '?')} "
                  f"(parent {p.get('parent_id', '?')})  "
                  f"bucket={p.get('bucket')} batch={p.get('batch_id')}  "
                  f"{e2e_s}   [{pr.get('src', '?')}]")
            if comp:
                print(f"         {comp}")
            for br in batches:
                b = _args(br)
                if b.get("batch_id") == p.get("batch_id"):
                    shared = len(b.get("traces") or []) - 1
                    print(f"         batch {b.get('batch_id')}: "
                          f"n={b.get('n')} bucket={b.get('bucket')} "
                          f"exec {b.get('exec_ms')} ms"
                          + (f", co-batched with {shared} other "
                             f"traced request(s)" if shared > 0 else ""))
        if not children and replicas:
            rc = 1
            print(f"    << BROKEN JOIN: {len(orphans)} replica record(s) "
                  f"with this trace_id but parent != router span "
                  f"{a.get('span_id')!r}")
        elif not children:
            print(f"    (no replica-side span arrived — an unjoined "
                  f"forward: replica not sampling, or its events log "
                  f"was not given here)")
    for pr in (replicas if not routers else []):
        p = _args(pr)
        print(f"  replica span {p.get('span_id', '?')} (parent "
              f"{p.get('parent_id', '?')})  e2e "
              f"{p.get('e2e_ms', 0.0):.2f} ms — no router record "
              f"(router events log not given here?)   "
              f"[{pr.get('src', '?')}]")
    return rc


def _trace_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="mxdiag.py trace",
        description="one request's cross-process span tree, joined on "
                    "the fleetscope trace_id across event logs")
    ap.add_argument("trace_id", help="32-hex fleetscope trace id (from "
                                     "a reply's trace_id field or an "
                                     "events record)")
    ap.add_argument("paths", nargs="+",
                    help="event-log .jsonl files from BOTH sides "
                         "(router's and each replica's)")
    args = ap.parse_args(argv)
    try:
        merged = merge_timelines(args.paths)
    except (OSError, ValueError, KeyError) as e:
        print(f"trace: {e}", file=sys.stderr)
        return 1
    return print_trace(args.trace_id.strip().lower(), merged)


# straggler flag threshold: a replica whose trace p99 exceeds this
# multiple of the fleet median gets flagged (report-only — the router's
# least-loaded score is the control loop, this is the explanation)
_POD_STRAGGLER_MULT = 1.5


def print_pod(doc) -> int:
    """Render the fleet-wide trace aggregate (``extra.fleetscope``) from
    a serve_load --fleet BENCH json: join accounting, wire-gap
    percentiles, per-replica table with straggler flags, and the
    collector's clock-offset estimates."""
    extra = doc.get("extra") or {}
    fs = extra.get("fleetscope")
    if not isinstance(fs, dict):
        print("pod: no extra.fleetscope section (serve_load runs with "
              "fleetscope armed; --fleet N adds the per-replica rows)",
              file=sys.stderr)
        return 1
    print(f"== pod: cross-process trace aggregate "
          f"({extra.get('model', doc.get('metric', '?'))}) ==")
    rate = fs.get("join_rate")
    print(f"  traces: {fs.get('client_minted')} client-minted, "
          f"{fs.get('sampled')} sampled, {fs.get('joined')} joined "
          + (f"(join rate {rate:.1%})" if isinstance(rate, (int, float))
             else "") + f", {fs.get('unjoined_forwards')} unjoined "
          f"forward(s) — counted, never guessed")
    gap = fs.get("wire_gap_ms")
    if isinstance(gap, dict):
        print(f"  wire gap: p50 {gap.get('p50')} / p95 {gap.get('p95')} "
              f"/ p99 {gap.get('p99')} ms  (router forward - replica "
              f"e2e: clock-skew free)")
    rows = fs.get("per_replica") or []
    if rows:
        p99s = sorted(r["e2e_p99_ms"] for r in rows
                      if isinstance(r.get("e2e_p99_ms"), (int, float)))
        median = p99s[(len(p99s) - 1) // 2] if p99s else None
        print(f"  {'replica':<14} {'traces':>7} {'e2e p99 ms':>11} "
              f"{'wire gap p50':>13}")
        for r in rows:
            p99 = r.get("e2e_p99_ms")
            flag = ""
            if isinstance(p99, (int, float)) and median \
                    and p99 > _POD_STRAGGLER_MULT * median:
                flag = (f"   << straggler ({p99 / median:.2f}x the "
                        f"median p99; report-only)")
            p99_s = f"{p99:.3f}" if isinstance(p99, (int, float)) else "-"
            g = r.get("wire_gap_p50_ms")
            g_s = f"{g:.3f}" if isinstance(g, (int, float)) else "-"
            print(f"  {r.get('name', '?'):<14} {r.get('traces', 0):>7} "
                  f"{p99_s:>11} {g_s:>13}{flag}")
        spread = fs.get("replica_spread")
        if isinstance(spread, (int, float)):
            print(f"  replica spread (max/median p99): {spread:.2f}"
                  + ("  — balanced" if spread <= _POD_STRAGGLER_MULT
                     else "  — investigate the flagged replica"))
    coll = fs.get("collector")
    if isinstance(coll, dict):
        procs = coll.get("processes") or {}
        print(f"  collector: {len(procs)} process(es), "
              f"interval {coll.get('interval_s')} s")
        for name in sorted(procs):
            p = procs[name]
            off, bound = p.get("offset_s"), p.get("offset_bound_s")
            if isinstance(off, (int, float)):
                skew = (f"clock offset {off * 1e3:+.2f} ms "
                        f"+/- {bound * 1e3:.2f} ms"
                        if isinstance(bound, (int, float))
                        else f"clock offset {off * 1e3:+.2f} ms")
            else:
                skew = "no successful pull"
            err = f"  last_error={p.get('last_error')}" \
                if p.get("last_error") else ""
            print(f"    {name:<12} {p.get('pulls', 0):>3} pull(s)  "
                  f"{skew}{err}")
    return 0


def _pod_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="mxdiag.py pod",
        description="fleet-wide trace aggregate from a serve_load "
                    "--fleet BENCH json (extra.fleetscope)")
    ap.add_argument("path", help="BENCH json (serve_load.py output)")
    args = ap.parse_args(argv)
    try:
        doc = _load_bench(args.path)
    except (OSError, ValueError) as e:
        print(f"pod: {e}", file=sys.stderr)
        return 1
    return print_pod(doc)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "merge":
        return _merge_main(argv[1:])
    if argv and argv[0] == "perf":
        return _perf_main(argv[1:])
    if argv and argv[0] == "comms":
        return _comms_main(argv[1:])
    if argv and argv[0] == "device":
        return _device_main(argv[1:])
    if argv and argv[0] == "mem":
        return _mem_main(argv[1:])
    if argv and argv[0] == "io":
        return _io_main(argv[1:])
    if argv and argv[0] == "serve":
        return _serve_main(argv[1:])
    if argv and argv[0] == "fleet":
        return _fleet_main(argv[1:])
    if argv and argv[0] == "trace":
        return _trace_main(argv[1:])
    if argv and argv[0] == "pod":
        return _pod_main(argv[1:])
    if argv and argv[0] == "recover":
        return _recover_main(argv[1:])
    if argv and argv[0] == "lint":
        return _lint_main(argv[1:])
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="flight dump .json or metrics .jsonl")
    ap.add_argument("--events", type=int, default=40,
                    help="how many trailing ring events to print")
    args = ap.parse_args(argv)
    if args.path.endswith(".jsonl"):
        print_metrics(args.path)
        return 0
    try:
        with open(args.path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"{args.path}: {e}", file=sys.stderr)
        return 1
    if isinstance(doc, dict) and str(doc.get("schema", "")).startswith(
            "mxtpu.flight/"):
        print_flight(doc, args.events)
        return 0
    print(f"{args.path}: not a flight dump (schema="
          f"{doc.get('schema') if isinstance(doc, dict) else None!r}); "
          f"for Chrome traces use chrome://tracing", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
