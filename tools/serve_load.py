#!/usr/bin/env python
"""Closed-loop serving load harness: ramp concurrency, find the knee.

Freeze a model, start :class:`ModelServer`, and
drive K concurrent **closed-loop** clients (each fires its next request
the moment the previous response lands — the load model under which
"QPS at a p99 target" is well-defined) through a ramped concurrency
sweep. For every level the harness records client-observed QPS and
p50/p95/p99, then finds the **saturation knee** — the last level where
throughput still scales before p99 inflects — and emits one
trace_check-valid BENCH json:

* ``metric`` = ``serve_load_<model>_qps_at_knee``, ``value`` = the QPS
  at the knee (gated by ``tools/perf_regress.py``'s value gate);
* ``extra.serving`` — the standard serving section (schema enforced by
  ``check_bench_json``), with p50/p95/p99 and qps measured AT the knee
  level and the request/batch accounting + latency histogram from the
  server's cumulative registry snapshot;
* ``extra.serve_load`` — the full per-level sweep table plus the knee
  verdict (``knee_concurrency`` / ``qps_at_knee`` / ``p99_at_knee_ms``,
  gated by perf_regress's p99 gate);
* ``extra.servescope`` — the tail-latency attribution
  (``queue_wait + coalesce_delay + pad_overhead + device_exec +
  respond`` per bucket, with roofline + resharding verdicts attached —
  ``check_servescope_extra`` validates it, ``mxdiag.py serve`` renders
  it);
* ``extra.fleetscope`` — cross-process trace accounting: every client
  request carries a freshly minted W3C ``traceparent`` header, and the
  section reports how many traces the serving side actually joined
  (``client_minted`` / ``sampled`` / ``joined`` / ``join_rate``, with
  ``unjoined_forwards`` counted — never guessed away). In --fleet mode
  it adds the **wire-gap** percentiles (router-observed forward time
  minus replica-observed total: a difference of durations, so clock
  skew cannot enter it), per-replica trace p99s, and the
  ``replica_spread`` straggler ratio — ``check_fleetscope_extra``
  validates it, ``mxdiag.py trace``/``pod`` render the raw records.

A server that dies mid-sweep (every request of a level failing, or a
dead /healthz) produces a self-describing ``{"status": "env_failure"}``
artifact — the convention perf_regress skips — instead of a
zero that would poison the BENCH trajectory.

With ``--fleet N`` the harness drives a whole replica fleet instead of
one server: N **spawned worker processes** (each its own GIL, warmed
through the shared on-disk
:class:`~incubator_mxnet_tpu.fleet.CompileCache`) behind a
:class:`~incubator_mxnet_tpu.fleet.Router`, the load aimed at the
router's front door. The artifact gains ``extra.fleet`` — per-replica
client-observed QPS/p99 (keyed off the ``replica`` tag the router
stamps into every reply), the dispatch-imbalance ratio, and the
router/cache accounting — validated by ``check_fleet_extra`` and
rendered by ``mxdiag.py fleet``; ``extra.serving`` is the MERGE of the
workers' ``/stats`` exports (each process owns a registry). The metric
name grows a ``_fleetN`` suffix so perf_regress's both-sides contract
compares fleet runs against fleet baselines, never against the
single-server trajectory. Replica scaling is a multi-core claim: on a
1-core host the fleet only measures its own routing overhead.

In --fleet mode each worker is spawned with ``servescope``/
``fleetscope``/``export`` armed and its own ``mxtpu.events/2`` log
(``<events>_replica_<pid>.jsonl``); the router's ``fleetscope.request``
records land in the harness's events file, and after the sweep the two
sides are joined on ``trace_id`` (one request = ONE trace: router admit
→ wire → replica queue_wait → coalesce → device_exec → respond). A
:class:`~incubator_mxnet_tpu.fleetscope.Collector` polls every
replica's ``diagnostics.export`` endpoint during the sweep; its
clock-offset snapshot rides along under ``extra.fleetscope.collector``.

Usage:
    python tools/serve_load.py [--model lenet] [--ramp 4,8,16,32,64]
        [--level-requests 128] [--max-delay-ms 5] [--out BENCH.json]
        [--events EVENTS.jsonl] [--sample N] [--devicescope N]
        [--fleet N] [--fleet-cache DIR]

Pure helpers (:func:`find_knee`, :func:`run_level`, :func:`sweep`,
:func:`write_env_failure`) are importable without a backend —
``tests/test_servescope.py`` unit-tests knee detection and the
env-failure path against synthetic levels.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

__all__ = ["find_knee", "run_level", "sweep", "build_result",
           "merge_serving_stats", "write_env_failure", "ServerDied",
           "read_event_records", "build_fleetscope_extra",
           "main", "DEFAULT_RAMP", "KNEE_QPS_GAIN", "KNEE_P99_MULT"]

DEFAULT_RAMP = "4,8,16,32,64"
# knee rules: saturation begins at the first level whose marginal QPS
# gain is below KNEE_QPS_GAIN x the concurrency scaling, or whose p99
# exceeds KNEE_P99_MULT x the base level's p99 (the inflection)
KNEE_QPS_GAIN = 0.10
KNEE_P99_MULT = 3.0


class ServerDied(RuntimeError):
    """Every request of a level failed (or /healthz went away): the
    server is gone, and the sweep has no perf meaning."""


# ---------------------------------------------------------------------------
# knee detection (pure)
# ---------------------------------------------------------------------------

def find_knee(levels, qps_gain: float = KNEE_QPS_GAIN,
              p99_mult: float = KNEE_P99_MULT):
    """The saturation knee of a ramped sweep.

    ``levels``: dicts with ``concurrency``, ``qps``, ``p99_ms``,
    ordered by ascending concurrency. Returns ``(index, reason)`` of
    the knee level — the last level BEFORE saturation:

    * level i saturates on **throughput** when its relative QPS gain
      over level i-1 falls below ``qps_gain`` x the relative
      concurrency increase (doubling clients for <10% more QPS means
      the extra clients only queue);
    * level i saturates on **latency** when ``p99_ms`` exceeds
      ``p99_mult`` x the base level's p99 (the inflection — latency has
      replaced throughput as the thing that grows).

    With no saturation observed the knee is the last level (reason
    says so: the ramp didn't reach the knee)."""
    if not levels:
        raise ValueError("find_knee needs at least one level")
    base_p99 = levels[0].get("p99_ms") or 0.0
    for i in range(1, len(levels)):
        prev, cur = levels[i - 1], levels[i]
        scale = (cur["concurrency"] / prev["concurrency"]) - 1.0
        gain = ((cur["qps"] - prev["qps"]) / prev["qps"]
                if prev["qps"] > 0 else 0.0)
        if scale > 0 and gain < qps_gain * scale:
            return i - 1, (f"throughput saturated at concurrency "
                           f"{cur['concurrency']} (+{gain:.1%} QPS for "
                           f"+{scale:.0%} clients)")
        if base_p99 > 0 and (cur.get("p99_ms") or 0.0) \
                > p99_mult * base_p99:
            return i - 1, (f"p99 inflected at concurrency "
                           f"{cur['concurrency']} "
                           f"({cur['p99_ms']:.1f} ms > {p99_mult:g}x "
                           f"base {base_p99:.1f} ms)")
    return len(levels) - 1, "no saturation observed (ramp too short?)"


# ---------------------------------------------------------------------------
# closed-loop level runner
# ---------------------------------------------------------------------------

def _percentile(sorted_vals, q):
    import math
    n = len(sorted_vals)
    return sorted_vals[min(n - 1, max(0, math.ceil(q * n) - 1))]


def run_level(send_fn, concurrency: int, total_requests: int) -> dict:
    """Drive ``total_requests`` through ``concurrency`` closed-loop
    client threads. ``send_fn(i)`` issues request i and blocks until
    its response (raising on failure). Returns the level dict
    {concurrency, requests, ok, errors, wall_s, qps, p50/p95/p99_ms};
    raises :class:`ServerDied` when NOTHING succeeded."""
    counter = [0]
    lock = threading.Lock()
    lats, errs = [], []

    def client():
        while True:
            with lock:
                i = counter[0]
                if i >= total_requests:
                    return
                counter[0] += 1
            t0 = time.perf_counter()
            try:
                send_fn(i)
            except Exception as e:  # noqa: BLE001 — a failed request is
                with lock:          # data, not a harness crash
                    errs.append(f"{type(e).__name__}: {e}")
                continue
            dt = (time.perf_counter() - t0) * 1e3
            with lock:
                lats.append(dt)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(max(1, int(concurrency)))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if not lats:
        raise ServerDied(
            f"level concurrency={concurrency}: all {total_requests} "
            f"requests failed; first error: {errs[0] if errs else '?'}")
    lats.sort()
    return {
        "concurrency": int(concurrency),
        "requests": int(total_requests),
        "ok": len(lats),
        "errors": len(errs),
        "first_error": errs[0][:200] if errs else None,
        "wall_s": round(wall, 4),
        "qps": round(len(lats) / wall, 2) if wall > 0 else 0.0,
        "p50_ms": round(_percentile(lats, 0.50), 3),
        "p95_ms": round(_percentile(lats, 0.95), 3),
        "p99_ms": round(_percentile(lats, 0.99), 3),
        "mean_ms": round(sum(lats) / len(lats), 3),
    }


def sweep(send_fn, ramp, level_requests: int, log=print,
          before_level=None) -> list:
    """Run every ramp level through :func:`run_level` (closed loop,
    ascending concurrency). ``before_level(index, concurrency)``, when
    given, runs ahead of each level (main() arms the devicescope
    window over the most loaded one). Propagates :class:`ServerDied`."""
    levels = []
    for li, c in enumerate(ramp):
        if before_level is not None:
            before_level(li, c)
        lv = run_level(send_fn, c, level_requests)
        levels.append(lv)
        log(f"serve_load: concurrency {c:>4}  qps {lv['qps']:>9.1f}  "
            f"p50/p95/p99 {lv['p50_ms']:.1f}/{lv['p95_ms']:.1f}/"
            f"{lv['p99_ms']:.1f} ms  errors {lv['errors']}")
    return levels


# ---------------------------------------------------------------------------
# artifacts
# ---------------------------------------------------------------------------

def _hist_quantile(buckets, count, q):
    """Prometheus-style quantile estimate from a cumulative bucket dict
    (upper bound of the first bucket covering the target rank; the
    largest finite bound stands in for +Inf)."""
    target = q * count
    finite = sorted(((float(le), c) for le, c in buckets.items()
                     if le not in ("+Inf", "inf")), key=lambda x: x[0])
    for le, c in finite:
        if c >= target:
            return le
    return finite[-1][0] if finite else 0.0


def merge_serving_stats(snaps) -> dict:
    """Merge per-replica ModelServer ``/stats`` snapshots into one
    fleet-wide serving section (the --fleet path: spawned replicas
    each own a metrics registry, so the aggregate must be computed from
    their exported snapshots). Counters sum; the latency histograms —
    identical bucket bounds, same histogram family in every process —
    merge by summing cumulative counts per bound, with percentiles
    re-estimated from the merged buckets."""
    merged = {}
    hist = {"count": 0, "sum": 0.0, "buckets": {}}
    mins, maxs = [], []
    for s in snaps:
        for k, v in s.items():
            if k == "serving.latency_ms":
                continue
            if k.startswith("serving.") and isinstance(v, (int, float)) \
                    and not isinstance(v, bool):
                merged[k] = merged.get(k, 0) + v
        h = s.get("serving.latency_ms")
        if isinstance(h, dict):
            hist["count"] += h.get("count", 0)
            hist["sum"] += h.get("sum", 0.0)
            if h.get("min") is not None:
                mins.append(h["min"])
            if h.get("max") is not None:
                maxs.append(h["max"])
            for le, c in (h.get("buckets") or {}).items():
                hist["buckets"][le] = hist["buckets"].get(le, 0) + c
    if mins:
        hist["min"] = min(mins)
    if maxs:
        hist["max"] = max(maxs)
    if hist["count"]:
        for key, q in (("p50", 0.50), ("p95", 0.95), ("p99", 0.99)):
            hist[key] = _hist_quantile(hist["buckets"], hist["count"], q)
    if hist["buckets"]:
        merged["serving.latency_ms"] = hist
    batches = merged.get("serving.batches", 0)
    merged["batch_fill"] = (
        merged.get("serving.batched_requests", 0) / batches
        if batches else 0.0)
    return merged


def build_result(model_name: str, levels, knee_idx: int, reason: str,
                 server_stats: dict, servescope_extra=None,
                 devicescope_extra=None, meta=None) -> dict:
    """Assemble the BENCH json: value = QPS at the knee, the standard
    ``extra.serving`` section (percentiles AT the knee, accounting from
    the server's cumulative snapshot), the sweep table, and the
    attribution."""
    knee = levels[knee_idx]
    hist = server_stats.get("serving.latency_ms")
    serving = {
        "model": model_name,
        "clients": knee["concurrency"],
        "requests": int(server_stats.get("serving.requests", 0)),
        "responses": int(server_stats.get("serving.responses", 0)),
        "batches": int(server_stats.get("serving.batches", 0)),
        "batch_fill": round(float(server_stats.get("batch_fill", 0.0)), 3),
        "rejected_queue_full":
            int(server_stats.get("serving.rejected_queue_full", 0)),
        "rejected_deadline":
            int(server_stats.get("serving.rejected_deadline", 0)),
        "rejected_deadline_post_batch":
            int(server_stats.get("serving.rejected_deadline_post_batch",
                                 0)),
        "rejected_invalid":
            int(server_stats.get("serving.rejected_invalid", 0)),
        "slotted_admissions":
            int(server_stats.get("serving.slotted_admissions", 0)),
        "qps": knee["qps"],
        "p50_ms": knee["p50_ms"],
        "p95_ms": knee["p95_ms"],
        "p99_ms": knee["p99_ms"],
        "latency_ms": hist if isinstance(hist, dict) else None,
    }
    extra = {
        "model": f"serve_load_{model_name}",
        "batch": None,
        "dtype": "float32",
        "serving": serving,
        "serve_load": {
            "levels": levels,
            "knee_index": knee_idx,
            "knee_reason": reason,
            "knee_concurrency": knee["concurrency"],
            "qps_at_knee": knee["qps"],
            "p99_at_knee_ms": knee["p99_ms"],
        },
    }
    if servescope_extra is not None:
        extra["servescope"] = servescope_extra
    if devicescope_extra is not None:
        extra["devicescope"] = devicescope_extra
    if meta:
        extra.update(meta)
    return {
        "metric": f"serve_load_{model_name}_qps_at_knee",
        "value": knee["qps"],
        "unit": "requests/sec",
        "vs_baseline": None,
        "extra": extra,
    }


def read_event_records(path, name=None) -> list:
    """Every parsed record of an ``mxtpu.events`` JSONL file, optionally
    filtered by record ``name``. Unlike the collector's bounded live
    tail this reads the WHOLE file: the harness owns these files and
    they are sweep-sized. IO errors yield ``[]`` — post-run accounting,
    not truth."""
    out = []
    try:
        with open(path, encoding="utf-8") as f:
            for ln in f:
                try:
                    rec = json.loads(ln)
                except ValueError:
                    continue
                if isinstance(rec, dict) and (name is None
                                              or rec.get("name") == name):
                    out.append(rec)
    except OSError:
        pass
    return out


def build_fleetscope_extra(client_minted: int, router_records,
                           replica_records) -> dict:
    """Assemble the ``extra.fleetscope`` BENCH section from router-side
    ``fleetscope.request`` records and replica-side ``serving.request``
    records (the shape ``check_fleetscope_extra`` enforces).

    * ``sampled`` — router-observed SUCCESSFUL forwards (status 200):
      the join denominator;
    * ``joined`` — sampled traces whose replica-side span arrived;
      ``unjoined_forwards`` is the remainder, counted — never guessed;
    * ``wire_gap_ms`` — per joined trace, router ``forward_ms`` minus
      replica ``e2e_ms``. Both are perf_counter DURATIONS, so the
      difference is clock-skew free (docs/fleetscope.md);
    * ``per_replica`` / ``replica_spread`` — replica-observed trace p99
      per replica and max/median across them (the straggler signal the
      pod view renders)."""
    from incubator_mxnet_tpu.fleetscope import join_traces
    traces = join_traces(router_records, replica_records)
    sampled = joined = 0
    gaps, by_rep = [], {}
    for slot in traces.values():
        rtr = slot["router"]
        if rtr is None:
            continue
        rargs = rtr.get("args") or {}
        if rargs.get("status") != 200:
            continue
        sampled += 1
        rep = slot["replica"]
        if rep is None:
            continue
        joined += 1
        agg = by_rep.setdefault(slot["replica_name"] or "?",
                                {"n": 0, "e2e": [], "gaps": []})
        agg["n"] += 1
        pargs = rep.get("args") or {}
        e2e, fw = pargs.get("e2e_ms"), rargs.get("forward_ms")
        if isinstance(e2e, (int, float)):
            agg["e2e"].append(float(e2e))
            if isinstance(fw, (int, float)):
                gap = float(fw) - float(e2e)
                gaps.append(gap)
                agg["gaps"].append(gap)
    out = {
        "client_minted": int(client_minted),
        "sampled": sampled,
        "joined": joined,
        "unjoined_forwards": sampled - joined,
        "join_rate": round(joined / sampled, 6) if sampled else 0.0,
    }
    if gaps:
        gaps.sort()
        out["wire_gap_ms"] = {k: round(_percentile(gaps, q), 3)
                              for k, q in (("p50", 0.50), ("p95", 0.95),
                                           ("p99", 0.99))}
    rows, p99s = [], []
    for name in sorted(by_rep):
        agg = by_rep[name]
        row = {"name": name, "traces": agg["n"]}
        if agg["e2e"]:
            row["e2e_p99_ms"] = round(
                _percentile(sorted(agg["e2e"]), 0.99), 3)
            p99s.append(row["e2e_p99_ms"])
        if agg["gaps"]:
            row["wire_gap_p50_ms"] = round(
                _percentile(sorted(agg["gaps"]), 0.50), 3)
        rows.append(row)
    if rows:
        out["per_replica"] = rows
    if p99s:
        p99s.sort()
        # lower median: with 2 replicas the upper median IS the max and
        # the straggler ratio would pin at 1.0
        median = p99s[(len(p99s) - 1) // 2]
        if median > 0:
            out["replica_spread"] = round(p99s[-1] / median, 4)
    return out


def write_env_failure(path: str, metric: str, error: str) -> dict:
    """The self-describing environment-failure artifact:
    perf_regress skips it, the trajectory stays
    unpoisoned, and the error travels with the file."""
    doc = {"status": "env_failure", "metric": metric, "value": 0.0,
           "unit": "requests/sec", "error": str(error)[:500],
           "ts": time.time()}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return doc


# ---------------------------------------------------------------------------
# main (backend-touching; imports deferred so helpers stay unit-testable)
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="closed-loop serving load harness (ramped "
                    "concurrency, saturation knee, BENCH json)")
    ap.add_argument("--model", default=os.environ.get(
        "BENCH_SERVING_MODEL", "lenet"))
    ap.add_argument("--ramp", default=DEFAULT_RAMP,
                    help=f"comma-separated concurrency ladder "
                         f"(default {DEFAULT_RAMP})")
    ap.add_argument("--level-requests", type=int, default=128,
                    help="closed-loop requests per ramp level")
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--timeout-ms", type=float, default=60000.0,
                    help="per-request deadline handed to the server")
    ap.add_argument("--sample", default=None,
                    help="servescope sampling (rate in (0,1] or an "
                         "every-Nth stride; default: trace everything)")
    ap.add_argument("--devicescope", type=int, default=0,
                    help="capture a devicescope window over N dispatches "
                         "of the final ramp level (0 = off)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="drive an N-replica fleet behind the Router "
                         "instead of one ModelServer (0 = off)")
    ap.add_argument("--fleet-cache", default=None,
                    help="shared AOT compile-cache dir for --fleet "
                         "(default: <out>_cache)")
    ap.add_argument("--out", default="/tmp/mxtpu_serve_load.json")
    ap.add_argument("--events", default=None,
                    help="write the mxtpu.events/1 request/batch stream "
                         "here (default: alongside --out)")
    args = ap.parse_args(argv)

    ramp = sorted({int(t) for t in args.ramp.split(",") if t.strip()})
    if not ramp:
        print("serve_load: empty --ramp", file=sys.stderr)
        return 2
    fleet_n = max(0, int(args.fleet))
    bench_name = (f"{args.model}_fleet{fleet_n}" if fleet_n
                  else args.model)
    metric = f"serve_load_{bench_name}_qps_at_knee"
    events_path = args.events or (
        os.path.splitext(args.out)[0] + "_events.jsonl")

    import numpy as np

    # runnable from anywhere: the repo root is this file's parent dir
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if _root not in sys.path:
        sys.path.insert(0, _root)
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import (commscope, devicescope, fleetscope,
                                     perfscope, servescope, serving)
    from incubator_mxnet_tpu.healthmon import events as hm_events
    from incubator_mxnet_tpu.models import get_model

    shapes = {"lenet": (1, 28, 28), "resnet50_v1": (224, 224, 3)}
    if args.model not in shapes:
        print(f"serve_load: no serving shape for {args.model!r} "
              f"(choose from {sorted(shapes)})", file=sys.stderr)
        return 2
    shape = shapes[args.model]

    # arm the observability stack: perfscope+commscope so every bucket
    # carries its roofline + resharding verdict, servescope for the
    # attribution, and the event log for the correlation stream
    perfscope.enable()
    commscope.enable()
    servescope.enable(sample=args.sample)
    # fleetscope: every client request carries a minted traceparent, and
    # the router/server side joins it (extra.fleetscope reports the rate)
    fleetscope.enable()
    run_id = f"serveload-{os.getpid()}-{int(time.time())}"
    hm_events.open_log(events_path, run_id=run_id, rank=0)

    kwargs = {"layout": "NHWC"} if args.model.startswith("resnet") else {}

    def make_model(compile_cache=None):
        net = get_model(args.model,
                        classes=10 if args.model == "lenet" else 1000,
                        **kwargs)
        net.initialize(init=mx.init.Xavier())
        return net.freeze(input_shape=shape, compile_cache=compile_cache)

    rset = router = srv = coll = None
    replica_events_tmpl = (os.path.splitext(events_path)[0]
                           + "_replica_{pid}.jsonl")
    buckets_list = []
    if fleet_n:
        from incubator_mxnet_tpu import fleet as fleet_mod
        cache_dir = args.fleet_cache or \
            (os.path.splitext(args.out)[0] + "_cache")
        # spawned workers: each replica is its own PROCESS (own GIL —
        # in-process replicas cannot out-scale one bare server), warmed
        # through the shared on-disk AOT cache. servescope/fleetscope in
        # the spec arm replica-side spans + trace joining; export gives
        # the fleetscope collector its pull target; {pid} keeps the
        # per-replica events logs apart (worker substitutes its PID)
        spec = {"model": args.model,
                "classes": 10 if args.model == "lenet" else 1000,
                "model_kwargs": kwargs,
                "input_shape": list(shape),
                "batcher": "continuous",
                "cache_dir": cache_dir,
                "servescope": True,
                "fleetscope": True,
                "export": True,
                "events": {"path": replica_events_tmpl,
                           "run_id": run_id, "rank": 0},
                "server": {"max_delay_ms": args.max_delay_ms,
                           "queue_limit": max(256, ramp[-1] * 4),
                           "default_timeout_ms": args.timeout_ms}}
        print(f"serve_load: spawning {fleet_n} {args.model} worker "
              f"processes (shared AOT cache at {cache_dir})")
        rset = fleet_mod.ReplicaSet(spec, n=fleet_n, spawn=True)
        rset.start()
        router = fleet_mod.Router(rset)
        host, port = router.start()
        targets = [{"name": rep.name, "host": rep.host,
                    "port": rep.diag_port}
                   for rep in rset.replicas if rep.diag_port]
        if targets:
            # clock-offset estimation + live counters over each worker's
            # diagnostics.export endpoint, for the whole sweep
            coll = fleetscope.Collector(targets, interval_s=1.0).start()
        try:
            _, r0 = rset.replicas[0].http_get("/stats")
            buckets_list = list(r0.get("buckets") or [])
        except Exception:  # noqa: BLE001 — cosmetic only
            pass
        print(f"serve_load: {args.model} fleet({fleet_n}) router at "
              f"{router.address} buckets={buckets_list} ramp={ramp} "
              f"x{args.level_requests} req/level")
    else:
        print(f"serve_load: freezing {args.model} (AOT compile + warmup)")
        frozen = make_model()
        srv = serving.ModelServer(
            frozen, max_delay_ms=args.max_delay_ms,
            queue_limit=max(256, ramp[-1] * 4),
            default_timeout_ms=args.timeout_ms)
        host, port = srv.start()
        buckets_list = list(frozen.buckets)
        print(f"serve_load: {args.model} at {srv.address} "
              f"buckets={frozen.buckets} ramp={ramp} "
              f"x{args.level_requests} req/level")

    import http.client
    rng = np.random.RandomState(0)
    samples = rng.rand(64, *shape).astype(np.float32)
    bodies = [json.dumps({"data": s.tolist(),
                          "timeout_ms": args.timeout_ms}).encode()
              for s in samples]

    # keep-alive connection per client thread (the wrk/hey load-gen
    # convention): a closed-loop client measures the SERVING path, not
    # per-request TCP connect — without reuse, a concurrent burst
    # overflows accept backlogs and the "p99" becomes kernel SYN
    # retransmit timeouts (measured: exact 1s/3s modes)
    tls = threading.local()

    # --fleet: client-observed per-replica latencies, keyed off the
    # `replica` tag the router stamps into every reply (the ONLY place
    # per-replica p99 exists: the in-process replicas share one metrics
    # registry, so server-side counters are already fleet-aggregated)
    fleet_lock = threading.Lock()
    fleet_lats = {}
    # client-side trace accounting: every request mints a fresh
    # traceparent; "echo" counts replies whose trace_id matches (the
    # single-server join — fleet mode joins the events files instead)
    fs_counts = {"minted": 0, "ok": 0, "echo": 0}

    def send(i):
        conn = getattr(tls, "conn", None)
        if conn is None:
            conn = tls.conn = http.client.HTTPConnection(
                host, port, timeout=120)
            conn.connect()
            import socket as _socket
            conn.sock.setsockopt(_socket.IPPROTO_TCP,
                                 _socket.TCP_NODELAY, 1)
        headers = {"Content-Type": "application/json"}
        tp = None
        if fleetscope.enabled():
            tp = fleetscope.mint()
            headers["traceparent"] = tp.header()
            with fleet_lock:
                fs_counts["minted"] += 1
        t0 = time.perf_counter()
        try:
            conn.request("POST", "/predict", body=bodies[i % len(bodies)],
                         headers=headers)
            r = conn.getresponse()
            data = r.read()
            if r.status != 200:
                raise RuntimeError(f"HTTP {r.status}: {data[:120]!r}")
        except Exception:
            try:
                conn.close()
            finally:
                tls.conn = None
            raise
        doc = None
        if fleet_n or tp is not None:
            try:
                doc = json.loads(data)
            except ValueError:
                doc = None
        if tp is not None:
            with fleet_lock:
                fs_counts["ok"] += 1
                if isinstance(doc, dict) \
                        and doc.get("trace_id") == tp.trace_id:
                    fs_counts["echo"] += 1
        if fleet_n:
            dt_ms = (time.perf_counter() - t0) * 1e3
            rep = doc.get("replica") if isinstance(doc, dict) else None
            if rep:
                with fleet_lock:
                    fleet_lats.setdefault(rep, []).append(dt_ms)

    win = None

    def _arm_window(li, c):
        # measured device window over the most loaded level: the
        # attribution's device_exec upgrades to measured(profile)
        # when it completes
        nonlocal win
        if args.devicescope > 0 and li == len(ramp) - 1:
            win = devicescope.capture(steps=args.devicescope).start()

    try:
        levels = sweep(send, ramp, args.level_requests,
                       before_level=_arm_window)
    except ServerDied as e:
        print(f"serve_load: SERVER DIED — writing env_failure artifact: "
              f"{e}", file=sys.stderr)
        write_env_failure(args.out, metric, str(e))
        hm_events.close_log()
        if coll is not None:
            coll.stop()
        if router is not None:
            router.stop()
        if rset is not None:
            rset.stop(drain=False)
        return 0
    finally:
        if win is not None:
            win.stop()

    knee_idx, reason = find_knee(levels)
    # ONE cumulative snapshot per replica. Spawned replicas each own a
    # metrics registry, so the fleet-wide serving section is the MERGE
    # of their /stats exports (counters sum, histograms merge by
    # bucket).
    if fleet_n:
        snaps = []
        for rep in rset.replicas:
            try:
                code, s = rep.http_get("/stats")
                if code == 200:
                    snaps.append(s)
            except Exception as e:  # noqa: BLE001 — partial fleet stats
                print(f"serve_load: /stats from {rep.name} failed: {e}",
                      file=sys.stderr)
        stats = merge_serving_stats(snaps)
    else:
        stats = srv.stats()
    fleet_meta = None
    if fleet_n:
        router_stats = router.stats()
        sweep_wall = sum(lv["wall_s"] for lv in levels) or 1.0
        rows = []
        for rep in rset.replicas:
            lats = sorted(fleet_lats.get(rep.name, []))
            row = {"name": rep.name, "requests": len(lats),
                   "qps": round(len(lats) / sweep_wall, 2),
                   "dispatched": router_stats.get(
                       "dispatch_counts", {}).get(rep.name, 0)}
            if lats:
                row.update(p50_ms=round(_percentile(lats, 0.50), 3),
                           p95_ms=round(_percentile(lats, 0.95), 3),
                           p99_ms=round(_percentile(lats, 0.99), 3))
            rows.append(row)
        fleet_meta = {
            "replicas": fleet_n,
            "batcher": "continuous",
            "cache_dir": cache_dir,
            "per_replica": rows,
            "dispatch_counts": router_stats.get("dispatch_counts"),
            "dispatch_imbalance": round(
                router_stats.get("dispatch_imbalance", 0.0), 4),
            "routed": int(router_stats.get("fleet.routed", 0)),
            "routed_errors": int(
                router_stats.get("fleet.routed_errors", 0)),
            "no_replica_available": int(
                router_stats.get("fleet.no_replica_available", 0)),
            # worker-reported warmup cache traffic (each worker owns
            # its registry; the readiness handshake carries these)
            "compile_cache": {
                key: sum(int((rep.cache_stats or {}).get(key, 0))
                         for rep in rset.replicas)
                for key in ("hits", "misses", "stores")
            },
        }
    # spawned replicas trace their own spans in their own processes —
    # the parent has no servescope data to attribute in fleet mode
    servescope_extra = None if fleet_n else servescope.bench_extra()
    ds_extra = devicescope.bench_extra() if win is not None else None
    # child PIDs locate the per-replica events files; grab them before
    # the processes are reaped
    replica_pids = []
    if fleet_n:
        replica_pids = [(rep.name, rep.proc.pid)
                        for rep in rset.replicas if rep.proc is not None]
        if coll is not None:
            coll.stop()
        router.stop()
        rset.stop(drain=True)
    else:
        srv.stop()
    hm_events.close_log()

    # join the traces: fleet mode joins the router's fleetscope.request
    # records (harness events file) against each worker's
    # serving.request records; single-server mode uses the reply echo
    # (the server runs in-process — there is no wire gap to measure)
    fs_extra = None
    if fleetscope.enabled():
        if fleet_n:
            replica_recs = []
            for _name, pid in replica_pids:
                replica_recs += read_event_records(
                    replica_events_tmpl.replace("{pid}", str(pid)),
                    "serving.request")
            fs_extra = build_fleetscope_extra(
                fs_counts["minted"],
                read_event_records(events_path, "fleetscope.request"),
                replica_recs)
            if coll is not None:
                fs_extra["collector"] = coll.snapshot()
        else:
            ok, echo = fs_counts["ok"], fs_counts["echo"]
            fs_extra = {
                "client_minted": fs_counts["minted"],
                "sampled": ok,
                "joined": echo,
                "unjoined_forwards": ok - echo,
                "join_rate": round(echo / ok, 6) if ok else 0.0,
            }

    meta = {"run_id": run_id, "events_file": events_path,
            "buckets": buckets_list,
            "max_delay_ms": args.max_delay_ms,
            "level_requests": args.level_requests}
    if fleet_meta is not None:
        meta["fleet"] = fleet_meta
    if fs_extra is not None:
        meta["fleetscope"] = fs_extra
    doc = build_result(bench_name, levels, knee_idx, reason, stats,
                       servescope_extra=servescope_extra,
                       devicescope_extra=ds_extra,
                       meta=meta)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    knee = levels[knee_idx]
    print(f"serve_load: knee at concurrency {knee['concurrency']} "
          f"({reason})")
    print(f"serve_load: {doc['metric']} = {doc['value']} requests/sec, "
          f"p99 {knee['p99_ms']:.1f} ms")
    att = (servescope_extra or {}).get("advice")
    if att:
        print(f"serve_load: attribution: {att}")
    if fs_extra is not None:
        gap = (fs_extra.get("wire_gap_ms") or {}).get("p50")
        print(f"serve_load: fleetscope: {fs_extra['joined']}/"
              f"{fs_extra['sampled']} traces joined (join_rate "
              f"{fs_extra['join_rate']:.3f}, "
              f"{fs_extra['client_minted']} client-minted"
              + (f", wire gap p50 {gap:.2f} ms" if gap is not None
                 else "") + ")")
    print(f"serve_load: wrote {args.out} (events: {events_path})")

    # self-check: the artifact must validate before anything gates on it
    # (fleet mode: every replica's events file too)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_check
    errors = trace_check.check_file(args.out) \
        + trace_check.check_file(events_path)
    for _name, pid in replica_pids:
        p = replica_events_tmpl.replace("{pid}", str(pid))
        if os.path.exists(p):
            errors += trace_check.check_file(p)
    if errors:
        for e in errors:
            print(f"serve_load: ARTIFACT INVALID: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
