"""Trace ingestion: Chrome-trace artifacts → measured device truth.

``jax.profiler.trace`` (driven by :mod:`.window`) writes a TensorBoard
profile directory whose ``<host>.trace.json.gz`` is a Chrome trace-event
file: ``M`` metadata events naming processes/threads, and ``X`` complete
events for everything the backend timed. The events that matter here are
the **device-op events** — on XLA:CPU they run on the client/Eigen
threadpool lanes and carry ``args.hlo_op``/``args.hlo_module``; on TPU
they additionally live under ``/device:TPU:n`` processes. Everything
else (the python lane, ``TfrtCpuBuffer::Await``, threadpool bookkeeping)
is host machinery.

From those events this module derives the measured ground truth the
analytic layers (perfscope's probe, commscope's ring estimates) are
reconciled against:

* **busy fraction** — the union of device-op intervals across every
  device lane, over the host-measured window wall: the chip was doing
  *something* during that fraction of the window. Union, not sum: four
  fake devices (or four TPU cores) running the same all-reduce
  concurrently are one busy interval, comparable with wall-clock step
  components.
* **top-K ops** — per-op device time (summed across lanes — the
  attribution view: "where do device-milliseconds go"), joined to
  perfscope's program table via the ``hlo_module`` name so each hot
  fusion carries its roofline verdict.
* **measured collectives** — device events whose op name matches the
  commscope kind taxonomy, as a union time (comparable with the step
  budget's ``collective`` component) and per kind, with the mesh-axis
  attribution joined from commscope's static inventory of the same
  program.
* **idle-gap taxonomy** — gaps in the union timeline, histogrammed, and
  the window's total idle classified input-starved / dispatch-serialized
  / host-gap from the ``io.wait_ms`` and dispatch-wall counter deltas
  the window snapshotted.

Every entry point is never-raise by contract: a malformed artifact (the
profiler was killed mid-write, an XLA upgrade renamed a lane) degrades
to an empty summary, not a crashed run. tests/test_devicescope.py
pins the edge cases (empty trace, single event, overlapping lanes,
missing metadata) against a checked-in real XLA:CPU artifact.
"""
from __future__ import annotations

import gzip
import json
import os
import re

from ..commscope.hlo import COLLECTIVE_KINDS as _CS_KINDS

__all__ = ["find_trace_file", "load_trace_events", "device_events",
           "union_intervals", "collective_kind_of", "summarize",
           "GAP_BUCKETS_MS"]

# gap-duration histogram bucket upper bounds (milliseconds) + overflow
GAP_BUCKETS_MS = (0.1, 1.0, 10.0, 100.0)

# measured collective op kinds ARE commscope's closed taxonomy (one
# home; a kind added there is measured here automatically), prefix-
# matched against the HLO op name ("all-reduce.5", "all-gather-start.2"
# and XLA:CPU's plain "all-to-all" all resolve). "other" is a bucket,
# not a spelling — nothing to prefix-match.
_COLLECTIVE_PREFIXES = tuple(k for k in _CS_KINDS if k != "other")

# "dot.3", "reduce.58.clone", "fusion.12.remat" → one op family each
_TRAILING_ID = re.compile(r"(\.(\d+|clone|remat\d*))+$")


def find_trace_file(path):
    """Newest ``*.trace.json(.gz)`` under ``path`` (a profile logdir),
    or ``path`` itself when it already names a file. None when nothing
    is there — the profiler wrote no artifact."""
    try:
        if os.path.isfile(path):
            return path
        best, best_mtime = None, -1.0
        for root, _dirs, files in os.walk(path):
            for fn in files:
                if fn.endswith((".trace.json.gz", ".trace.json")):
                    p = os.path.join(root, fn)
                    m = os.path.getmtime(p)
                    if m > best_mtime:
                        best, best_mtime = p, m
        return best
    except Exception:  # noqa: BLE001 — discovery must never raise
        return None


def load_trace_events(path):
    """The trace-event list from one artifact (file or profile logdir).
    Accepts both container shapes (bare list / ``{"traceEvents": []}``)
    and gzipped or plain JSON. Returns ``(events, trace_file)``;
    ``([], None)`` when nothing loadable is found."""
    f = find_trace_file(path) if path else None
    if not f:
        return [], None
    try:
        opener = gzip.open if f.endswith(".gz") else open
        with opener(f, "rt") as fh:
            doc = json.load(fh)
        if isinstance(doc, dict):
            doc = doc.get("traceEvents")
        if not isinstance(doc, list):
            return [], f
        return [e for e in doc if isinstance(e, dict)], f
    except Exception:  # noqa: BLE001 — a torn artifact is not a crash
        return [], f


def _num(x):
    return x if isinstance(x, (int, float)) and not isinstance(x, bool) \
        else None


def device_events(events):
    """Split a raw event list into (device_ops, lane_meta).

    A device-op event is an ``X`` event that carries ``args.hlo_op`` or
    lives under a process whose name contains ``/device:`` (the TPU
    layout; XLA:CPU op events run on host threadpool lanes and are
    recognized by their args). Returned ops are normalized dicts
    ``{lane, ts, dur, name, op, module}`` with ts/dur in microseconds;
    lane_meta maps ``(pid, tid) -> {process, thread}``."""
    procs, threads = {}, {}
    for e in events:
        try:
            if e.get("ph") != "M":
                continue
            args = e.get("args") or {}
            if e.get("name") == "process_name":
                procs[e.get("pid")] = str(args.get("name", ""))
            elif e.get("name") == "thread_name":
                threads[(e.get("pid"), e.get("tid"))] = \
                    str(args.get("name", ""))
        except Exception:  # noqa: BLE001
            continue
    ops, lanes = [], {}
    for e in events:
        try:
            if e.get("ph") != "X":
                continue
            ts, dur = _num(e.get("ts")), _num(e.get("dur"))
            if ts is None or dur is None or dur < 0:
                continue
            args = e.get("args") or {}
            if not isinstance(args, dict):
                args = {}
            pid, tid = e.get("pid"), e.get("tid")
            proc = procs.get(pid, "")
            is_dev = "hlo_op" in args or "/device:" in proc
            if not is_dev:
                continue
            name = str(e.get("name") or args.get("hlo_op") or "?")
            lane = (pid, tid)
            lanes.setdefault(lane, {
                "pid": pid, "tid": tid, "process": proc,
                "thread": threads.get(lane, "")})
            ops.append({"lane": lane, "ts": float(ts), "dur": float(dur),
                        "name": name,
                        "op": _TRAILING_ID.sub("", name),
                        "module": args.get("hlo_module")})
        except Exception:  # noqa: BLE001 — one bad event never sinks a trace
            continue
    return ops, lanes


def union_intervals(intervals):
    """Merge ``(start, end)`` pairs; returns (merged_list, total_length).
    Tolerates unordered and overlapping input (concurrent lanes)."""
    ivs = sorted((float(a), float(b)) for a, b in intervals if b > a)
    merged, total = [], 0.0
    for a, b in ivs:
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                total += b - merged[-1][1]
                merged[-1][1] = b
        else:
            merged.append([a, b])
            total += b - a
    return [(a, b) for a, b in merged], total


def collective_kind_of(op_name):
    """The commscope kind a device-op name measures, or None for a
    non-collective op."""
    n = str(op_name)
    for k in _COLLECTIVE_PREFIXES:
        if n.startswith(k):
            return k
    return None


def _gap_histogram(gaps_ms):
    hist = {str(b): 0 for b in GAP_BUCKETS_MS}
    hist["+Inf"] = 0
    for g in gaps_ms:
        for b in GAP_BUCKETS_MS:
            if g <= b:
                hist[str(b)] += 1
                break
        else:
            hist["+Inf"] += 1
    return hist


def _starved_split(input_starved_ms, counters_delta):
    """Split the ``input_starved`` bucket into disk / decode / transfer
    attribution from the io pipeline's per-stage wall deltas
    (``io.read_ms`` / ``io.decode_ms`` / ``io.put_ms``).

    The stage walls are not spans of the idle gaps themselves — decode
    runs on N workers concurrently with compute — so they are used as
    attribution WEIGHTS: each stage's share of the starved time is its
    share of the summed stage wall, scaled so the split sums to
    ``input_starved_ms``. Returns None when there is nothing to split
    (no starvation, or a pre-pipeline artifact with no stage walls) —
    absent, not zeros, so old artifacts stay schema-stable."""
    if not input_starved_ms or input_starved_ms <= 0:
        return None
    read = max(0.0, float(counters_delta.get("io_read_ms") or 0.0))
    decode = max(0.0, float(counters_delta.get("io_decode_ms") or 0.0))
    put = max(0.0, float(counters_delta.get("io_put_ms") or 0.0))
    total = read + decode + put
    if total <= 0:
        return None
    shares = {"read_ms": read, "decode_ms": decode, "transfer_ms": put}
    dominant = {"read_ms": "read", "decode_ms": "decode",
                "transfer_ms": "transfer"}[max(shares, key=shares.get)]
    return {
        **{k: round(v / total * input_starved_ms, 4)
           for k, v in shares.items()},
        "dominant": dominant,
    }


def _axis_map_for(program, comms_programs):
    """kind -> mesh axis for one program, from commscope's static
    inventory (None when ambiguous: two axes running the same kind).
    Delegates to commscope's :func:`axis_by_kind` — one home for the
    join rule — with a record-matching shim over the caller-provided
    inventory snapshot (the pure-data path fixture tests drive)."""
    recs = [r for r in comms_programs or []
            if isinstance(r, dict) and r.get("name") == program]
    if not recs:
        return {}
    try:
        from ..commscope.extract import axis_by_kind
    except Exception:  # noqa: BLE001 — ingest stays standalone-usable
        return {}
    out = {}
    for rec in recs:
        for k, ax in axis_by_kind(rec).items():
            if k in out and out[k] != ax:
                out[k] = None          # ambiguous across records
            else:
                out[k] = ax
    return out


def summarize(events, wall_ms, steps, counters_delta=None,
              program_map=None, programs=None, comms_programs=None,
              top_k=10):
    """Derive the measured-truth summary from one window's raw events.

    wall_ms / steps: the HOST-measured window wall and the step count
    the caller marked — the denominators every per-step number uses.
    counters_delta: ``{"io_wait_ms", "dispatch_ms"}`` deltas over the
    window (gap taxonomy inputs), plus the optional io stage walls
    (``io_read_ms`` / ``io_decode_ms`` / ``io_put_ms``) that split the
    input_starved bucket into disk/decode/transfer attribution. program_map: ``hlo_module name ->
    perfscope program name`` (the join key recorded at compile capture);
    programs: perfscope's program table (roofline verdicts);
    comms_programs: commscope's inventory (mesh-axis attribution).
    Never raises."""
    try:
        return _summarize(events, wall_ms, steps, counters_delta or {},
                          program_map or {}, programs or [],
                          comms_programs or [], int(top_k))
    except Exception as e:  # noqa: BLE001 — a parse bug costs the summary,
        return {                       # never the run that asked for it
            "busy_fraction": None, "busy_ms": 0.0, "idle_ms": None,
            "per_step": None, "lanes": [], "top_ops": [],
            "collectives": {"union_ms": 0.0, "sum_ms": 0.0, "by_kind": []},
            "gaps": None, "device_events": 0,
            "error": f"{type(e).__name__}: {e}"[:200],
        }


def _summarize(events, wall_ms, steps, counters_delta, program_map,
               programs, comms_programs, top_k):
    ops, lanes = device_events(events)
    steps = max(1, int(steps or 1))
    wall = float(wall_ms) if _num(wall_ms) else None

    busy_iv, busy_us = union_intervals(
        (o["ts"], o["ts"] + o["dur"]) for o in ops)
    busy_ms = busy_us / 1e3
    # per-lane busy (diagnostic detail, not the headline denominator):
    # one grouping pass, not a rescan of the op list per lane
    ops_by_lane: "dict[tuple, list]" = {}
    for o in ops:
        ops_by_lane.setdefault(o["lane"], []).append(o)
    lane_rows = []
    for lane, meta in lanes.items():
        lane_ops = ops_by_lane.get(lane, [])
        _, lb = union_intervals((o["ts"], o["ts"] + o["dur"])
                                for o in lane_ops)
        lane_rows.append(dict(meta, events=len(lane_ops),
                              busy_ms=round(lb / 1e3, 4)))
    lane_rows.sort(key=lambda r: -r["busy_ms"])

    # top-K ops by summed device time, joined to the roofline table
    by_op = {}
    verdict_by_name = {p.get("name"): p.get("verdict")
                       for p in programs if isinstance(p, dict)}
    for o in ops:
        slot = by_op.setdefault((o["op"], o["module"]),
                                {"op": o["op"], "module": o["module"],
                                 "count": 0, "total_us": 0.0})
        slot["count"] += 1
        slot["total_us"] += o["dur"]
    top = sorted(by_op.values(), key=lambda s: -s["total_us"])[:top_k]
    top_ops = []
    for s in top:
        prog = program_map.get(s["module"]) if s["module"] else None
        top_ops.append({
            "op": s["op"], "count": s["count"],
            "total_ms": round(s["total_us"] / 1e3, 4),
            "mean_us": round(s["total_us"] / s["count"], 3),
            "module": s["module"], "program": prog,
            "verdict": verdict_by_name.get(prog),
        })

    # measured collectives: union time (step-budget-comparable) + per kind
    coll_ops = [(o, collective_kind_of(o["op"])) for o in ops]
    coll_ops = [(o, k) for o, k in coll_ops if k]
    _, coll_union_us = union_intervals(
        (o["ts"], o["ts"] + o["dur"]) for o, _k in coll_ops)
    by_kind = {}
    for o, k in coll_ops:
        slot = by_kind.setdefault(k, {"kind": k, "count": 0,
                                      "total_us": 0.0})
        slot["count"] += 1
        slot["total_us"] += o["dur"]
    kind_rows = []
    for k, s in sorted(by_kind.items(), key=lambda kv: -kv[1]["total_us"]):
        # axis join: the program the collective ran in, via module map
        mods = {o["module"] for o, kk in coll_ops if kk == k}
        progs = {program_map.get(m) for m in mods if m}
        axis = None
        if len(progs) == 1:
            axis = _axis_map_for(next(iter(progs)), comms_programs).get(k)
        kind_rows.append({"kind": k, "count": s["count"],
                          "total_ms": round(s["total_us"] / 1e3, 4),
                          "axis": axis})

    # idle gaps inside the device span (union-timeline holes)
    gaps_ms = [(nxt[0] - cur[1]) / 1e3
               for cur, nxt in zip(busy_iv, busy_iv[1:])
               if nxt[0] > cur[1]]
    span_ms = ((busy_iv[-1][1] - busy_iv[0][0]) / 1e3) if busy_iv else 0.0

    denom = wall if wall and wall > 0 else (span_ms or None)
    busy_fraction = None
    idle_ms = None
    gaps = None
    if denom:
        busy_fraction = round(min(1.0, busy_ms / denom), 6)
        idle_ms = max(0.0, denom - busy_ms)
        io_wait = max(0.0, float(counters_delta.get("io_wait_ms") or 0.0))
        disp = max(0.0, float(counters_delta.get("dispatch_ms") or 0.0))
        input_starved = min(idle_ms, io_wait)
        rest = idle_ms - input_starved
        dispatch_serialized = min(rest, disp)
        host_gap = rest - dispatch_serialized
        gaps = {
            "count": len(gaps_ms),
            "total_ms": round(sum(gaps_ms), 4),
            "max_ms": round(max(gaps_ms), 4) if gaps_ms else 0.0,
            "histogram_ms": _gap_histogram(gaps_ms),
            "taxonomy": {
                "input_starved_ms": round(input_starved, 4),
                "dispatch_serialized_ms": round(dispatch_serialized, 4),
                "host_gap_ms": round(host_gap, 4),
            },
        }
        split = _starved_split(input_starved, counters_delta)
        if split is not None:
            gaps["input_starved_split"] = split

    per_step = None
    if denom:
        per_step = {
            "device_busy_ms": round(busy_ms / steps, 4),
            "collective_ms": round(coll_union_us / 1e3 / steps, 4),
            "idle_ms": round(idle_ms / steps, 4),
        }
    return {
        "busy_fraction": busy_fraction,
        "busy_ms": round(busy_ms, 4),
        "idle_ms": round(idle_ms, 4) if idle_ms is not None else None,
        "device_span_ms": round(span_ms, 4),
        "per_step": per_step,
        "lanes": lane_rows,
        "top_ops": top_ops,
        "collectives": {
            "union_ms": round(coll_union_us / 1e3, 4),
            "sum_ms": round(sum(s["total_us"]
                                for s in by_kind.values()) / 1e3, 4),
            "by_kind": kind_rows,
        },
        "gaps": gaps,
        "device_events": len(ops),
    }
