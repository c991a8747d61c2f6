#!/usr/bin/env python
"""Validate observability artifacts produced by this framework:

* **Chrome trace-event JSON** (`profiler.dump()`) — the subset of the
  Trace Event Format that chrome://tracing / Perfetto require to render;
* **flight-recorder dumps** (`diagnostics.flight`) — versioned schema
  (``mxtpu.flight/1``), required header fields, events with monotonic
  non-decreasing timestamps;
* **Prometheus text exposition** (`diagnostics.export.prometheus_text`)
  — metric-name/label/value syntax, `# TYPE` declarations;
* **metrics newline-JSON** (`diagnostics` sampler `metrics.jsonl`) —
  per-line schema, non-decreasing sample timestamps, and MONOTONIC
  counters: any metric declared `kind == "counter"` must never decrease
  across samples (a decrease means a broken registry or a torn read).
  Histogram-kind metrics are validated structurally (cumulative buckets,
  `+Inf` == count) and their observation count must be monotonic;
* **run artifact JSON** (what `tools/serve_load.py` writes) — when the
  result carries an `extra.serving` section, its latency
  histograms, percentiles, and fill-ratio/error accounting are
  structurally validated;
* **structured event logs** (`healthmon.events` / ``mxtpu.events/1``
  JSONL, including `mxdiag merge` output) — per-record schema with the
  run_id/rank/step correlation ids, non-decreasing timestamps;
* **counter families** — any `healthmon/*`, `io/*`, `trainloop/*`,
  `perfscope/*`, `commscope/*`, `devicescope/*`, `servescope/*`,
  `mxlint/*` or `sharding/*` metric appearing in a flight dump or metrics series must
  belong to the known family table with the declared kind (an unknown
  or re-kinded metric means a producer drifted from the documented
  schema). The tables have ONE home —
  `incubator_mxnet_tpu/mxlint/families.py` — which this validator and
  mxlint's `unregistered-counter` rule both derive from.

Usage:
    python tools/trace_check.py FILE [more files ...]

File kind is auto-detected (extension, then content). Exit status 0 iff
every file validates; errors are printed one per line.
`tools/serve_load.py` imports :func:`check_file` and fails the run
on malformed output, so a broken exporter can't silently ship garbage
telemetry.
"""
from __future__ import annotations

import json
import numbers
import re
import sys

__all__ = ["check_trace", "check_events", "check_flight", "check_prom",
           "check_metrics_jsonl", "check_histogram_snapshot",
           "check_bench_json", "check_events_jsonl",
           "check_healthmon_kinds", "check_perfscope_extra",
           "check_commscope_extra", "check_devicescope_extra",
           "check_servescope_extra", "check_serve_load_extra",
           "check_sharding_extra", "check_resilience_extra",
           "check_mxlint_extra", "check_io_extra",
           "check_embedding_extra", "check_fleetscope_extra",
           "check_file"]

FLIGHT_SCHEMA_PREFIX = "mxtpu.flight/"
EVENTS_SCHEMA_PREFIX = "mxtpu.events/"

# The counter-family tables. ONE home: they derive from
# incubator_mxnet_tpu/mxlint/families.py (pure stdlib data, loaded by
# path so this validator needs no framework/jax import) — the same
# source mxlint's `unregistered-counter` rule reads, so the validator
# and the linter cannot disagree. Adding a metric to a governed family
# is one edit THERE; tests/test_mxlint.py fails on drift between these
# module globals and the home tables.
def _load_families():
    import importlib.util
    import os as _os
    here = _os.path.dirname(_os.path.abspath(__file__))
    path = _os.path.join(_os.path.dirname(here), "incubator_mxnet_tpu",
                         "mxlint", "families.py")
    spec = importlib.util.spec_from_file_location(
        "mxtpu_mxlint_families", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_families = (sys.modules.get("incubator_mxnet_tpu.mxlint.families")
             or _load_families())

HEALTHMON_FAMILIES = _families.family_table("healthmon")
# io.* (device prefetcher) + trainloop.* (whole-loop executor) share one
# exported table (docs/trainloop.md documents each metric)
IO_TRAINLOOP_FAMILIES = _families.family_table("io", "trainloop")
SHARDING_FAMILIES = _families.family_table("sharding")
PERFSCOPE_FAMILIES = _families.family_table("perfscope")
COMMSCOPE_FAMILIES = _families.family_table("commscope")
DEVICESCOPE_FAMILIES = _families.family_table("devicescope")
SERVESCOPE_FAMILIES = _families.family_table("servescope")
# memscope.* — static footprints + watermark ring + OOM forensics
# (docs/memscope.md)
MEMSCOPE_FAMILIES = _families.family_table("memscope")
RESILIENCE_FAMILIES = _families.family_table("resilience")
# mxlint.* — the strict-mode jit-program auditor (docs/mxlint.md)
MXLINT_FAMILIES = _families.family_table("mxlint")
# fleet.* — continuous batching + replica fleet (docs/serving.md)
FLEET_FAMILIES = _families.family_table("fleet")
# embedding.* — sharded tables, dedup lookup, row-sparse updates
# (docs/embedding.md)
EMBEDDING_FAMILIES = _families.family_table("embedding")
# fleetscope.* — cross-process trace context + clock-aligned collection
# (docs/fleetscope.md)
FLEETSCOPE_FAMILIES = _families.family_table("fleetscope")

# sharding modes a BENCH extra.sharding may declare (parallel/sharding.py)
SHARDING_MODES = ("dp", "fsdp", "auto")

ROOFLINE_VERDICTS = ("compute_bound", "hbm_bound", "trivial", "unknown")

# the closed collective op-kind taxonomy an `extra.commscope` record may
# use (commscope/hlo.py COLLECTIVE_KINDS — unknown HLO spellings are
# bucketed as "other" by the producer, never invented here)
COMMSCOPE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute", "other")

# provenance values the step budget's collective component may declare:
# kvstore-counter / devicescope-window measurements, commscope's static
# estimate, or an honest unknown
COLLECTIVE_SOURCES = ("measured", "measured(profile)", "estimated",
                      "unavailable")

# idle-gap taxonomy buckets an `extra.devicescope` gaps block classifies
DEVICESCOPE_GAP_TAXONOMY = ("input_starved_ms", "dispatch_serialized_ms",
                            "host_gap_ms")

# the closed footprint provenance taxonomy an `extra.memscope` program
# record may declare (memscope/footprint.py FOOTPRINT_PROVENANCE):
# XLA reported the peak, we derived it from the component sum, or the
# backend has no memory_analysis at all
MEMSCOPE_PROVENANCE = ("reported", "derived", "unavailable")

# capacity resolution sources (memscope.device_capacity)
MEMSCOPE_CAPACITY_SOURCES = ("env", "memory_stats", "host_ram", "unknown")

# headroom verdicts (memscope.headroom_state) and the in-use pairing
MEMSCOPE_HEADROOM_VERDICTS = ("ok", "tight", "unknown")
MEMSCOPE_IN_USE_SOURCES = ("memory_stats", "host_rss")

# non-negative byte fields of one footprint record (peak checked apart)
MEMSCOPE_BYTE_FIELDS = ("argument_bytes", "output_bytes", "temp_bytes",
                        "alias_bytes", "generated_code_bytes")

MEMSCOPE_OOM_SCHEMA = "mxtpu.memscope.oom/1"

# per-stage attribution keys of the optional input_starved_split block
# (devicescope/ingest.py _starved_split), plus its dominant-stage tags
DEVICESCOPE_STARVED_SPLIT = ("read_ms", "decode_ms", "transfer_ms")
DEVICESCOPE_STARVED_DOMINANTS = ("read", "decode", "transfer")

# the closed request-latency component taxonomy an `extra.servescope`
# attribution decomposes into (servescope/spans.py COMPONENTS)
SERVESCOPE_COMPONENTS = ("queue_wait_ms", "coalesce_delay_ms",
                         "pad_overhead_ms", "device_exec_ms",
                         "respond_ms")

# provenance values the attribution's device_exec component may declare
SERVESCOPE_DEVICE_SOURCES = ("host_wall", "measured(profile)")

# structural tolerance on |cohort sum - e2e quantile| / quantile: the
# cohort-mean sum equals the cohort's mean e2e exactly, so this only
# bounds cohort tightness. The CPU smoke enforces the acceptance bound
# of 15%; the validator allows a little more slack (same split as
# PERFSCOPE_SUM_TOLERANCE).
SERVESCOPE_SUM_TOLERANCE = 0.25

# decomposition components that must sum (with "other" absorbing the
# residual) to the measured step time
PERFSCOPE_COMPONENTS = ("device_compute_ms", "collective_ms",
                        "input_wait_ms", "host_gap_ms", "other_ms")

# structural tolerance on |sum - step_ms| / step_ms. The CPU smoke
# enforces the acceptance bound of 15%; the validator allows a little
# more slack so a noisy-box artifact is flagged by the smoke (a perf
# verdict) rather than rejected as malformed telemetry.
PERFSCOPE_SUM_TOLERANCE = 0.25


def _is_num(x) -> bool:
    return isinstance(x, numbers.Real) and not isinstance(x, bool)


# ---------------------------------------------------------------------------
# Chrome trace events
# ---------------------------------------------------------------------------

def check_events(events) -> list:
    """Validate a list of trace events. Returns a list of error strings
    (empty = valid)."""
    errors = []
    if not isinstance(events, list):
        return [f"traceEvents must be a list, got {type(events).__name__}"]
    for i, ev in enumerate(events):
        where = f"event[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        name = ev.get("name")
        if not isinstance(name, str) or not name:
            errors.append(f"{where}: missing/empty 'name'")
        ph = ev.get("ph")
        if not isinstance(ph, str) or len(ph) != 1:
            errors.append(f"{where} ({name!r}): missing 'ph'")
            continue
        if ph == "X":
            if not _is_num(ev.get("ts")) or ev["ts"] < 0:
                errors.append(f"{where} ({name!r}): 'X' event needs numeric "
                              f"ts >= 0, got {ev.get('ts')!r}")
            if not _is_num(ev.get("dur")) or ev["dur"] < 0:
                errors.append(f"{where} ({name!r}): 'X' event needs numeric "
                              f"dur >= 0, got {ev.get('dur')!r}")
        elif ph in ("i", "I", "C", "B", "E"):
            if not _is_num(ev.get("ts")):
                errors.append(f"{where} ({name!r}): '{ph}' event needs "
                              f"numeric ts, got {ev.get('ts')!r}")
        for key in ("pid", "tid"):
            if key in ev and not isinstance(ev[key], int):
                errors.append(f"{where} ({name!r}): '{key}' must be int, "
                              f"got {ev[key]!r}")
    return errors


def check_trace(path: str) -> list:
    """Validate one Chrome trace file. Returns a list of error strings."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable/invalid JSON: {e}"]
    if isinstance(doc, list):
        events = doc
    elif isinstance(doc, dict):
        if "traceEvents" not in doc:
            return [f"{path}: object form requires a 'traceEvents' key"]
        events = doc["traceEvents"]
    else:
        return [f"{path}: top level must be a list or object, "
                f"got {type(doc).__name__}"]
    return [f"{path}: {e}" for e in check_events(events)]


# ---------------------------------------------------------------------------
# flight-recorder dumps
# ---------------------------------------------------------------------------

def check_flight(path: str) -> list:
    """Validate a diagnostics flight-recorder dump."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable/invalid JSON: {e}"]
    errors = []
    if not isinstance(doc, dict):
        return [f"{path}: flight dump must be a JSON object"]
    schema = doc.get("schema")
    if not isinstance(schema, str) or \
            not schema.startswith(FLIGHT_SCHEMA_PREFIX):
        errors.append(f"schema must start with {FLIGHT_SCHEMA_PREFIX!r}, "
                      f"got {schema!r}")
    for key, typ in (("dumped_at", numbers.Real), ("reason", str),
                     ("env", dict), ("config", dict), ("counters", dict),
                     ("counter_kinds", dict), ("events", list)):
        if not isinstance(doc.get(key), typ):
            errors.append(f"missing/mistyped {key!r} "
                          f"(want {typ.__name__}, "
                          f"got {type(doc.get(key)).__name__})")
    events = doc.get("events")
    if isinstance(events, list):
        last_ts = None
        for i, ev in enumerate(events):
            if not isinstance(ev, dict):
                errors.append(f"events[{i}]: not an object")
                continue
            if not _is_num(ev.get("ts")):
                errors.append(f"events[{i}]: needs numeric 'ts', "
                              f"got {ev.get('ts')!r}")
                continue
            for key in ("kind", "name"):
                if not isinstance(ev.get(key), str) or not ev[key]:
                    errors.append(f"events[{i}]: missing/empty {key!r}")
            if last_ts is not None and ev["ts"] < last_ts:
                errors.append(f"events[{i}]: ts went backwards "
                              f"({ev['ts']} < {last_ts})")
            last_ts = ev["ts"]
        n = doc.get("n_events")
        if isinstance(n, int) and n != len(events):
            errors.append(f"n_events={n} but {len(events)} events present")
    kinds = doc.get("counter_kinds")
    if isinstance(kinds, dict):
        bad = [k for k, v in kinds.items()
               if v not in ("counter", "gauge", "histogram")]
        if bad:
            errors.append(f"counter_kinds values must be "
                          f"counter|gauge|histogram: {bad[:3]}")
        counters = doc.get("counters")
        if isinstance(counters, dict):
            for k, kind in kinds.items():
                if kind == "histogram" and k in counters:
                    errors += [f"counters[{k!r}]: {e}" for e in
                               check_histogram_snapshot(counters[k])]
        errors += check_healthmon_kinds(kinds)
    return [f"{path}: {e}" for e in errors]


# ---------------------------------------------------------------------------
# healthmon counter families
# ---------------------------------------------------------------------------

def check_healthmon_kinds(kinds: dict) -> list:
    """Every healthmon/*, io/*, trainloop/*, perfscope/*, commscope/*,
    devicescope/*, servescope/* and sharding/* metric must belong to
    its family table with the declared kind."""
    errors = []
    tables = (("healthmon/", HEALTHMON_FAMILIES, "HEALTHMON_FAMILIES"),
              ("io/", IO_TRAINLOOP_FAMILIES, "IO_TRAINLOOP_FAMILIES"),
              ("trainloop/", IO_TRAINLOOP_FAMILIES,
               "IO_TRAINLOOP_FAMILIES"),
              ("perfscope/", PERFSCOPE_FAMILIES, "PERFSCOPE_FAMILIES"),
              ("commscope/", COMMSCOPE_FAMILIES, "COMMSCOPE_FAMILIES"),
              ("devicescope/", DEVICESCOPE_FAMILIES,
               "DEVICESCOPE_FAMILIES"),
              ("servescope/", SERVESCOPE_FAMILIES, "SERVESCOPE_FAMILIES"),
              ("memscope/", MEMSCOPE_FAMILIES, "MEMSCOPE_FAMILIES"),
              ("resilience/", RESILIENCE_FAMILIES,
               "RESILIENCE_FAMILIES"),
              ("mxlint/", MXLINT_FAMILIES, "MXLINT_FAMILIES"),
              ("fleet/", FLEET_FAMILIES, "FLEET_FAMILIES"),
              ("fleetscope/", FLEETSCOPE_FAMILIES,
               "FLEETSCOPE_FAMILIES"),
              ("sharding/", SHARDING_FAMILIES, "SHARDING_FAMILIES"))
    for k, kind in sorted(kinds.items()):
        for prefix, table, tname in tables:
            if not k.startswith(prefix):
                continue
            want = table.get(k)
            if want is None:
                errors.append(f"unknown {prefix.rstrip('/')} counter "
                              f"family {k!r} (update {tname} if "
                              f"intentional)")
            elif kind != want:
                errors.append(f"counter {k!r} has kind {kind!r}, "
                              f"schema says {want!r}")
    return errors


# ---------------------------------------------------------------------------
# structured event logs (mxtpu.events/1 JSONL)
# ---------------------------------------------------------------------------

def check_events_jsonl(path: str) -> list:
    """Validate a healthmon structured event log (or a `mxdiag merge`
    output): every record a JSON object with the versioned schema tag,
    the run_id/rank/step correlation ids, non-empty kind/name, and
    non-decreasing timestamps. Schema /2 added a ``mono`` companion
    stamp (NTP-step-safe merges); it stays OPTIONAL here so /1 records
    (wall-only) keep validating — when present it must be numeric."""
    try:
        with open(path) as f:
            raw_lines = [ln for ln in f.read().splitlines() if ln.strip()]
    except OSError as e:
        return [f"{path}: unreadable: {e}"]
    if not raw_lines:
        return [f"{path}: empty event log"]
    errors = []
    last_ts = None
    for i, ln in enumerate(raw_lines, 1):
        try:
            rec = json.loads(ln)
        except ValueError as e:
            errors.append(f"line {i}: invalid JSON: {e}")
            continue
        if not isinstance(rec, dict):
            errors.append(f"line {i}: record must be an object")
            continue
        schema = rec.get("schema")
        if not isinstance(schema, str) or \
                not schema.startswith(EVENTS_SCHEMA_PREFIX):
            errors.append(f"line {i}: schema must start with "
                          f"{EVENTS_SCHEMA_PREFIX!r}, got {schema!r}")
        if not _is_num(rec.get("ts")):
            errors.append(f"line {i}: needs numeric 'ts', "
                          f"got {rec.get('ts')!r}")
        else:
            if last_ts is not None and rec["ts"] < last_ts:
                errors.append(f"line {i}: ts went backwards "
                              f"({rec['ts']} < {last_ts})")
            last_ts = rec["ts"]
        if "mono" in rec and not _is_num(rec["mono"]):
            # monotone ordering is per-process, so a merged multi-process
            # file can't demand non-decreasing mono — numeric is the
            # contract here
            errors.append(f"line {i}: 'mono' must be numeric when "
                          f"present, got {rec['mono']!r}")
        if not isinstance(rec.get("run_id"), str) or not rec["run_id"]:
            errors.append(f"line {i}: missing/empty 'run_id'")
        rank = rec.get("rank")
        if not isinstance(rank, int) or isinstance(rank, bool) or rank < 0:
            errors.append(f"line {i}: 'rank' must be int >= 0, "
                          f"got {rank!r}")
        step = rec.get("step")
        if step is not None and (not isinstance(step, int)
                                 or isinstance(step, bool)):
            errors.append(f"line {i}: 'step' must be int or null, "
                          f"got {step!r}")
        for key in ("kind", "name"):
            if not isinstance(rec.get(key), str) or not rec[key]:
                errors.append(f"line {i}: missing/empty {key!r}")
        if "args" in rec and not isinstance(rec["args"], dict):
            errors.append(f"line {i}: 'args' must be an object, "
                          f"got {type(rec['args']).__name__}")
    return [f"{path}: {e}" for e in errors]


# ---------------------------------------------------------------------------
# histogram snapshots (profiler.counters.Histogram.value)
# ---------------------------------------------------------------------------

def check_histogram_snapshot(h) -> list:
    """Structural validation of one histogram snapshot dict: numeric
    count/sum, cumulative non-decreasing buckets ending in `+Inf` ==
    count, and ordered percentile estimates."""
    if not isinstance(h, dict):
        return [f"histogram snapshot must be an object, "
                f"got {type(h).__name__}"]
    errors = []
    for key in ("count", "sum"):
        if not _is_num(h.get(key)):
            errors.append(f"needs numeric {key!r}, got {h.get(key)!r}")
    buckets = h.get("buckets")
    if not isinstance(buckets, dict) or not buckets:
        errors.append("needs non-empty 'buckets'")
    else:
        prev = None
        for le, c in buckets.items():
            if not _is_num(c) or c < 0:
                errors.append(f"bucket le={le!r}: bad count {c!r}")
                continue
            if prev is not None and c < prev:
                errors.append(f"bucket le={le!r}: cumulative count "
                              f"decreased ({c} < {prev})")
            prev = c
        if "+Inf" not in buckets:
            errors.append("buckets must end with '+Inf'")
        elif _is_num(h.get("count")) and buckets["+Inf"] != h["count"]:
            errors.append(f"buckets['+Inf']={buckets['+Inf']} != "
                          f"count={h['count']}")
    pcts = [h.get(k) for k in ("p50", "p95", "p99")]
    if h.get("count"):
        if not all(_is_num(p) for p in pcts):
            errors.append(f"non-empty histogram needs numeric "
                          f"p50/p95/p99, got {pcts!r}")
        elif not (pcts[0] <= pcts[1] <= pcts[2]):
            errors.append(f"percentiles must be ordered "
                          f"p50<=p95<=p99, got {pcts!r}")
    return errors


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

_PROM_METRIC = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"          # metric name
    r"(\{[^}]*\})?"                          # optional label set
    r"\s+(-?[0-9.eE+-]+|NaN|[+-]?Inf)\s*$")  # value
_PROM_LABELS = re.compile(
    r'^\{([a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*,?\}$')
_PROM_TYPE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
    r"(counter|gauge|histogram|summary|untyped)$")


def check_prom(path: str) -> list:
    """Validate a Prometheus text-format file."""
    try:
        with open(path) as f:
            lines = f.read().splitlines()
    except OSError as e:
        return [f"{path}: unreadable: {e}"]
    errors = []
    typed = {}
    n_samples = 0
    for i, line in enumerate(lines, 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            if line.startswith("# TYPE"):
                m = _PROM_TYPE.match(line)
                if not m:
                    errors.append(f"line {i}: malformed TYPE comment: "
                                  f"{line!r}")
                else:
                    if m.group(1) in typed:
                        errors.append(f"line {i}: duplicate TYPE for "
                                      f"{m.group(1)}")
                    typed[m.group(1)] = m.group(2)
            continue
        m = _PROM_METRIC.match(line)
        if not m:
            errors.append(f"line {i}: malformed sample line: {line!r}")
            continue
        n_samples += 1
        labels = m.group(2)
        if labels and not _PROM_LABELS.match(labels):
            errors.append(f"line {i}: malformed label set: {labels!r}")
        try:
            float(m.group(3).replace("Inf", "inf"))
        except ValueError:
            errors.append(f"line {i}: unparseable value {m.group(3)!r}")
        name = m.group(1)
        if name not in typed:
            # histogram/summary families declare the base name; their
            # samples carry the _bucket/_sum/_count suffixes
            base = None
            for suffix in ("_bucket", "_sum", "_count"):
                if name.endswith(suffix) and \
                        typed.get(name[:-len(suffix)]) in ("histogram",
                                                           "summary"):
                    base = name[:-len(suffix)]
                    break
            if base is None:
                errors.append(f"line {i}: sample {name!r} has no "
                              f"preceding # TYPE declaration")
    if n_samples == 0:
        errors.append("no metric samples present")
    return [f"{path}: {e}" for e in errors]


# ---------------------------------------------------------------------------
# metrics newline-JSON (sampler time series)
# ---------------------------------------------------------------------------

def check_metrics_jsonl(path: str) -> list:
    """Validate a sampler metrics.jsonl: per-line schema, non-decreasing
    timestamps, and monotonic non-decreasing values for every metric of
    kind 'counter'."""
    try:
        with open(path) as f:
            raw_lines = [ln for ln in f.read().splitlines() if ln.strip()]
    except OSError as e:
        return [f"{path}: unreadable: {e}"]
    errors = []
    if not raw_lines:
        return [f"{path}: empty metrics file"]
    last_ts = None
    last_counter_vals = {}
    seen_kinds = {}
    for i, ln in enumerate(raw_lines, 1):
        try:
            s = json.loads(ln)
        except ValueError as e:
            errors.append(f"line {i}: invalid JSON: {e}")
            continue
        if not isinstance(s, dict) or not _is_num(s.get("ts")) \
                or not isinstance(s.get("counters"), dict):
            errors.append(f"line {i}: sample needs numeric 'ts' and "
                          f"object 'counters'")
            continue
        if last_ts is not None and s["ts"] < last_ts:
            errors.append(f"line {i}: ts went backwards "
                          f"({s['ts']} < {last_ts})")
        last_ts = s["ts"]
        kinds = s.get("kinds") or {}
        seen_kinds.update(kinds)
        for name, v in s["counters"].items():
            kind = kinds.get(name)
            if kind == "histogram":
                errors += [f"line {i}: histogram {name!r}: {e}"
                           for e in check_histogram_snapshot(v)]
                n = v.get("count") if isinstance(v, dict) else None
                if not _is_num(n):
                    continue
                v = n              # observation count is the monotone series
            elif kind != "counter" or not _is_num(v):
                continue
            prev = last_counter_vals.get(name)
            if prev is not None and v < prev:
                errors.append(f"line {i}: counter {name!r} decreased "
                              f"({prev} -> {v})")
            last_counter_vals[name] = v
    errors += check_healthmon_kinds(seen_kinds)
    return [f"{path}: {e}" for e in errors]


# ---------------------------------------------------------------------------
# perfscope bench section (extra.perfscope)
# ---------------------------------------------------------------------------

def check_perfscope_extra(ps) -> list:
    """Validate an `extra.perfscope` BENCH section: per-program roofline
    records with verdicts from the known taxonomy, a peak table, and —
    when the run carried a step budget — a decomposition whose
    components sum to the measured step time within tolerance."""
    if ps is None:
        return []
    if not isinstance(ps, dict):
        return [f"must be an object, got {type(ps).__name__}"]
    errors = []
    peaks = ps.get("peaks")
    if not isinstance(peaks, dict):
        errors.append("needs a 'peaks' object")
    else:
        # a device_kind outside the peak table has no row and no peaks
        # (all null); a known one has all three
        unknown = peaks.get("table_row") is None
        for key in ("peak_flops_f32", "peak_flops_bf16", "hbm_bytes_per_s"):
            v = peaks.get(key)
            if unknown and v is None:
                continue
            if not _is_num(v) or v <= 0:
                errors.append(f"peaks[{key!r}] must be positive, got {v!r}")
    progs = ps.get("programs")
    if not isinstance(progs, list):
        errors.append("needs a 'programs' list")
    else:
        for i, p in enumerate(progs):
            if not isinstance(p, dict):
                errors.append(f"programs[{i}]: not an object")
                continue
            if not isinstance(p.get("name"), str) or not p["name"]:
                errors.append(f"programs[{i}]: missing/empty 'name'")
            if p.get("verdict") not in ROOFLINE_VERDICTS:
                errors.append(f"programs[{i}] ({p.get('name')!r}): verdict "
                              f"{p.get('verdict')!r} not in "
                              f"{ROOFLINE_VERDICTS}")
            for key in ("flops", "bytes_accessed", "ai"):
                v = p.get(key)
                if v is not None and not _is_num(v):
                    errors.append(f"programs[{i}] ({p.get('name')!r}): "
                                  f"{key!r} must be numeric or null, "
                                  f"got {v!r}")
    d = ps.get("decomposition")
    if d is None:
        return errors
    if not isinstance(d, dict):
        return errors + ["decomposition must be an object"]
    step_ms = d.get("step_ms")
    if not _is_num(step_ms) or step_ms <= 0:
        errors.append(f"decomposition.step_ms must be positive, "
                      f"got {step_ms!r}")
        return errors
    total = 0.0
    comp_ok = True
    for key in PERFSCOPE_COMPONENTS:
        v = d.get(key)
        if not _is_num(v) or v < 0:
            errors.append(f"decomposition[{key!r}] must be numeric >= 0, "
                          f"got {v!r}")
            comp_ok = False
        else:
            total += v
    if comp_ok:
        off = abs(total - step_ms) / step_ms
        if off > PERFSCOPE_SUM_TOLERANCE:
            errors.append(
                f"components sum to {total:.4g} ms but step_ms="
                f"{step_ms:.4g} ({off:.1%} apart, tolerance "
                f"{PERFSCOPE_SUM_TOLERANCE:.0%})")
    mfu = d.get("mfu")
    if mfu is not None and (not _is_num(mfu) or not 0.0 <= mfu <= 1.5):
        errors.append(f"decomposition.mfu={mfu!r} outside [0, 1.5]")
    src = d.get("collective_source")
    if src is not None and src not in COLLECTIVE_SOURCES:
        errors.append(f"decomposition.collective_source={src!r} not in "
                      f"{COLLECTIVE_SOURCES}")
    return errors


# ---------------------------------------------------------------------------
# commscope bench section (extra.commscope)
# ---------------------------------------------------------------------------

def check_commscope_extra(cs) -> list:
    """Validate an `extra.commscope` BENCH section: per-program
    collective inventories drawn from the closed op-kind taxonomy with
    non-negative bytes/counts and numeric estimates, an ICI peak table,
    and a well-formed (or null) steady-step summary."""
    if cs is None:
        return []
    if not isinstance(cs, dict):
        return [f"must be an object, got {type(cs).__name__}"]
    errors = []
    peaks = cs.get("peaks")
    if not isinstance(peaks, dict):
        errors.append("needs a 'peaks' object")
    else:
        v = peaks.get("ici_bytes_per_s")
        if not _is_num(v) or v <= 0:
            errors.append(f"peaks['ici_bytes_per_s'] must be positive, "
                          f"got {v!r}")
    progs = cs.get("programs")
    if not isinstance(progs, list):
        errors.append("needs a 'programs' list")
        progs = []
    for i, p in enumerate(progs):
        if not isinstance(p, dict):
            errors.append(f"programs[{i}]: not an object")
            continue
        where = f"programs[{i}] ({p.get('name')!r})"
        if not isinstance(p.get("name"), str) or not p["name"]:
            errors.append(f"programs[{i}]: missing/empty 'name'")
        totals = p.get("totals")
        if not isinstance(totals, dict):
            errors.append(f"{where}: missing 'totals' object")
            totals = {}
        for key in ("count", "bytes"):
            v = totals.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errors.append(f"{where}: totals[{key!r}] must be an int "
                              f">= 0, got {v!r}")
        est = totals.get("est_ms")
        if not _is_num(est) or est < 0:
            errors.append(f"{where}: totals['est_ms'] must be numeric "
                          f">= 0, got {est!r}")
        colls = p.get("collectives")
        if not isinstance(colls, list):
            errors.append(f"{where}: missing 'collectives' list")
            colls = []
        kind_count = 0
        for j, c in enumerate(colls):
            if not isinstance(c, dict):
                errors.append(f"{where}: collectives[{j}] not an object")
                continue
            if c.get("kind") not in COMMSCOPE_KINDS:
                errors.append(f"{where}: collectives[{j}] kind "
                              f"{c.get('kind')!r} not in {COMMSCOPE_KINDS}")
            n = c.get("count")
            if not isinstance(n, int) or isinstance(n, bool) or n < 1:
                errors.append(f"{where}: collectives[{j}] count must be "
                              f"an int >= 1, got {n!r}")
            else:
                kind_count += n
            b = c.get("bytes")
            if not _is_num(b) or b < 0:
                errors.append(f"{where}: collectives[{j}] bytes must be "
                              f">= 0, got {b!r}")
            e = c.get("est_ms")
            if not _is_num(e) or e < 0:
                errors.append(f"{where}: collectives[{j}] est_ms must be "
                              f"numeric >= 0, got {e!r}")
            ax = c.get("axis")
            if ax is not None and not isinstance(ax, str):
                errors.append(f"{where}: collectives[{j}] axis must be a "
                              f"string or null, got {ax!r}")
        if isinstance(totals.get("count"), int) \
                and kind_count != totals["count"] \
                and not any(not isinstance(c, dict) or
                            not isinstance(c.get("count"), int)
                            for c in colls):
            errors.append(f"{where}: per-kind counts sum to {kind_count} "
                          f"but totals.count={totals['count']}")
        r = p.get("resharding_collectives")
        if not isinstance(r, int) or isinstance(r, bool) or r < 0:
            errors.append(f"{where}: resharding_collectives must be an "
                          f"int >= 0, got {r!r}")
    step = cs.get("step")
    if step is not None:
        if not isinstance(step, dict):
            errors.append("'step' must be an object or null")
        else:
            e = step.get("est_ms")
            if e is not None and (not _is_num(e) or e < 0):
                errors.append(f"step.est_ms must be numeric >= 0 or null, "
                              f"got {e!r}")
            b = step.get("bytes")
            if b is not None and (not _is_num(b) or b < 0):
                errors.append(f"step.bytes must be >= 0 or null, got {b!r}")
    return errors


# ---------------------------------------------------------------------------
# devicescope bench section (extra.devicescope)
# ---------------------------------------------------------------------------

def check_devicescope_extra(ds) -> list:
    """Validate an `extra.devicescope` BENCH section: a window header
    (or the armed-but-no-window `window: null` shape), a busy fraction
    in [0, 1], top-K rows with non-negative measured times, measured
    collective kinds from the closed commscope taxonomy, a gap taxonomy
    whose buckets are numeric, and — when present — a reconciliation
    block whose analytic and measured sides both carry numeric
    components."""
    if ds is None:
        return []
    if not isinstance(ds, dict):
        return [f"must be an object, got {type(ds).__name__}"]
    errors = []
    win = ds.get("window")
    if win is None:
        # armed but no completed window: everything else must be empty
        if ds.get("busy_fraction") is not None:
            errors.append("window is null but busy_fraction is set")
        return errors
    if not isinstance(win, dict):
        return [f"'window' must be an object or null, "
                f"got {type(win).__name__}"]
    steps = win.get("steps")
    # 0 is legal: a window stopped before its first step mark still
    # reports honestly (its per-step numbers just use a 1-step floor)
    if not isinstance(steps, int) or isinstance(steps, bool) or steps < 0:
        errors.append(f"window.steps must be an int >= 0, got {steps!r}")
    wall = win.get("wall_ms")
    if wall is not None and (not _is_num(wall) or wall <= 0):
        errors.append(f"window.wall_ms must be positive or null, "
                      f"got {wall!r}")
    if not isinstance(win.get("path"), str) or not win["path"]:
        errors.append("window needs a non-empty 'path'")
    bf = ds.get("busy_fraction")
    if bf is not None and (not _is_num(bf) or not 0.0 <= bf <= 1.0):
        errors.append(f"busy_fraction={bf!r} outside [0, 1]")
    per = ds.get("per_step")
    if per is not None:
        if not isinstance(per, dict):
            errors.append("per_step must be an object or null")
        else:
            for key in ("device_busy_ms", "collective_ms", "idle_ms"):
                v = per.get(key)
                if not _is_num(v) or v < 0:
                    errors.append(f"per_step[{key!r}] must be numeric "
                                  f">= 0, got {v!r}")
    tops = ds.get("top_ops")
    if not isinstance(tops, list):
        errors.append("needs a 'top_ops' list")
    else:
        for i, t in enumerate(tops):
            if not isinstance(t, dict):
                errors.append(f"top_ops[{i}]: not an object")
                continue
            if not isinstance(t.get("op"), str) or not t["op"]:
                errors.append(f"top_ops[{i}]: missing/empty 'op'")
            n = t.get("count")
            if not isinstance(n, int) or isinstance(n, bool) or n < 1:
                errors.append(f"top_ops[{i}] ({t.get('op')!r}): count "
                              f"must be an int >= 1, got {n!r}")
            v = t.get("total_ms")
            if not _is_num(v) or v < 0:
                errors.append(f"top_ops[{i}] ({t.get('op')!r}): total_ms "
                              f"must be >= 0, got {v!r}")
            verdict = t.get("verdict")
            if verdict is not None and verdict not in ROOFLINE_VERDICTS:
                errors.append(f"top_ops[{i}] ({t.get('op')!r}): verdict "
                              f"{verdict!r} not in {ROOFLINE_VERDICTS}")
    colls = ds.get("collectives")
    if colls is not None:
        if not isinstance(colls, dict):
            errors.append("collectives must be an object or null")
        else:
            for row in colls.get("by_kind") or []:
                if not isinstance(row, dict):
                    errors.append("collectives.by_kind row not an object")
                    continue
                if row.get("kind") not in COMMSCOPE_KINDS:
                    errors.append(f"collectives kind {row.get('kind')!r} "
                                  f"not in {COMMSCOPE_KINDS}")
                v = row.get("total_ms")
                if not _is_num(v) or v < 0:
                    errors.append(f"collectives[{row.get('kind')!r}] "
                                  f"total_ms must be >= 0, got {v!r}")
    gaps = ds.get("gaps")
    if gaps is not None:
        if not isinstance(gaps, dict):
            errors.append("gaps must be an object or null")
        else:
            tax = gaps.get("taxonomy")
            if not isinstance(tax, dict):
                errors.append("gaps needs a 'taxonomy' object")
            else:
                for key in DEVICESCOPE_GAP_TAXONOMY:
                    v = tax.get(key)
                    if not _is_num(v) or v < 0:
                        errors.append(f"gaps.taxonomy[{key!r}] must be "
                                      f"numeric >= 0, got {v!r}")
            split = gaps.get("input_starved_split")
            if split is not None:
                # optional: present only when the pipeline's stage walls
                # could attribute a nonzero starved bucket
                if not isinstance(split, dict):
                    errors.append("gaps.input_starved_split must be an "
                                  "object or absent")
                else:
                    for key in DEVICESCOPE_STARVED_SPLIT:
                        v = split.get(key)
                        if not _is_num(v) or v < 0:
                            errors.append(
                                f"gaps.input_starved_split[{key!r}] must "
                                f"be numeric >= 0, got {v!r}")
                    dom = split.get("dominant")
                    if dom not in DEVICESCOPE_STARVED_DOMINANTS:
                        errors.append(
                            f"gaps.input_starved_split.dominant={dom!r} "
                            f"not in {DEVICESCOPE_STARVED_DOMINANTS}")
    recon = ds.get("reconciliation")
    if recon is not None:
        if not isinstance(recon, dict):
            errors.append("reconciliation must be an object or null")
        else:
            for side in ("analytic", "measured"):
                blk = recon.get(side)
                if not isinstance(blk, dict):
                    errors.append(f"reconciliation needs a {side!r} "
                                  f"object")
                    continue
                for key in ("device_compute_ms", "collective_ms"):
                    v = blk.get(key)
                    if not _is_num(v) or v < 0:
                        errors.append(f"reconciliation.{side}[{key!r}] "
                                      f"must be >= 0, got {v!r}")
            src = (recon.get("analytic") or {}).get("collective_source")
            if src is not None and src not in COLLECTIVE_SOURCES:
                errors.append(f"reconciliation analytic "
                              f"collective_source={src!r} not in "
                              f"{COLLECTIVE_SOURCES}")
            drift = recon.get("drift")
            if drift is not None and not isinstance(drift, dict):
                errors.append("reconciliation.drift must be an object")
            elif isinstance(drift, dict):
                for k, v in drift.items():
                    if v is not None and (not _is_num(v) or v < 0):
                        errors.append(f"reconciliation.drift[{k!r}] must "
                                      f"be numeric >= 0 or null, "
                                      f"got {v!r}")
            if not isinstance(recon.get("drift_warning"), bool):
                errors.append(f"reconciliation.drift_warning must be a "
                              f"bool, got {recon.get('drift_warning')!r}")
    return errors


# ---------------------------------------------------------------------------
# memscope bench section (extra.memscope)
# ---------------------------------------------------------------------------

def check_memscope_extra(ms) -> list:
    """Validate an `extra.memscope` BENCH section: footprint records
    with non-negative bytes and the closed provenance taxonomy (an
    unavailable backend must keep the honest all-None shape), a
    bounded watermark ring whose peak dominates the latest in-use
    reading, a capacity block from the closed source taxonomy, a
    headroom verdict, and — when present — an OOM post-mortem with the
    right schema tag."""
    if ms is None:
        return []
    if not isinstance(ms, dict):
        return [f"must be an object, got {type(ms).__name__}"]
    errors = []
    progs = ms.get("programs")
    if not isinstance(progs, list):
        errors.append("needs a 'programs' list")
        progs = []
    for i, p in enumerate(progs):
        if not isinstance(p, dict):
            errors.append(f"programs[{i}]: not an object")
            continue
        name = p.get("name")
        if not isinstance(name, str) or not name:
            errors.append(f"programs[{i}]: missing/empty 'name'")
        prov = p.get("provenance")
        if prov not in MEMSCOPE_PROVENANCE:
            errors.append(f"programs[{i}] ({name!r}): provenance "
                          f"{prov!r} not in {MEMSCOPE_PROVENANCE}")
        avail = p.get("available")
        if not isinstance(avail, bool):
            errors.append(f"programs[{i}] ({name!r}): 'available' must "
                          f"be a bool, got {avail!r}")
        if avail is False:
            # armed-but-unavailable: the byte fields must stay honest
            # Nones, not invented zeros
            if prov != "unavailable":
                errors.append(f"programs[{i}] ({name!r}): unavailable "
                              f"record declares provenance {prov!r}")
            for key in MEMSCOPE_BYTE_FIELDS + ("peak_bytes",):
                if p.get(key) is not None:
                    errors.append(f"programs[{i}] ({name!r}): "
                                  f"unavailable record carries "
                                  f"{key}={p.get(key)!r}")
            continue
        for key in MEMSCOPE_BYTE_FIELDS:
            v = p.get(key)
            if not _is_num(v) or v < 0:
                errors.append(f"programs[{i}] ({name!r}): {key} must "
                              f"be numeric >= 0, got {v!r}")
        peak = p.get("peak_bytes")
        if not _is_num(peak) or peak < 0:
            errors.append(f"programs[{i}] ({name!r}): peak_bytes must "
                          f"be numeric >= 0, got {peak!r}")
        verdict = p.get("roofline")
        if verdict is not None and verdict not in ROOFLINE_VERDICTS:
            errors.append(f"programs[{i}] ({name!r}): roofline "
                          f"{verdict!r} not in {ROOFLINE_VERDICTS}")
    wm = ms.get("watermarks")
    if wm is not None:
        if not isinstance(wm, dict):
            errors.append("watermarks must be an object or null")
        else:
            n, ring, limit = (wm.get("samples"), wm.get("ring"),
                              wm.get("ring_limit"))
            for key, v in (("samples", n), ("ring", ring),
                           ("ring_limit", limit)):
                if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                    errors.append(f"watermarks.{key} must be an int "
                                  f">= 0, got {v!r}")
            if isinstance(ring, int) and isinstance(limit, int) \
                    and ring > limit:
                errors.append(f"watermarks.ring={ring} exceeds "
                              f"ring_limit={limit} (unbounded ring)")
            if isinstance(ring, int) and isinstance(n, int) and ring > n:
                errors.append(f"watermarks.ring={ring} > samples={n} "
                              f"(phantom samples)")
            for sect in ("device", "host_rss"):
                blk = wm.get(sect)
                if blk is None:
                    continue
                if not isinstance(blk, dict):
                    errors.append(f"watermarks.{sect} must be an object "
                                  f"or null")
                    continue
                for key in ("p50", "p95", "peak", "latest"):
                    v = blk.get(key)
                    if v is not None and (not _is_num(v) or v < 0):
                        errors.append(f"watermarks.{sect}.{key} must be "
                                      f"numeric >= 0, got {v!r}")
                peak, latest = blk.get("peak"), blk.get("latest")
                if sect == "device" and _is_num(peak) \
                        and _is_num(latest) and peak < latest:
                    errors.append(f"watermarks.device peak={peak} < "
                                  f"latest in-use={latest} (a peak "
                                  f"watermark cannot undercut current "
                                  f"use)")
    cap = ms.get("capacity")
    if cap is not None:
        if not isinstance(cap, dict):
            errors.append("capacity must be an object or null")
        else:
            if cap.get("source") not in MEMSCOPE_CAPACITY_SOURCES:
                errors.append(f"capacity.source={cap.get('source')!r} "
                              f"not in {MEMSCOPE_CAPACITY_SOURCES}")
            v = cap.get("bytes")
            if v is not None and (not _is_num(v) or v <= 0):
                errors.append(f"capacity.bytes must be positive or "
                              f"null, got {v!r}")
            if cap.get("source") != "unknown" and v is None:
                errors.append(f"capacity declares source "
                              f"{cap.get('source')!r} but bytes is null")
    hr = ms.get("headroom")
    if hr is not None:
        if not isinstance(hr, dict):
            errors.append("headroom must be an object or null")
        else:
            if hr.get("verdict") not in MEMSCOPE_HEADROOM_VERDICTS:
                errors.append(f"headroom.verdict={hr.get('verdict')!r} "
                              f"not in {MEMSCOPE_HEADROOM_VERDICTS}")
            hf = hr.get("headroom_fraction")
            if hf is not None and (not _is_num(hf)
                                   or not 0.0 <= hf <= 1.0):
                errors.append(f"headroom_fraction={hf!r} outside [0, 1]")
            tgt = hr.get("target")
            if not _is_num(tgt) or not 0.0 < tgt <= 1.0:
                errors.append(f"headroom.target must be in (0, 1], "
                              f"got {tgt!r}")
            src = hr.get("in_use_source")
            if src is not None and src not in MEMSCOPE_IN_USE_SOURCES:
                errors.append(f"headroom.in_use_source={src!r} not in "
                              f"{MEMSCOPE_IN_USE_SOURCES}")
            if hr.get("verdict") != "unknown" and hf is None:
                errors.append("headroom verdict is decided but "
                              "headroom_fraction is null")
    oom = ms.get("oom")
    if oom is not None:
        if not isinstance(oom, dict):
            errors.append("oom must be an object or null")
        elif oom.get("schema") != MEMSCOPE_OOM_SCHEMA:
            errors.append(f"oom.schema={oom.get('schema')!r}, expected "
                          f"{MEMSCOPE_OOM_SCHEMA!r}")
        elif not isinstance(oom.get("error"), str) or not oom["error"]:
            errors.append("oom post-mortem needs a non-empty 'error'")
    return errors


# ---------------------------------------------------------------------------
# mxlint bench section (extra.mxlint)
# ---------------------------------------------------------------------------

def check_mxlint_extra(mx) -> list:
    """Validate an `extra.mxlint` BENCH section: the disabled shape
    (`strict: false`), or the full strict-mode audit record — the
    finding counters must be present, non-negative, and SUM to the
    `findings` total, and every recompiled program must be named."""
    if mx is None:
        return []
    if not isinstance(mx, dict):
        return [f"must be an object, got {type(mx).__name__}"]
    errors = []
    strict = mx.get("strict")
    if not isinstance(strict, bool):
        errors.append(f"needs a boolean 'strict', got {strict!r}")
        return errors
    if not strict:
        return errors
    parts = ("transfer_guard_trips", "recompiles", "donation_violations")
    for key in parts + ("findings", "allowed_syncs",
                        "guarded_dispatches"):
        v = mx.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errors.append(f"'{key}' must be an int >= 0, got {v!r}")
    if all(isinstance(mx.get(k), int) for k in parts + ("findings",)) \
            and mx["findings"] != sum(mx[k] for k in parts):
        errors.append(
            f"findings={mx['findings']} != "
            f"{' + '.join(parts)} = {sum(mx[k] for k in parts)}")
    rp = mx.get("recompiled_programs")
    if not isinstance(rp, list) or \
            any(not isinstance(n, str) or not n for n in rp):
        errors.append(f"'recompiled_programs' must be a list of program "
                      f"names, got {rp!r}")
    elif isinstance(mx.get("recompiles"), int) \
            and mx["recompiles"] == 0 and rp:
        errors.append(f"recompiles=0 but recompiled_programs={rp!r}")
    return errors


# ---------------------------------------------------------------------------
# io pipeline bench section (extra.io)
# ---------------------------------------------------------------------------

def check_io_extra(io) -> list:
    """Validate an `extra.io` BENCH section: the ingest-pipeline shape
    (docs/io.md). Stage walls are cumulative thread-wall milliseconds —
    they may each exceed the run wall (stages overlap), but never go
    negative, and the pipeline must declare its geometry (workers,
    depth) so a smoke comparison knows what it measured."""
    if io is None:
        return []
    if not isinstance(io, dict):
        return [f"must be an object, got {type(io).__name__}"]
    errors = []
    for key in ("workers", "depth"):
        v = io.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            errors.append(f"'{key}' must be an int >= 1, got {v!r}")
    for key in ("batches_prefetched", "wait_ms", "read_ms",
                "decode_ms", "stage_ms", "put_ms"):
        v = io.get(key)
        if not _is_num(v) or v < 0:
            errors.append(f"'{key}' must be numeric >= 0, got {v!r}")
    for key in ("batches_skipped", "records_read", "slow_ms"):
        if key in io and (not _is_num(io[key]) or io[key] < 0):
            errors.append(f"'{key}' must be numeric >= 0, "
                          f"got {io[key]!r}")
    return errors


# ---------------------------------------------------------------------------
# servescope bench section (extra.servescope)
# ---------------------------------------------------------------------------

def _check_servescope_group(grp, where: str) -> list:
    """One attribution group (overall or one bucket): count, ordered
    e2e percentiles, per-component distributions, and quantile-cohort
    attributions whose components are non-negative, whose sum_ms equals
    the component sum, and whose sum stays within tolerance of the e2e
    quantile it attributes."""
    errors = []
    if not isinstance(grp, dict):
        return [f"{where}: must be an object, got {type(grp).__name__}"]
    n = grp.get("count")
    if not isinstance(n, int) or isinstance(n, bool) or n < 0:
        errors.append(f"{where}: count must be an int >= 0, got {n!r}")
        return errors
    if n == 0:
        return errors
    e2e = grp.get("e2e_ms")
    if not isinstance(e2e, dict):
        errors.append(f"{where}: needs an 'e2e_ms' distribution object")
    else:
        pcts = [e2e.get(k) for k in ("p50", "p95", "p99")]
        if not all(_is_num(p) for p in pcts):
            errors.append(f"{where}: e2e_ms needs numeric p50/p95/p99, "
                          f"got {pcts!r}")
        elif not (pcts[0] <= pcts[1] <= pcts[2]):
            errors.append(f"{where}: e2e percentiles must be ordered, "
                          f"got {pcts!r}")
    dist = grp.get("component_dist")
    if not isinstance(dist, dict):
        errors.append(f"{where}: needs a 'component_dist' object")
    else:
        for key in SERVESCOPE_COMPONENTS:
            if key not in dist:
                errors.append(f"{where}: component_dist missing {key!r}")
        for key in dist:
            if key not in SERVESCOPE_COMPONENTS:
                errors.append(f"{where}: component_dist key {key!r} not "
                              f"in {SERVESCOPE_COMPONENTS}")
    att = grp.get("attribution")
    if not isinstance(att, dict):
        errors.append(f"{where}: needs an 'attribution' object")
        return errors
    for q, a in att.items():
        aw = f"{where}.attribution[{q!r}]"
        if not isinstance(a, dict):
            errors.append(f"{aw}: not an object")
            continue
        qe = a.get("e2e_ms")
        if not _is_num(qe) or qe < 0:
            errors.append(f"{aw}: e2e_ms must be numeric >= 0, got {qe!r}")
            continue
        comps = a.get("components")
        if not isinstance(comps, dict):
            errors.append(f"{aw}: needs a 'components' object")
            continue
        total = 0.0
        ok = True
        for key in SERVESCOPE_COMPONENTS:
            v = comps.get(key)
            if not _is_num(v) or v < 0:
                errors.append(f"{aw}: components[{key!r}] must be "
                              f"numeric >= 0, got {v!r}")
                ok = False
            else:
                total += v
        for key in comps:
            if key not in SERVESCOPE_COMPONENTS:
                errors.append(f"{aw}: component {key!r} not in "
                              f"{SERVESCOPE_COMPONENTS}")
        s = a.get("sum_ms")
        if not _is_num(s):
            errors.append(f"{aw}: needs numeric 'sum_ms', got {s!r}")
        elif ok and abs(total - s) > max(0.05, 0.01 * max(total, s)):
            # sum_ms IS the component sum (the spans' accounting
            # identity) — disagreement means a torn producer
            errors.append(f"{aw}: components sum to {total:.4g} but "
                          f"sum_ms={s:.4g}")
        if ok and _is_num(s) and qe > 0:
            off = abs(s - qe) / qe
            if off > SERVESCOPE_SUM_TOLERANCE:
                errors.append(
                    f"{aw}: attribution sums to {s:.4g} ms but the "
                    f"e2e quantile is {qe:.4g} ms ({off:.1%} apart, "
                    f"tolerance {SERVESCOPE_SUM_TOLERANCE:.0%})")
        top = a.get("top_component")
        if top is not None and top not in SERVESCOPE_COMPONENTS:
            errors.append(f"{aw}: top_component {top!r} not in "
                          f"{SERVESCOPE_COMPONENTS}")
    return errors


def check_servescope_extra(ss) -> list:
    """Validate an `extra.servescope` BENCH section: the sampling
    header, the closed component taxonomy, the overall + per-bucket
    attribution groups (cohort sums within tolerance of their e2e
    quantiles), bucket verdicts from the roofline taxonomy, and the
    device_exec provenance."""
    if ss is None:
        return []
    if not isinstance(ss, dict):
        return [f"must be an object, got {type(ss).__name__}"]
    errors = []
    se = ss.get("sample_every")
    if se is not None and (not isinstance(se, int)
                           or isinstance(se, bool) or se < 1):
        errors.append(f"sample_every must be an int >= 1, got {se!r}")
    comps = ss.get("components")
    if comps is not None and tuple(comps) != SERVESCOPE_COMPONENTS:
        errors.append(f"components {comps!r} != the closed taxonomy "
                      f"{SERVESCOPE_COMPONENTS}")
    src = ss.get("device_exec_source")
    if src is not None and src not in SERVESCOPE_DEVICE_SOURCES:
        errors.append(f"device_exec_source {src!r} not in "
                      f"{SERVESCOPE_DEVICE_SOURCES}")
    overall = ss.get("overall")
    if overall is None:
        errors.append("needs an 'overall' attribution group")
    else:
        errors += _check_servescope_group(overall, "overall")
    pb = ss.get("per_bucket")
    if pb is not None:
        if not isinstance(pb, dict):
            errors.append("per_bucket must be an object")
        else:
            for key, grp in pb.items():
                errors += _check_servescope_group(grp,
                                                  f"per_bucket[{key!r}]")
                if not isinstance(grp, dict):
                    continue
                v = grp.get("verdict")
                if v is not None and v not in ROOFLINE_VERDICTS:
                    errors.append(f"per_bucket[{key!r}]: verdict {v!r} "
                                  f"not in {ROOFLINE_VERDICTS}")
                r = grp.get("resharding_collectives")
                if r is not None and (not isinstance(r, int)
                                      or isinstance(r, bool) or r < 0):
                    errors.append(f"per_bucket[{key!r}]: "
                                  f"resharding_collectives must be an "
                                  f"int >= 0 or null, got {r!r}")
    return errors


def check_serve_load_extra(sl) -> list:
    """Validate an `extra.serve_load` BENCH section (tools/serve_load.py
    sweeps): an ordered ramp of per-level records with positive
    concurrency/qps and ordered percentiles, and a knee whose index and
    headline numbers agree with the level it points at."""
    if sl is None:
        return []
    if not isinstance(sl, dict):
        return [f"must be an object, got {type(sl).__name__}"]
    errors = []
    levels = sl.get("levels")
    if not isinstance(levels, list) or not levels:
        return errors + ["needs a non-empty 'levels' list"]
    prev_c = 0
    for i, lv in enumerate(levels):
        where = f"levels[{i}]"
        if not isinstance(lv, dict):
            errors.append(f"{where}: not an object")
            continue
        c = lv.get("concurrency")
        if not isinstance(c, int) or isinstance(c, bool) or c < 1:
            errors.append(f"{where}: concurrency must be an int >= 1, "
                          f"got {c!r}")
        elif c <= prev_c:
            errors.append(f"{where}: ramp must be strictly ascending "
                          f"({c} after {prev_c})")
        else:
            prev_c = c
        q = lv.get("qps")
        if not _is_num(q) or q <= 0:
            errors.append(f"{where}: qps must be positive, got {q!r}")
        pcts = [lv.get(k) for k in ("p50_ms", "p95_ms", "p99_ms")]
        if not all(_is_num(p) for p in pcts):
            errors.append(f"{where}: needs numeric p50/p95/p99_ms, "
                          f"got {pcts!r}")
        elif not (pcts[0] <= pcts[1] <= pcts[2]):
            errors.append(f"{where}: percentiles must be ordered, "
                          f"got {pcts!r}")
    ki = sl.get("knee_index")
    if not isinstance(ki, int) or isinstance(ki, bool) \
            or not 0 <= ki < len(levels):
        errors.append(f"knee_index {ki!r} outside the levels list")
        return errors
    knee = levels[ki] if isinstance(levels[ki], dict) else {}
    for key, lkey in (("knee_concurrency", "concurrency"),
                      ("qps_at_knee", "qps"),
                      ("p99_at_knee_ms", "p99_ms")):
        v, lv = sl.get(key), knee.get(lkey)
        if _is_num(v) and _is_num(lv) and v != lv:
            errors.append(f"{key}={v!r} disagrees with "
                          f"levels[{ki}].{lkey}={lv!r}")
    return errors


def check_fleet_extra(fl) -> list:
    """Validate an `extra.fleet` BENCH section (tools/serve_load.py
    ``--fleet N`` runs): a replica count that matches the per-replica
    rows, client-observed per-replica QPS + ordered percentiles, a
    dispatch-imbalance ratio that is mathematically possible (max/mean
    >= 1 once anything was dispatched), and router accounting that
    covers the per-replica totals."""
    if fl is None:
        return []
    if not isinstance(fl, dict):
        return [f"must be an object, got {type(fl).__name__}"]
    errors = []
    n = fl.get("replicas")
    if not isinstance(n, int) or isinstance(n, bool) or n < 1:
        errors.append(f"replicas must be an int >= 1, got {n!r}")
    rows = fl.get("per_replica")
    if not isinstance(rows, list) or not rows:
        return errors + ["needs a non-empty 'per_replica' list"]
    if isinstance(n, int) and not isinstance(n, bool) and n >= 1 \
            and len(rows) != n:
        errors.append(f"per_replica has {len(rows)} rows but "
                      f"replicas={n}")
    names = set()
    total_requests = 0
    for i, row in enumerate(rows):
        where = f"per_replica[{i}]"
        if not isinstance(row, dict):
            errors.append(f"{where}: not an object")
            continue
        name = row.get("name")
        if not isinstance(name, str) or not name:
            errors.append(f"{where}: needs a non-empty 'name'")
        elif name in names:
            errors.append(f"{where}: duplicate replica name {name!r}")
        else:
            names.add(name)
        reqs = row.get("requests")
        if not isinstance(reqs, int) or isinstance(reqs, bool) \
                or reqs < 0:
            errors.append(f"{where}: requests must be an int >= 0, "
                          f"got {reqs!r}")
        else:
            total_requests += reqs
        q = row.get("qps")
        if not _is_num(q) or q < 0:
            errors.append(f"{where}: qps must be >= 0, got {q!r}")
        pcts = [row.get(k) for k in ("p50_ms", "p95_ms", "p99_ms")]
        if reqs:
            if not all(_is_num(p) for p in pcts):
                errors.append(f"{where}: needs numeric p50/p95/p99_ms, "
                              f"got {pcts!r}")
            elif not (pcts[0] <= pcts[1] <= pcts[2]):
                errors.append(f"{where}: percentiles must be ordered, "
                              f"got {pcts!r}")
    imb = fl.get("dispatch_imbalance")
    if total_requests:
        # max/mean over a non-degenerate dispatch is >= 1 by definition;
        # anything below 1 means the numbers were not computed from the
        # same counts
        if not _is_num(imb) or imb < 1.0:
            errors.append(f"dispatch_imbalance must be >= 1 once "
                          f"requests flowed, got {imb!r}")
    routed = fl.get("routed")
    if not _is_num(routed) or routed < 0:
        errors.append(f"routed must be >= 0, got {routed!r}")
    elif routed < total_requests:
        errors.append(f"routed={routed} < sum of per-replica "
                      f"requests={total_requests} (lost accounting)")
    for key in ("routed_errors", "no_replica_available"):
        if key in fl and (not _is_num(fl[key]) or fl[key] < 0):
            errors.append(f"{key} must be >= 0, got {fl[key]!r}")
    return errors


def check_fleetscope_extra(fs) -> list:
    """Validate an `extra.fleetscope` BENCH section (tools/serve_load.py
    runs with cross-process tracing armed): trace accounting that adds
    up (joined never exceeds the sampled denominator, a join rate in
    [0, 1] that agrees with the counts, unjoined forwards counted — not
    guessed away), ordered wire-gap percentiles (durations, so clock
    skew cannot make them meaningfully negative), and per-replica rows
    with unique names."""
    if fs is None:
        return []
    if not isinstance(fs, dict):
        return [f"must be an object, got {type(fs).__name__}"]
    errors = []
    counts = {}
    for key in ("client_minted", "sampled", "joined",
                "unjoined_forwards"):
        v = fs.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errors.append(f"{key} must be an int >= 0, got {v!r}")
        else:
            counts[key] = v
    if "sampled" in counts and "joined" in counts \
            and counts["joined"] > counts["sampled"]:
        errors.append(f"joined={counts['joined']} exceeds "
                      f"sampled={counts['sampled']}")
    rate = fs.get("join_rate")
    if not _is_num(rate) or not (0.0 <= rate <= 1.0):
        errors.append(f"join_rate must be in [0, 1], got {rate!r}")
    elif "sampled" in counts and "joined" in counts and counts["sampled"]:
        want = counts["joined"] / counts["sampled"]
        if abs(rate - want) > 1e-6:
            errors.append(f"join_rate={rate} disagrees with "
                          f"joined/sampled={want:.6f}")
    gap = fs.get("wire_gap_ms")
    if gap is not None:
        if not isinstance(gap, dict):
            errors.append("wire_gap_ms must be an object of percentiles")
        else:
            pcts = [gap.get(k) for k in ("p50", "p95", "p99")]
            if not all(_is_num(p) for p in pcts):
                errors.append(f"wire_gap_ms needs numeric p50/p95/p99, "
                              f"got {pcts!r}")
            elif not (pcts[0] <= pcts[1] <= pcts[2]):
                errors.append(f"wire_gap_ms percentiles must be ordered, "
                              f"got {pcts!r}")
            elif pcts[0] < -1.0:
                # the gap is a DIFFERENCE OF DURATIONS (router-observed
                # forward minus replica-observed total), so no clock
                # offset enters it; anything past scheduling noise
                # negative means the join mixed up its sides
                errors.append(f"wire_gap_ms.p50={pcts[0]} < -1 ms — a "
                              f"duration difference cannot be this "
                              f"negative")
    rows = fs.get("per_replica")
    if rows is not None:
        if not isinstance(rows, list):
            return errors + ["per_replica must be a list"]
        names = set()
        for i, row in enumerate(rows):
            where = f"per_replica[{i}]"
            if not isinstance(row, dict):
                errors.append(f"{where}: not an object")
                continue
            name = row.get("name")
            if not isinstance(name, str) or not name:
                errors.append(f"{where}: needs a non-empty 'name'")
            elif name in names:
                errors.append(f"{where}: duplicate replica name {name!r}")
            else:
                names.add(name)
            t = row.get("traces")
            if not isinstance(t, int) or isinstance(t, bool) or t < 0:
                errors.append(f"{where}: traces must be an int >= 0, "
                              f"got {t!r}")
            for key in ("e2e_p99_ms", "wire_gap_p50_ms"):
                v = row.get(key)
                if v is not None and not _is_num(v):
                    errors.append(f"{where}: {key} must be numeric or "
                                  f"absent, got {v!r}")
    spread = fs.get("replica_spread")
    if spread is not None and (not _is_num(spread) or spread < 1.0):
        # max/median of per-replica p99 — >= 1 by construction once
        # any replica has traces
        errors.append(f"replica_spread must be >= 1 when present, "
                      f"got {spread!r}")
    return errors


def check_sharding_extra(sh) -> list:
    """Validate an `extra.sharding` section (mesh runs): a positive mesh shape, a mode from the closed taxonomy, and
    spec counts that add up to the param total."""
    if sh is None:
        return []
    if not isinstance(sh, dict):
        return [f"must be an object, got {type(sh).__name__}"]
    errors = []
    mesh = sh.get("mesh")
    if not isinstance(mesh, dict) or not mesh:
        errors.append(f"needs a non-empty 'mesh' axis->size object, "
                      f"got {mesh!r}")
    else:
        for ax, size in mesh.items():
            if not isinstance(size, int) or size < 1:
                errors.append(f"mesh[{ax!r}] must be a positive int, "
                              f"got {size!r}")
    if sh.get("mode") not in SHARDING_MODES:
        errors.append(f"mode {sh.get('mode')!r} not in {SHARDING_MODES}")
    if not isinstance(sh.get("fsdp"), bool):
        errors.append(f"fsdp must be a bool, got {sh.get('fsdp')!r}")
    counts = {}
    for key in ("params_total", "params_model_sharded",
                "params_data_sharded", "params_replicated"):
        v = sh.get(key)
        if not isinstance(v, int) or v < 0:
            errors.append(f"{key} must be an int >= 0, got {v!r}")
        else:
            counts[key] = v
    if len(counts) == 4:
        parts = (counts["params_model_sharded"]
                 + counts["params_data_sharded"]
                 + counts["params_replicated"])
        if parts != counts["params_total"]:
            errors.append(f"spec counts sum to {parts} but params_total="
                          f"{counts['params_total']}")
    for key in ("param_bytes_per_device", "state_bytes_per_device"):
        v = sh.get(key)
        if v is not None and (not _is_num(v) or v < 0):
            errors.append(f"{key} must be numeric >= 0 or absent, "
                          f"got {v!r}")
    return errors


def check_embedding_extra(em) -> list:
    """Validate an `extra.embedding` section (emitted by
    mxtpu.embedding.bench_extra): the table census
    (logical vs per-device bytes — sharded means per-device <=
    logical), the dedup accounting (rate in [0, 1], rows touched never
    above ids seen), and the closed out-of-range-id policy."""
    if em is None:
        return []
    if not isinstance(em, dict):
        return [f"must be an object, got {type(em).__name__}"]
    errors = []
    for key in ("tables", "table_bytes_logical", "table_bytes_per_device",
                "rows_total", "ids_per_step", "rows_touched_per_step",
                "oor_ids", "lookups"):
        v = em.get(key)
        if not _is_num(v) or v < 0:
            errors.append(f"{key} must be numeric >= 0, got {v!r}")
    logical = em.get("table_bytes_logical")
    per_dev = em.get("table_bytes_per_device")
    if _is_num(logical) and _is_num(per_dev) and per_dev > logical:
        errors.append(f"table_bytes_per_device={per_dev} exceeds the "
                      f"replicated footprint table_bytes_logical={logical}")
    rate = em.get("dedup_rate")
    if not _is_num(rate) or not (0.0 <= rate <= 1.0):
        errors.append(f"dedup_rate must be in [0, 1], got {rate!r}")
    ids = em.get("ids_per_step")
    rows = em.get("rows_touched_per_step")
    if _is_num(ids) and _is_num(rows) and rows > ids:
        errors.append(f"rows_touched_per_step={rows} exceeds "
                      f"ids_per_step={ids}")
    if em.get("oor_policy") not in ("clip", "error"):
        errors.append(f"oor_policy {em.get('oor_policy')!r} not in "
                      f"('clip', 'error')")
    return errors


# ---------------------------------------------------------------------------
# run artifact JSON (with serving stats)
# ---------------------------------------------------------------------------

def check_resilience_extra(rx) -> list:
    """Validate a BENCH `extra.resilience` block (resilience.bench_extra):
    recovery accounting must be numeric and non-negative, the save/copy
    cost blocks must carry ordered percentiles, and a recovery count
    implies a rollback/resume trail (a recovered run is USABLE but its
    cost must be visible — perf_regress notes it, never hides it)."""
    if rx is None:
        return []
    if not isinstance(rx, dict):
        return ["must be an object"]
    errors = []
    for key in ("checkpoints_saved", "recoveries_total", "rollbacks",
                "steps_lost_last", "steps_lost_total"):
        v = rx.get(key)
        if not _is_num(v):
            errors.append(f"needs numeric {key!r}, got {v!r}")
        elif v < 0:
            errors.append(f"{key}={v} negative")
    lcs = rx.get("last_checkpoint_step")
    if lcs is not None and not _is_num(lcs):
        errors.append(f"last_checkpoint_step must be numeric or null, "
                      f"got {lcs!r}")
    for blk in ("save", "copy"):
        b = rx.get(blk)
        if b is None:
            continue
        if not isinstance(b, dict):
            errors.append(f"{blk} block must be an object or null")
            continue
        if not _is_num(b.get("count")) or b["count"] < 0:
            errors.append(f"{blk}.count must be numeric >= 0, "
                          f"got {b.get('count')!r}")
        p50, p95 = b.get("p50_ms"), b.get("p95_ms")
        for k, v in (("p50_ms", p50), ("p95_ms", p95)):
            if v is not None and not _is_num(v):
                errors.append(f"{blk}.{k} must be numeric or null")
        if _is_num(p50) and _is_num(p95) and p50 > p95:
            errors.append(f"{blk} percentiles out of order "
                          f"(p50={p50} > p95={p95})")
    if _is_num(rx.get("every")) and rx["every"] < 0:
        errors.append(f"every={rx['every']} negative")
    if _is_num(rx.get("keep")) and rx["keep"] < 1:
        errors.append(f"keep={rx['keep']} < 1")
    if _is_num(rx.get("recoveries_total")) and rx["recoveries_total"] > 0:
        trail = sum(rx.get(k, 0) or 0
                    for k in ("rollbacks", "resumes", "rank_departures")
                    if _is_num(rx.get(k)))
        if trail == 0:
            errors.append(
                f"recoveries_total={rx['recoveries_total']} with no "
                f"rollback/resume/departure trail — a recovery must say "
                f"what it was")
    return errors


def check_bench_json(path: str) -> list:
    """Validate a run artifact (tools/serve_load.py's result file).
    Core keys always; when the
    run was the serving benchmark, its `extra.serving` section must carry
    well-formed latency histograms and request accounting."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable/invalid JSON: {e}"]
    errors = []
    if not isinstance(doc, dict):
        return [f"{path}: bench result must be a JSON object"]
    if not isinstance(doc.get("metric"), str) or not doc["metric"]:
        errors.append("missing/empty 'metric'")
    if not _is_num(doc.get("value")):
        errors.append(f"needs numeric 'value', got {doc.get('value')!r}")
    extra = doc.get("extra") or {}
    # training benches must carry MFU (ROADMAP item 1: regressions visible
    # per-PR) — null on a device outside the peak table (a CPU run has no
    # utilisation to report). Serving benches and error results are exempt.
    if (isinstance(extra, dict) and extra
            and "serving" not in extra and "error" not in doc):
        mfu = extra.get("mfu")
        if "mfu" not in extra or (mfu is not None and not _is_num(mfu)):
            errors.append(f"training bench extra needs 'mfu' (numeric, or "
                          f"null without device peaks), got {mfu!r}")
        elif mfu is not None and not (0.0 <= mfu <= 1.5):
            errors.append(f"extra.mfu={mfu} outside [0, 1.5] — wrong "
                          f"peak-FLOPs or flops-per-sample accounting")
    errors += [f"extra.perfscope: {e}"
               for e in check_perfscope_extra(
                   (doc.get("extra") or {}).get("perfscope"))]
    errors += [f"extra.commscope: {e}"
               for e in check_commscope_extra(
                   (doc.get("extra") or {}).get("commscope"))]
    errors += [f"extra.devicescope: {e}"
               for e in check_devicescope_extra(
                   (doc.get("extra") or {}).get("devicescope"))]
    errors += [f"extra.memscope: {e}"
               for e in check_memscope_extra(
                   (doc.get("extra") or {}).get("memscope"))]
    errors += [f"extra.sharding: {e}"
               for e in check_sharding_extra(
                   (doc.get("extra") or {}).get("sharding"))]
    errors += [f"extra.servescope: {e}"
               for e in check_servescope_extra(
                   (doc.get("extra") or {}).get("servescope"))]
    errors += [f"extra.serve_load: {e}"
               for e in check_serve_load_extra(
                   (doc.get("extra") or {}).get("serve_load"))]
    errors += [f"extra.fleet: {e}"
               for e in check_fleet_extra(
                   (doc.get("extra") or {}).get("fleet"))]
    errors += [f"extra.resilience: {e}"
               for e in check_resilience_extra(
                   (doc.get("extra") or {}).get("resilience"))]
    errors += [f"extra.mxlint: {e}"
               for e in check_mxlint_extra(
                   (doc.get("extra") or {}).get("mxlint"))]
    errors += [f"extra.io: {e}"
               for e in check_io_extra(
                   (doc.get("extra") or {}).get("io"))]
    errors += [f"extra.embedding: {e}"
               for e in check_embedding_extra(
                   (doc.get("extra") or {}).get("embedding"))]
    errors += [f"extra.fleetscope: {e}"
               for e in check_fleetscope_extra(
                   (doc.get("extra") or {}).get("fleetscope"))]
    serving = (doc.get("extra") or {}).get("serving")
    if serving is not None:
        if not isinstance(serving, dict):
            return [f"{path}: extra.serving must be an object"]
        for key in ("requests", "responses", "batches", "batch_fill",
                    "p50_ms", "p95_ms", "p99_ms", "qps"):
            if not _is_num(serving.get(key)):
                errors.append(f"extra.serving needs numeric {key!r}, "
                              f"got {serving.get(key)!r}")
        for key in ("rejected_queue_full", "rejected_deadline",
                    "rejected_deadline_post_batch", "rejected_invalid"):
            if key in serving and not _is_num(serving[key]):
                errors.append(f"extra.serving[{key!r}] must be numeric")
        hist = serving.get("latency_ms")
        if hist is None:
            errors.append("extra.serving needs a 'latency_ms' histogram")
        else:
            errors += [f"extra.serving.latency_ms: {e}"
                       for e in check_histogram_snapshot(hist)]
            if isinstance(hist, dict) and _is_num(serving.get("responses")) \
                    and _is_num(hist.get("count")) \
                    and hist["count"] < serving["responses"]:
                errors.append(
                    f"latency_ms.count={hist['count']} < "
                    f"responses={serving['responses']} (lost observations)")
        if _is_num(serving.get("batch_fill")) and serving["batch_fill"] < 1.0:
            errors.append(f"batch_fill={serving['batch_fill']} < 1.0 "
                          f"(more batches than requests?)")
        ordered = [serving.get(k) for k in ("p50_ms", "p95_ms", "p99_ms")]
        if all(_is_num(p) for p in ordered) and \
                not (ordered[0] <= ordered[1] <= ordered[2]):
            errors.append(f"serving percentiles must be ordered, "
                          f"got {ordered!r}")
    return [f"{path}: {e}" for e in errors]


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def check_file(path: str) -> list:
    """Validate one file, auto-detecting its kind: `.prom`/`.txt` →
    Prometheus, `.jsonl` → metrics time series, JSON object with a
    flight `schema` → flight dump, a bench result (has `metric` +
    `value`) → bench JSON, anything else → Chrome trace."""
    low = path.lower()
    if low.endswith((".prom", ".txt")):
        return check_prom(path)
    if low.endswith(".jsonl"):
        # events vs metrics series: event records are self-describing
        # (every line carries the schema tag), so sniff the first line
        try:
            with open(path) as f:
                first = f.readline()
        except OSError as e:
            return [f"{path}: unreadable: {e}"]
        if f'"{EVENTS_SCHEMA_PREFIX}' in first:
            return check_events_jsonl(path)
        return check_metrics_jsonl(path)
    try:
        with open(path) as f:
            head = f.read(4096)
    except OSError as e:
        return [f"{path}: unreadable: {e}"]
    if f'"{FLIGHT_SCHEMA_PREFIX}' in head:
        return check_flight(path)
    if '"metric"' in head and '"value"' in head:
        # bench result detection must parse the WHOLE document — a
        # serving/diag bench json easily exceeds the 4 KB sniff window
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = None
        if isinstance(doc, dict) and "metric" in doc and "value" in doc:
            return check_bench_json(path)
    return check_trace(path)


def main(argv) -> int:
    if not argv:
        print(__doc__.strip().splitlines()[0])
        print("usage: python tools/trace_check.py FILE [...]")
        return 2
    rc = 0
    for path in argv:
        errors = check_file(path)
        if errors:
            rc = 1
            for e in errors:
                print(e, file=sys.stderr)
        else:
            print(f"{path}: OK")
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
