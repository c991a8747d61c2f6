"""mxtpu.memscope — per-program memory footprints, device watermark
timelines, and OOM forensics.

The eighth observability layer (docs/observability.md). The earlier
layers explain *time* — perfscope's rooflines, devicescope's measured
timelines, commscope's collectives, servescope's request tails — but
*memory*, the resource that bounds batch × remat × mesh and the
classic way a TPU run dies
(``RESOURCE_EXHAUSTED`` with no attribution), had no layer. Memscope is
that layer:

* **static per-program footprints** (:mod:`.footprint`) — every
  perfscope compile site (FusedTrainStep, TrainLoop chunks, the
  hybridize jit cache, serving buckets) additionally captures XLA's
  ``compiled.memory_analysis()`` — argument / output / temp /
  generated-code bytes and the peak — into a program table joined to
  the roofline verdicts by name. Backends without the analysis are
  counted ``unavailable``, never raised.
* **runtime watermark timeline** (:mod:`.watermark`) — a bounded ring
  (``MXTPU_MEMSCOPE_RING``, default 256) of per-step-boundary
  ``device.memory_stats()`` samples (bytes_in_use, peak_bytes_in_use)
  plus host RSS, sampled at the existing step marks so the off path
  pays one predicate, feeding p50/p95/peak gauges and a headroom
  fraction.
* **OOM forensics** (:mod:`.forensics`) — a ``RESOURCE_EXHAUSTED`` /
  allocator-failure hook on the dispatch sites that assembles a
  post-mortem (the offending program's static footprint, the watermark
  tail, top-K live buffers from the diagnostics ledger, the resolved
  settings) and lands it on the healthmon alert surfaces, so an OOM
  names its program instead of dying mute.
* **headroom** — live bytes against device capacity ×
  ``MXTPU_MEMSCOPE_HEADROOM``; fleet/serving admission embeds it in
  deep ``/healthz`` so the router can weigh it.

Everything lands in the ``memscope.*`` counter family,
``extra.memscope`` in artifact json, and ``tools/mxdiag.py mem``.

Fast-path contract: the single module global ``_MS`` (the perfscope /
commscope / devicescope discipline) — every passive hook costs one
predicate when memscope is off, and ingestion never raises.
"""
from __future__ import annotations

import os
import warnings

from ..diagnostics import flight as _flight
from ..profiler.counters import counter as _counter
from . import footprint as _footprint
from . import forensics as _forensics
from . import watermark as _watermark
from .footprint import capture, footprints, footprint_of
from .forensics import is_oom_error, post_mortem, record_oom, \
    last_post_mortem
from .watermark import WatermarkRing, host_rss_bytes

__all__ = ["enable", "disable", "enabled", "enable_from_env", "reset",
           "capture", "footprints", "footprint_of", "sample",
           "watermark_summary", "device_capacity", "headroom_target",
           "headroom_state", "register_analytic", "reconciliation",
           "bench_extra", "is_oom_error", "post_mortem", "record_oom",
           "last_post_mortem", "WatermarkRing", "host_rss_bytes",
           "DRIFT_THRESHOLD", "DEFAULT_HEADROOM", "DEFAULT_RING"]

# analytic-vs-measured relative disagreement that fires the loud drift
# warning — deliberately the same 25% devicescope established, so one
# number means "an estimate went stale" across the whole layer map
DRIFT_THRESHOLD = 0.25

# usable fraction of device capacity: a candidate whose predicted peak
# exceeds capacity * headroom is infeasible (MXTPU_MEMSCOPE_HEADROOM)
DEFAULT_HEADROOM = 0.9

# watermark ring bound (MXTPU_MEMSCOPE_RING)
DEFAULT_RING = 256

# module global: None = memscope off (THE fast-path predicate)
_MS = None

# analytic per-device expectation registered by an FSDP-aware call site
# (fsdp.memory_report) — the reconciliation's analytic side
_ANALYTIC = None


class _MemScope:
    """Marker object holding enable-time state (the perfscope
    module-global discipline). Owns the watermark ring."""

    def __init__(self, ring_limit=None):
        if ring_limit is None:
            from ..settings import env_int
            ring_limit = env_int("MXTPU_MEMSCOPE_RING", DEFAULT_RING,
                                 on_error="default")
        self.ring = WatermarkRing(ring_limit)


def enable(ring_limit=None):
    """Arm memscope: perfscope's compile sites start capturing static
    footprints, the step marks start feeding the watermark ring, and
    the OOM guards start assembling post-mortems.

    Arms perfscope too when it is off — the footprint capture hook
    lives inside perfscope's analyze funnel (the commscope
    discipline), so memscope without perfscope would see no compiles.
    """
    global _MS
    try:
        from .. import perfscope as _ps
        if _ps._PS is None:
            _ps.enable()
    except Exception:  # noqa: BLE001 — arming must never raise
        pass
    _MS = _MemScope(ring_limit)
    return _MS


def disable():
    global _MS
    _MS = None


def enabled() -> bool:
    return _MS is not None


def enable_from_env():
    """MXTPU_MEMSCOPE=1 arms memscope at import (like MXTPU_PERFSCOPE /
    MXTPU_DEVICESCOPE)."""
    if os.environ.get("MXTPU_MEMSCOPE", "") == "1":
        enable()


def reset():
    """Test hook: drop the footprint table, the ring, the last
    post-mortem and any registered analytic expectation."""
    global _ANALYTIC
    _ANALYTIC = None
    _footprint.reset()
    _forensics.reset()
    if _MS is not None:
        _MS.ring.reset()


# ---------------------------------------------------------------------------
# watermark surface (delegates to the armed ring)
# ---------------------------------------------------------------------------

def sample(step=None, workload=None):
    """Take one watermark sample into the armed ring (the step-mark
    hook). No-op returning None when memscope is off. Never raises."""
    ms = _MS
    if ms is None:
        return None
    return ms.ring.sample(step=step, workload=workload)


def watermark_summary():
    """The armed ring's p50/p95/peak summary, or the armed-but-empty
    shape; None when memscope is off."""
    ms = _MS
    if ms is None:
        return None
    return ms.ring.summary()


# ---------------------------------------------------------------------------
# capacity + headroom
# ---------------------------------------------------------------------------

def headroom_target() -> float:
    """Usable fraction of capacity (MXTPU_MEMSCOPE_HEADROOM, default
    0.9): predicted peaks above capacity * target are infeasible."""
    from ..settings import env_float
    v = env_float("MXTPU_MEMSCOPE_HEADROOM", DEFAULT_HEADROOM,
                  on_error="default")
    try:
        v = float(v)
    except (TypeError, ValueError):
        return DEFAULT_HEADROOM
    return v if 0.0 < v <= 1.0 else DEFAULT_HEADROOM


def device_capacity() -> dict:
    """Per-accelerator memory capacity, ``{"bytes", "source"}`` (+
    ``per_device`` when the allocator reports limits).

    Resolution: ``MXTPU_MEMSCOPE_CAPACITY`` override >
    ``memory_stats()["bytes_limit"]`` (the tightest device bounds) >
    host RAM (the honest bound on XLA:CPU, where device stats are
    absent) > unknown. Never raises."""
    from ..settings import env_int
    override = env_int("MXTPU_MEMSCOPE_CAPACITY", None,
                       on_error="default")
    if override:
        return {"bytes": int(override), "source": "env"}
    try:
        import jax
        per = {}
        for d in jax.local_devices():
            try:
                st = d.memory_stats()
            except Exception:  # noqa: BLE001
                st = None
            if st and st.get("bytes_limit"):
                per[str(d)] = int(st["bytes_limit"])
        if per:
            return {"bytes": min(per.values()),
                    "source": "memory_stats", "per_device": per}
    except Exception:  # noqa: BLE001
        pass
    try:
        cap = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
        if cap > 0:
            return {"bytes": int(cap), "source": "host_ram"}
    except (ValueError, OSError, AttributeError):
        pass
    return {"bytes": None, "source": "unknown"}


def headroom_state() -> dict:
    """Live headroom verdict: how much of capacity is in use right now,
    and whether the configured target still holds.

    ``in_use`` pairs with its matching capacity source — device
    bytes_in_use against the allocator limit when the backend reports
    both, host RSS against host RAM on backends (XLA:CPU) that report
    neither — so the fraction always compares like with like."""
    cap = device_capacity()
    target = headroom_target()
    out = {"capacity_bytes": cap.get("bytes"),
           "capacity_source": cap.get("source"),
           "in_use_bytes": None, "in_use_source": None,
           "headroom_fraction": None, "target": target,
           "verdict": "unknown"}
    ms = _MS
    latest = ms.ring.latest() if ms is not None else None
    in_use = None
    if latest is not None and latest.get("available"):
        vals = [d.get("bytes_in_use") or 0
                for d in latest.get("devices", {}).values()
                if isinstance(d, dict) and d.get("bytes_in_use")]
        if vals:
            in_use = max(vals)
            out["in_use_source"] = "memory_stats"
    if in_use is None:
        rss = latest.get("host_rss_bytes") if latest is not None \
            else host_rss_bytes()
        if rss and cap.get("source") in ("host_ram", "env", "unknown"):
            in_use = rss
            out["in_use_source"] = "host_rss"
    if in_use is not None and cap.get("bytes"):
        out["in_use_bytes"] = int(in_use)
        frac = 1.0 - float(in_use) / float(cap["bytes"])
        out["headroom_fraction"] = round(max(0.0, frac), 6)
        out["verdict"] = "ok" if float(in_use) <= cap["bytes"] * target \
            else "tight"
        try:
            from ..profiler.counters import set_gauge as _set_gauge
            _set_gauge("memscope.headroom_fraction",
                       out["headroom_fraction"], "memscope")
        except Exception:  # noqa: BLE001
            pass
    return out


# ---------------------------------------------------------------------------
# analytic-vs-measured reconciliation
# ---------------------------------------------------------------------------

def register_analytic(report, source="fsdp.memory_report"):
    """Hand memscope an analytic per-device expectation
    (``parallel/fsdp.memory_report`` under fsdp meshes) — the
    reconciliation's analytic side. Never raises; a malformed report is
    dropped."""
    global _ANALYTIC
    try:
        if not isinstance(report, dict):
            return
        per = report.get("param_bytes_per_device")
        state = report.get("state_bytes_per_device")
        if per is None:
            return
        _ANALYTIC = {"param_bytes_per_device": int(per),
                     "state_bytes_per_device": int(state or 0),
                     "total_per_device": int(per) + int(state or 0),
                     "reduction": report.get("reduction"),
                     "source": source}
    except Exception:  # noqa: BLE001 — registration never breaks callers
        _ANALYTIC = None


def reconciliation() -> dict:
    """Analytic per-device bytes (fsdp.memory_report, when registered)
    BESIDE the measured truth — watermark device peaks when the
    allocator reports them, the diagnostics ledger's sharding-aware
    live census otherwise — with the devicescope drift discipline:
    >25% disagreement fires the loud warning, and the analytic number
    stays in the block either way."""
    measured = {"peak_bytes_in_use": None, "per_device_live_bytes": None,
                "source": None}
    ms = _MS
    if ms is not None:
        s = ms.ring.summary()
        dev = (s or {}).get("device") or {}
        if dev.get("peak"):
            measured["peak_bytes_in_use"] = dev["peak"]
            measured["source"] = "memory_stats"
    if measured["source"] is None:
        try:
            from ..diagnostics.memory import reconcile as _ledger_rec
            rec = _ledger_rec()
            per = rec.get("per_device_live_bytes")
            if per:
                measured["per_device_live_bytes"] = dict(per)
                measured["peak_bytes_in_use"] = max(per.values())
                measured["source"] = "ledger_census"
        except Exception:  # noqa: BLE001
            pass
    out = {"analytic": dict(_ANALYTIC) if _ANALYTIC else None,
           "measured": measured,
           "drift": None, "threshold": DRIFT_THRESHOLD,
           "drift_warning": False}
    if _ANALYTIC and measured["peak_bytes_in_use"]:
        analytic = float(_ANALYTIC["total_per_device"])
        meas = float(measured["peak_bytes_in_use"])
        if analytic > 1e-9:
            drift = abs(meas - analytic) / analytic
            out["drift"] = {"per_device_bytes": round(drift, 6)}
            if drift > DRIFT_THRESHOLD:
                out["drift_warning"] = True
                _warn_drift(analytic, meas, drift)
    return out


def _warn_drift(analytic, measured, drift):
    """The loud estimate-went-stale signal: counter + flight breadcrumb
    + structured event + Python warning. Never raises."""
    try:
        _counter("memscope.drift_warnings", "memscope").increment()
        if _flight._REC is not None:
            _flight.record("alert", "memscope.drift", {
                "analytic_bytes": analytic, "measured_bytes": measured,
                "drift": round(drift, 4),
                "threshold": DRIFT_THRESHOLD})
        try:
            from .. import healthmon as _hm
            if _hm._HM is not None:
                _hm._HM.events.emit(
                    "alert", "memscope.drift",
                    args={"analytic_bytes": analytic,
                          "measured_bytes": measured,
                          "threshold": DRIFT_THRESHOLD})
        except Exception:  # noqa: BLE001
            pass
        warnings.warn(
            f"memscope: analytic per-device bytes "
            f"({analytic / 2**20:.1f} MiB) and measured peak "
            f"({measured / 2**20:.1f} MiB) disagree by {drift:.0%} "
            f"(threshold {DRIFT_THRESHOLD:.0%}) — the FSDP memory "
            f"claim has gone stale against the allocator; trust the "
            f"measurement (docs/memscope.md)", stacklevel=3)
    except Exception:  # noqa: BLE001 — warning plumbing must never raise
        pass


# ---------------------------------------------------------------------------
# extra.memscope payload
# ---------------------------------------------------------------------------

def _programs_joined() -> list:
    """The footprint table with each record joined to its perfscope
    roofline verdict by name (the memscope-perfscope join key)."""
    progs = footprints()
    roof = {}
    try:
        from ..perfscope import cost as _cost
        roof = {r.get("name"): r for r in _cost.programs()}
    except Exception:  # noqa: BLE001
        roof = {}
    for rec in progs:
        r = roof.get(rec.get("name"))
        rec["roofline"] = r.get("verdict") if r else None
    return progs


def bench_extra() -> dict:
    """The ``extra.memscope`` payload for artifact json: the footprint
    table joined to the roofline verdicts, the watermark summary, the
    capacity/headroom verdict, the analytic-vs-measured
    reconciliation, and the last OOM post-mortem (usually None)."""
    return {"programs": _programs_joined(),
            "watermarks": watermark_summary(),
            "capacity": device_capacity(),
            "headroom": headroom_state(),
            "reconciliation": reconciliation(),
            "oom": last_post_mortem()}
