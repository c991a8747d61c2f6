"""mxtpu.devicescope — measured device-timeline ground truth.

The sixth observability layer (docs/observability.md). Everything the
earlier layers say about where step time goes is *derived*: perfscope's
``device_compute`` comes from a fetch-barrier probe, commscope's
``collective`` from a ring-model estimate that is ALWAYS marked
estimated. Devicescope is the layer that **measures what the device
actually did** and keeps those estimates honest:

* **windowed capture** (:mod:`.window`) — ``devicescope.capture
  (steps=N)`` wraps a bounded N-step window of the steady train loop in
  ``jax.profiler.trace``. Off by default; the artifact dir is rotated
  (``MXTPU_DEVICESCOPE_KEEP``, default 3) so repeated runs don't grow
  it unboundedly.
* **trace ingestion** (:mod:`.ingest`) — the emitted Chrome-trace
  artifact (works on XLA:CPU in tier-1, no TPU required) parses into
  per-lane device events and yields measured truth: device **busy
  fraction**, **top-K ops/fusions** by device time (joined to
  perfscope's program table by ``hlo_module`` name, so each hot fusion
  carries its roofline verdict), **collective-lane time** per kind with
  commscope mesh-axis attribution, and an **idle-gap histogram**
  classified input-starved / dispatch-serialized / host-gap from the
  ``io.*`` / ``trainloop.dispatch_ms`` counters.
* **reconciliation** (:func:`budget_overrides`) — when a completed
  window exists, perfscope's :class:`StepBudget` upgrades its
  provenance to ``measured(profile)``: measured ``device_compute`` /
  ``collective`` replace the probe/estimate numbers (which stay beside
  them in the reconciliation block), and a LOUD drift warning — counter
  + flight breadcrumb + structured event — fires when analytic and
  measured disagree by more than :data:`DRIFT_THRESHOLD` (25%): the
  signal that an estimate went stale.

Everything lands in the ``devicescope.*`` counter family,
``extra.devicescope`` in artifact json, and ``tools/mxdiag.py device``.

Fast-path contract: the single module global ``_DS`` (the perfscope /
commscope / healthmon discipline) — every passive hook costs one
predicate when devicescope is off, and a run that never opens a window
pays nothing at all.
"""
from __future__ import annotations

import os
import threading
import warnings

from ..diagnostics import flight as _flight
from ..profiler.counters import counter as _counter
from . import ingest
from . import window as _window
from .ingest import summarize, device_events, union_intervals, \
    collective_kind_of, load_trace_events, find_trace_file
from .window import CaptureWindow

__all__ = ["enable", "disable", "enabled", "enable_from_env", "capture",
           "active_window", "last_window", "last_window_path",
           "window_summary", "register_program", "module_name_of",
           "program_map", "budget_overrides", "bench_extra", "reset",
           "CaptureWindow", "DRIFT_THRESHOLD", "ingest", "summarize",
           "device_events", "union_intervals", "collective_kind_of",
           "load_trace_events", "find_trace_file"]

# analytic-vs-measured relative disagreement that triggers the loud
# drift warning (the estimate-went-stale signal)
DRIFT_THRESHOLD = 0.25

# module global: None = devicescope off (THE fast-path predicate)
_DS = None

# capture state: the currently-tracing window, and the last completed
# one (what reconciliation / healthmon post-mortems read)
_ACTIVE = None
_LAST = None

# hlo_module name -> perfscope program name, recorded at compile capture
# (perfscope's analyze hooks call register_program when armed) — the
# join key between trace lanes and the roofline table
_MODULES: "dict[str, str]" = {}
_mlock = threading.Lock()


class _DeviceScope:
    """Marker object holding enable-time options (the perfscope
    module-global discipline)."""

    def __init__(self):
        pass


def enable():
    """Arm devicescope: compile sites start recording the hlo_module →
    program join map, and :func:`capture` windows feed the step budget.
    Capture itself stays explicit — arming costs nothing per step."""
    global _DS
    _DS = _DeviceScope()
    return _DS


def disable():
    global _DS, _ACTIVE, _LAST
    if _ACTIVE is not None:
        try:
            _ACTIVE.stop()
        except Exception:  # noqa: BLE001
            pass
    _DS = None
    _ACTIVE = None
    _LAST = None


def enabled() -> bool:
    return _DS is not None


def enable_from_env():
    """MXTPU_DEVICESCOPE=1 arms devicescope at import (like
    MXTPU_PERFSCOPE / MXTPU_COMMSCOPE)."""
    if os.environ.get("MXTPU_DEVICESCOPE", "") == "1":
        enable()


def reset():
    """Test hook: drop capture state and the module join map."""
    global _ACTIVE, _LAST
    _ACTIVE = None
    _LAST = None
    with _mlock:
        _MODULES.clear()


# ---------------------------------------------------------------------------
# capture surface
# ---------------------------------------------------------------------------

def capture(steps: int = 10, logdir: str | None = None) -> CaptureWindow:
    """A bounded capture window over the next ``steps`` train steps.

    Arms devicescope if it isn't already (an explicit capture IS the
    opt-in). Use as a context manager around a loop that marks its own
    steps (TrainLoop.run_chunk marks automatically), or drive
    ``start()`` / ``step()`` / ``stop()`` by hand::

        with mx.devicescope.capture(steps=10) as win:
            loop.fit(data, steps=200)      # window stops itself at 10
        print(win.summary()["busy_fraction"])
    """
    if _DS is None:
        enable()
    return CaptureWindow(steps=steps, logdir=logdir)


def _set_active(win):
    global _ACTIVE
    _ACTIVE = win


def _set_last(win):
    global _LAST
    _LAST = win


def active_window():
    """The currently-tracing window (what instrumented executors mark),
    or None."""
    return _ACTIVE


def last_window():
    """The most recently completed window object, or None."""
    return _LAST


def last_window_path():
    """Artifact dir of the last completed window — what healthmon
    attaches to stall/NaN post-mortems. None when no window completed."""
    w = _LAST
    return w.logdir if w is not None else None


def window_summary():
    """The last completed window's measured summary (ingested lazily),
    or None — the perfscope step budget's reconciliation source."""
    w = _LAST
    if w is None:
        return None
    return w.summary()


# ---------------------------------------------------------------------------
# program join map (compile-site hook)
# ---------------------------------------------------------------------------

def register_program(program_name: str, module_name) -> None:
    """Record that perfscope program ``program_name`` lowered to HLO
    module ``module_name`` — called from perfscope's analyze hooks when
    devicescope is armed. The trace's ``hlo_module`` arg joins through
    this map.

    Module names are NOT unique across programs (every hybridized
    Block jits a function named ``raw_fn``, so all of them lower to
    ``jit_raw_fn``): a module seen under two different program names is
    POISONED to None — ambiguous attribution is reported as unjoined,
    never guessed (the same rule as the collective axis join).
    Re-registering the same (module, program) pair — a batch-signature
    re-analysis — keeps the join."""
    if not module_name:
        return
    mod = str(module_name)
    with _mlock:
        if mod in _MODULES and _MODULES[mod] != str(program_name):
            _MODULES[mod] = None
        else:
            _MODULES[mod] = str(program_name)


def module_name_of(lowered):
    """The HLO module name of a lowered jax stage ("jit_step_fn"), or
    None. Never raises — the MLIR surface is backend/version-dependent."""
    try:
        attr = lowered.compiler_ir().operation.attributes["sym_name"]
        v = getattr(attr, "value", None)
        if v:
            return str(v)
        return str(attr).strip('"')
    except Exception:  # noqa: BLE001
        pass
    try:
        import re
        head = lowered.as_text()[:300]
        m = re.search(r"module @([\w.\-]+)", head)
        return m.group(1) if m else None
    except Exception:  # noqa: BLE001
        return None


def program_map() -> dict:
    with _mlock:
        return dict(_MODULES)


# ---------------------------------------------------------------------------
# step-budget reconciliation
# ---------------------------------------------------------------------------

def _drift(analytic, measured):
    """Relative disagreement, None when the analytic side is ~0 (no
    basis to reconcile against)."""
    if analytic is None or measured is None or analytic <= 1e-9:
        return None
    return abs(measured - analytic) / analytic


def budget_overrides(step_ms, device, collective, collective_source,
                     source, since=None):
    """Measured overrides for one settled step budget, or None.

    Called from :meth:`perfscope.StepBudget.finish` with the ANALYTIC
    components (probe device time, kvstore/commscope collective).
    When devicescope is armed and a completed window measured device
    activity, returns::

        {"device_compute_ms", "collective_ms", "collective_source",
         "source", "reconciliation"}

    * ``device_compute`` becomes the window's per-step busy time minus
      its measured collective share (clipped at step_ms), provenance
      ``measured(profile)``;
    * ``collective`` is overridden — and its provenance upgraded — only
      when the window actually measured collective lanes (a measured 0
      with host-side kvstore collectives would erase a real
      measurement: host collectives never appear on device lanes);
    * the reconciliation block keeps the analytic numbers BESIDE the
      measured ones and carries the drift verdict; >25% disagreement
      additionally fires the loud drift warning (counter + flight
      breadcrumb + structured event + Python warning).

    ``since``: a ``time.monotonic()`` reference (the budget's begin
    time) — a window completed BEFORE it is someone else's steady
    phase, and stale measurements must not be presented with the
    strongest provenance against a workload they never saw.

    Returns None (no override, budget falls back exactly as today) when
    devicescope is off or no usable window exists."""
    if _DS is None:
        return None
    w = _LAST
    if since is not None and w is not None \
            and (w.completed_at is None or w.completed_at < float(since)):
        return None               # stale window: predates this budget
    if w is not None and getattr(w, "workload", None) \
            not in (None, "train"):
        # workload identity, not just freshness: a window stepped by
        # the serving batcher (or by both loops — "mixed") measured
        # dispatches this TRAIN budget never issued; upgrading from it
        # would pin measured(profile) on someone else's busy time.
        # None stays accepted for steppers that predate the stamp.
        return None
    try:
        s = window_summary()
    except Exception:  # noqa: BLE001
        return None
    if not isinstance(s, dict) or not isinstance(s.get("per_step"), dict):
        return None
    per = s["per_step"]
    meas_busy = per.get("device_busy_ms")
    meas_coll = per.get("collective_ms") or 0.0
    if not isinstance(meas_busy, (int, float)) or meas_busy <= 0.0:
        return None
    step_ms = float(step_ms)
    meas_busy = float(meas_busy)
    meas_coll = float(meas_coll)
    new_coll = float(collective)
    new_coll_src = collective_source
    if meas_coll > 0.0:
        new_coll = min(meas_coll, step_ms)
        new_coll_src = "measured(profile)"
    # device = busy minus its collective share, capped so device +
    # collective never exceeds the steady per-step wall — the traced
    # window's steps pay profiler overhead, so its per-step busy time
    # can legitimately exceed the untraced steady step_ms, and the
    # budget's components must still sum to what was measured steady
    new_device = min(max(0.0, meas_busy - meas_coll),
                     max(0.0, step_ms - new_coll))
    recon = {
        "analytic": {
            "device_compute_ms": round(float(device), 4),
            "collective_ms": round(float(collective), 4),
            "collective_source": collective_source,
            "source": source,
        },
        "measured": {
            "device_compute_ms": round(new_device, 4),
            "collective_ms": round(meas_coll, 4),
            "busy_fraction": s.get("busy_fraction"),
            "window": (s.get("window") or {}).get("path"),
        },
        "drift": {
            "device_compute": _drift(float(device), new_device),
            "collective": (_drift(float(collective), meas_coll)
                           if meas_coll > 0.0 else None),
        },
        "threshold": DRIFT_THRESHOLD,
    }
    drifted = [k for k, v in recon["drift"].items()
               if v is not None and v > DRIFT_THRESHOLD]
    recon["drift_warning"] = bool(drifted)
    if drifted:
        _warn_drift(recon, drifted)
    # attach to the window summary so extra.devicescope carries it
    s["reconciliation"] = recon
    return {"device_compute_ms": new_device, "collective_ms": new_coll,
            "collective_source": new_coll_src,
            "source": "measured(profile)", "reconciliation": recon}


def _warn_drift(recon, drifted):
    """The loud estimate-went-stale signal: counter + flight breadcrumb
    + structured event + Python warning. Never raises."""
    try:
        _counter("devicescope.drift_warnings",
                 "devicescope").increment(len(drifted))
        detail = {k: {"analytic": recon["analytic"][k + "_ms"],
                      "measured": recon["measured"][k + "_ms"],
                      "drift": round(recon["drift"][k], 4)}
                  for k in drifted}
        if _flight._REC is not None:
            _flight.record("alert", "devicescope.drift",
                           dict(detail, threshold=DRIFT_THRESHOLD))
        try:
            from .. import healthmon as _hm
            if _hm._HM is not None:
                _hm._HM.events.emit(
                    "alert", "devicescope.drift",
                    args={"components": sorted(drifted),
                          "threshold": DRIFT_THRESHOLD})
        except Exception:  # noqa: BLE001
            pass
        parts = "; ".join(
            f"{k}: analytic {v['analytic']:.3f} ms vs measured "
            f"{v['measured']:.3f} ms ({v['drift']:.0%} apart)"
            for k, v in detail.items())
        warnings.warn(
            f"devicescope: analytic and measured step components "
            f"disagree by more than {DRIFT_THRESHOLD:.0%} — {parts}. "
            f"An estimate (probe / ring model / peak table) has gone "
            f"stale; trust the measured window (docs/devicescope.md)",
            stacklevel=3)
    except Exception:  # noqa: BLE001 — warning plumbing must never raise
        pass


# ---------------------------------------------------------------------------
# extra.devicescope payload
# ---------------------------------------------------------------------------

def bench_extra() -> dict:
    """The ``extra.devicescope`` payload for artifact json: the last
    window's measured summary (busy fraction, top-K ops joined to the
    roofline table, measured collectives, gap taxonomy, reconciliation),
    or the armed-but-no-window shape ``{"window": None}``."""
    s = window_summary()
    if not isinstance(s, dict):
        return {"window": None, "busy_fraction": None, "per_step": None,
                "top_ops": [], "gaps": None, "reconciliation": None}
    return dict(s)
