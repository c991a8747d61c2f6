"""OOM forensics: when a run dies of RESOURCE_EXHAUSTED, name the killer.

The classic TPU death is an allocator failure with a raw XLA error
string and no attribution — which program, how big, what was already
live, which knobs produced the shape. The dispatch sites (TrainLoop,
FusedTrainStep) wrap their device calls with :func:`record_oom`: when
the escaping exception matches the allocator-failure taxonomy, a
post-mortem is assembled from evidence memscope already holds —

* the offending program's **static footprint** (what the compile said
  it would need),
* the **watermark tail** (what memory did in the steps before death),
* the **top-K live buffers** from the diagnostics ledger (who held the
  bytes),
* the **resolved knob config** (which batch/remat/mesh produced it),
* the **capacity** verdict,

— counted, breadcrumbed, and emitted on the healthmon alert surface,
then the exception re-raises unchanged. The last post-mortem rides
``extra.memscope.oom`` in artifact json and renders via
``tools/mxdiag.py mem``. Assembly never raises: forensics on a dying
process must not replace the real error with its own.
"""
from __future__ import annotations

from ..diagnostics import flight as _flight
from ..profiler.counters import counter as _counter
from . import footprint as _footprint

__all__ = ["is_oom_error", "post_mortem", "record_oom",
           "last_post_mortem", "reset", "OOM_SCHEMA"]

OOM_SCHEMA = "mxtpu.memscope.oom/1"

# substrings (lowercased) that mark an allocator failure across
# backends: XLA's status code, the C++ allocator, plain host OOM
_OOM_MARKERS = ("resource_exhausted", "resource exhausted",
                "out of memory", "failed to allocate",
                "allocation failure", "bad_alloc")

_LAST_PM = None


def reset():
    global _LAST_PM
    _LAST_PM = None


def last_post_mortem():
    """The most recent OOM post-mortem dict, or None."""
    return _LAST_PM


def is_oom_error(exc) -> bool:
    """Is this exception an allocator failure? Matches the
    RESOURCE_EXHAUSTED taxonomy on the message (XlaRuntimeError carries
    the status code in its text) and plain MemoryError. Never raises."""
    try:
        if isinstance(exc, MemoryError):
            return True
        text = f"{type(exc).__name__}: {exc}".lower()
        return any(m in text for m in _OOM_MARKERS)
    except Exception:  # noqa: BLE001
        return False


def _top_buffers(k=8) -> list:
    """Top-K live buffers by Gluon-Block attribution from the
    diagnostics ledger (empty when the ledger is off)."""
    try:
        from ..diagnostics.memory import memory_summary
        s = memory_summary(include_reconcile=False)
        top = sorted(s.get("by_block", {}).items(),
                     key=lambda kv: -kv[1])[:int(k)]
        return [{"block": b, "bytes": int(n)} for b, n in top]
    except Exception:  # noqa: BLE001
        return []


def post_mortem(error=None, program=None, step=None) -> dict:
    """Assemble (but do not publish) an OOM post-mortem. Every section
    degrades independently — a dead allocator must still yield
    whatever evidence survives. See the module docstring for the
    sections."""
    pm = {"schema": OOM_SCHEMA,
          "error": None, "error_type": None,
          "program": program, "step": step,
          "footprint": None, "watermark_tail": [],
          "top_buffers": [], "ledger": None,
          "knobs": None, "capacity": None}
    try:
        if error is not None:
            pm["error"] = str(error)[:2000]
            pm["error_type"] = type(error).__name__
    except Exception:  # noqa: BLE001
        pass
    try:
        if program is not None:
            pm["footprint"] = _footprint.footprint_of(program)
    except Exception:  # noqa: BLE001
        pass
    try:
        from . import _MS
        if _MS is not None:
            pm["watermark_tail"] = _MS.ring.tail(8)
    except Exception:  # noqa: BLE001
        pass
    pm["top_buffers"] = _top_buffers()
    try:
        from ..diagnostics.memory import memory_summary
        s = memory_summary(include_reconcile=False)
        pm["ledger"] = {"current_bytes": s.get("current_bytes"),
                        "peak_bytes": s.get("peak_bytes"),
                        "live_arrays": s.get("live_arrays")}
    except Exception:  # noqa: BLE001
        pass
    try:
        from .. import settings as _settings
        pm["knobs"] = {f: _settings.resolve(f)[0]
                       for f in _settings.FIELDS}
    except Exception:  # noqa: BLE001
        pass
    try:
        from . import device_capacity
        pm["capacity"] = device_capacity()
    except Exception:  # noqa: BLE001
        pass
    return pm


def record_oom(error, program=None, step=None):
    """The dispatch-site hook: if ``error`` is an allocator failure,
    assemble the post-mortem and land it on every finding surface
    (counter + flight breadcrumb + healthmon structured event), then
    return it so the caller re-raises the original error. Returns None
    for non-OOM errors. Never raises."""
    global _LAST_PM
    try:
        if not is_oom_error(error):
            return None
        pm = post_mortem(error=error, program=program, step=step)
        _LAST_PM = pm
        _counter("memscope.oom_events", "memscope").increment()
        if _flight._REC is not None:
            _flight.record("alert", "memscope.oom", {
                "program": program, "step": step,
                "error_type": pm.get("error_type"),
                "footprint_peak_bytes":
                    (pm.get("footprint") or {}).get("peak_bytes"),
                "ledger_current_bytes":
                    (pm.get("ledger") or {}).get("current_bytes")})
        try:
            from .. import healthmon as _hm
            if _hm._HM is not None:
                _hm._HM.events.emit(
                    "alert", "memscope.oom",
                    args={"program": program, "step": step,
                          "error_type": pm.get("error_type")})
        except Exception:  # noqa: BLE001
            pass
        return pm
    except Exception:  # noqa: BLE001 — forensics never masks the OOM
        return None
