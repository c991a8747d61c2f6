"""Static per-program memory footprints from ``compiled.memory_analysis()``.

Every compiled hot program already passes through perfscope's analyze
funnel (the HybridBlock jit cache, FusedTrainStep's programs, TrainLoop
chunks, FrozenModel serving buckets). When memscope is armed, that
funnel's ``_memscope_capture`` hook hands each program here, and XLA's
compiled-executable memory analysis — argument / output / temp /
alias / generated-code bytes, plus the peak — lands in a process-wide
table keyed by program name, the same key perfscope's roofline table
uses, so ``extra.memscope.programs`` joins the two for free.

Acquisition follows commscope's discipline: a site that already holds
the compiled executable (serving buckets) passes it and the analysis is
free; a site that only lowered pays one extra host-side XLA compile —
which is why memscope is off by default and armed per run.

Peak provenance is a CLOSED taxonomy (trace_check pins it):

* ``reported`` — the backend's analysis carried an explicit peak field;
* ``derived`` — no peak field (CPU jaxlib): peak approximated as
  argument + output + temp + generated-code bytes;
* ``unavailable`` — no executable or no analysis on this backend:
  counted ``memscope.capture_unknown``, never raised.
"""
from __future__ import annotations

import threading

from ..diagnostics import flight as _flight
from ..profiler.counters import counter as _counter

__all__ = ["capture", "footprints", "footprint_of", "reset",
           "FOOTPRINT_PROVENANCE", "BYTE_FIELDS"]

FOOTPRINT_PROVENANCE = ("reported", "derived", "unavailable")

# normalized field -> attribute spellings across jaxlib versions (the
# device_memory_stats key-normalization discipline, compile-side)
_FIELD_CANDIDATES = {
    "argument_bytes": ("argument_size_in_bytes", "arg_size_in_bytes"),
    "output_bytes": ("output_size_in_bytes",),
    "temp_bytes": ("temp_size_in_bytes",),
    "alias_bytes": ("alias_size_in_bytes",),
    "generated_code_bytes": ("generated_code_size_in_bytes",
                             "code_size_in_bytes"),
}

BYTE_FIELDS = tuple(_FIELD_CANDIDATES)

# explicit peak spellings (absent on CPU jaxlib: peak is then derived)
_PEAK_CANDIDATES = ("peak_memory_in_bytes", "peak_memory_bytes")

# process-wide table: name -> record (last analysis wins per name — the
# perfscope _PROGRAMS discipline, recompiles overwrite)
_FOOTPRINTS: "dict[str, dict]" = {}
_flock = threading.Lock()


def footprints() -> list:
    """Snapshot of every captured footprint, insertion-ordered."""
    with _flock:
        return [dict(v) for v in _FOOTPRINTS.values()]


def footprint_of(name):
    """The captured footprint record for one program name, or None."""
    with _flock:
        rec = _FOOTPRINTS.get(name)
        return dict(rec) if rec is not None else None


def reset() -> None:
    with _flock:
        _FOOTPRINTS.clear()


def _read_bytes(ma, spellings):
    for attr in spellings:
        v = getattr(ma, attr, None)
        if isinstance(v, (int, float)) and v >= 0:
            return int(v)
    return None


def _unavailable(name, kind) -> dict:
    return {"name": name, "kind": kind, "available": False,
            "provenance": "unavailable", "peak_bytes": None,
            **{f: None for f in BYTE_FIELDS}}


def capture(name, lowered=None, compiled=None, kind="program"):
    """Capture one program's static memory footprint. Never raises —
    called from inside compile sites via perfscope's hook, where an
    analysis failure must not break the compile. Returns the stored
    record (an ``unavailable`` record when the backend has no
    analysis), or None on an internal error."""
    try:
        return _capture(str(name), lowered, compiled, str(kind))
    except Exception:  # noqa: BLE001 — ingestion never raises
        try:
            _counter("memscope.capture_errors", "memscope").increment()
        except Exception:  # noqa: BLE001
            pass
        return None


def _capture(name, lowered, compiled, kind):
    if compiled is None and lowered is not None:
        # the commscope acquisition pattern: pay one host-side compile
        # to read the optimized executable (why memscope is opt-in)
        try:
            compiled = lowered.compile()
        except Exception:  # noqa: BLE001 — backend-dependent surface
            compiled = None
    ma = None
    if compiled is not None:
        try:
            ma = compiled.memory_analysis()
        except Exception:  # noqa: BLE001 — absent on some backends
            ma = None
    if ma is None:
        rec = _unavailable(name, kind)
        _counter("memscope.capture_unknown", "memscope").increment()
    else:
        rec = {"name": name, "kind": kind, "available": True}
        for field, spellings in _FIELD_CANDIDATES.items():
            rec[field] = _read_bytes(ma, spellings)
        peak = _read_bytes(ma, _PEAK_CANDIDATES)
        if peak is not None:
            rec["peak_bytes"] = peak
            rec["provenance"] = "reported"
        else:
            rec["peak_bytes"] = sum(
                rec[f] or 0 for f in ("argument_bytes", "output_bytes",
                                      "temp_bytes",
                                      "generated_code_bytes"))
            rec["provenance"] = "derived"
        _counter("memscope.programs_captured", "memscope").increment()
        if _flight._REC is not None:
            # the compile span gains the footprint — a crash dump now
            # says how much memory each program wanted
            _flight.record("compile", f"memscope.footprint:{name}", {
                "peak_bytes": rec["peak_bytes"],
                "temp_bytes": rec["temp_bytes"],
                "argument_bytes": rec["argument_bytes"],
                "output_bytes": rec["output_bytes"],
                "provenance": rec["provenance"]})
    with _flock:
        _FOOTPRINTS[name] = rec
    return dict(rec)
