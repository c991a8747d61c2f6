"""mxtpu.perfscope — roofline-aware performance attribution.

The fourth observability layer (docs/observability.md): the profiler
answers *what ran when*, diagnostics *what the process is doing*,
healthmon *which rank is unhealthy* — perfscope answers **why a step is
slow and what fixing it would buy**:

* **per-program cost analysis** (:mod:`.cost`) — every compile site
  (HybridBlock jit cache, FusedTrainStep, TrainLoop chunks, FrozenModel
  serving buckets) captures XLA ``cost_analysis()`` FLOPs/bytes per
  executable and derives an analytic roofline verdict — compute-bound,
  HBM-bound, trivially small, or unknown — against the per-device peak
  table (v5e/v4/v5p; a device that is not in it has no peaks and gets
  "unknown"). Verdicts land in the flight recorder's compile spans and
  the ``perfscope.*`` counter family.
* **step-time decomposition** (:mod:`.decomp`) — the per-step budget
  ``step_ms = device_compute + collective + input_wait + host_gap +
  other``, assembled from signals the earlier layers already export
  (``io.wait_ms``, ``kvstore.collective_ms``, dispatch wall) plus a
  fetch-barrier device-time probe. ``bench_extra()`` returns it as
  ``extra.perfscope``; ``tools/mxdiag.py perf`` renders the
  MFU-decomposition report.
* **regression gate** — ``tools/perf_regress.py`` compares run
  artifacts with noise-aware thresholds and skips ``env_failure``
  artifacts, so every future perf PR gets a machine verdict instead of
  an anecdote.

Cost capture costs one extra host-side trace per compiled signature, so
it is **off by default**: ``enable()`` arms it, ``MXTPU_PERFSCOPE=1``
arms it at import. The fast-path contract matches healthmon: every hook
site checks the single module global ``_PS`` and pays one predicate when
perfscope is off.
"""
from __future__ import annotations

import os

from . import cost
from . import decomp
from .cost import (analyze_jit, analyze_lowered, classify, device_peaks,
                   programs, record_program, reset_programs,
                   ROOFLINE_VERDICTS)
from .decomp import StepBudget, probe_device_time

__all__ = ["enable", "disable", "enabled", "enable_from_env",
           "analyze_jit", "analyze_lowered", "classify", "device_peaks",
           "programs", "record_program", "reset_programs", "StepBudget",
           "probe_device_time", "bench_extra", "ROOFLINE_VERDICTS",
           "cost", "decomp"]

# module global: None = perfscope off (THE fast-path predicate; compile
# sites guard with `if _ps._PS is not None:`)
_PS = None


class _PerfScope:
    """Marker object holding enable-time options (mirrors the healthmon
    module-global discipline; the object exists so future options have a
    home without changing the predicate)."""

    def __init__(self, capture_jit_cache: bool = True):
        self.capture_jit_cache = bool(capture_jit_cache)


def enable(capture_jit_cache: bool = True):
    """Arm cost capture at every compile site. ``capture_jit_cache=False``
    keeps FusedTrainStep/TrainLoop/FrozenModel capture but skips the
    per-signature HybridBlock jit-cache analysis (one extra host trace
    per hybridized signature — measurable in compile-heavy suites)."""
    global _PS
    _PS = _PerfScope(capture_jit_cache=capture_jit_cache)
    return _PS


def disable():
    global _PS
    _PS = None


def enabled() -> bool:
    return _PS is not None


def enable_from_env():
    """MXTPU_PERFSCOPE=1 arms perfscope at import (like MXTPU_DIAG /
    MXTPU_HEALTHMON); =jit0 arms it without jit-cache capture."""
    v = os.environ.get("MXTPU_PERFSCOPE", "")
    if v == "1":
        enable()
    elif v == "jit0":
        enable(capture_jit_cache=False)


def bench_extra(decomposition=None) -> dict:
    """The ``extra.perfscope`` payload for artifact json: the step budget
    (when the caller ran one), every analyzed program's roofline record,
    and the peak table the verdicts were scored against."""
    out = {"programs": programs(), "peaks": device_peaks()}
    if decomposition is not None:
        out["decomposition"] = decomposition
    return out
