"""mxtpu.servescope — request-lifecycle tracing & tail-latency
attribution for the serving path.

The seventh observability layer (docs/observability.md), and the
serving counterpart of perfscope + devicescope: PRs 7–10 taught the
*training* loop to explain its milliseconds, but the serving stack
(PR 4) still only exposes aggregate histograms — a p99 number with no
story. Servescope measures the request lifecycle end to end and
attributes the tail:

* **per-request lifecycle spans** (:mod:`.spans`) — every sampled
  request gets a ``request_id`` and monotonic marks through
  ``admitted -> queued -> coalesced(batch_id, bucket, pad_slot) ->
  dispatched -> device_done -> unpadded -> responded``, recorded into
  the shared counters registry / flight ring and emitted as
  ``serving.request`` records in ``mxtpu.events/1`` (run_id/batch_id
  correlation with the per-dispatch ``serving.batch`` records);
* **tail-latency attribution** (:mod:`.budget`) — the
  :class:`LatencyBudget` decomposes per-bucket latency into
  ``queue_wait + coalesce_delay + pad_overhead + device_exec +
  respond`` (an exact accounting identity per request), publishes
  p50/p95/p99 per component, joins each bucket's AOT executable to its
  perfscope roofline verdict and commscope resharding verdict, and —
  when a devicescope window covered serving dispatches — upgrades
  ``device_exec`` provenance to ``measured(profile)`` under PR 10's
  stale-window/drift rules. ``tools/mxdiag.py serve`` renders it as
  "p99 is 83% queue_wait at bucket 128 - raise max_batch, not the
  kernel";
* **closed-loop load harness** — ``tools/serve_load.py`` drives K
  concurrent closed-loop clients through :class:`ModelServer` over a
  ramped concurrency sweep, finds the saturation knee where p99
  inflects, and writes the full attribution into trace_check-valid
  artifact json gated by ``tools/perf_regress.py``.

Cost model: off = one predicate per batcher hook (the
perfscope/commscope/devicescope module-global discipline). Armed, the
per-request cost is bounded by ``MXTPU_SERVESCOPE_SAMPLE``: a value in
(0, 1] is a sampling rate (0.1 = every 10th request), a value >= 1 is
the stride directly; unsampled requests pay one counter increment and a
modulo, keeping steady-state overhead inside healthmon's <5% budget.

``enable()`` arms it (tools/serve_load.py does); ``MXTPU_SERVESCOPE=1``
arms at import.
"""
from __future__ import annotations

import os

from .. import profiler as _prof
from . import budget as _budget_mod
from . import spans as _spans_mod
from .budget import (LatencyBudget, quantile_cohorts, DEFAULT_WINDOW,
                     DEVICE_EXEC_SOURCES)
from .spans import RequestSpan, COMPONENTS, components_of

__all__ = ["enable", "disable", "enabled", "enable_from_env",
           "sample_every", "attribution", "attribution_brief",
           "bench_extra", "current_budget", "LatencyBudget",
           "RequestSpan", "COMPONENTS", "components_of",
           "quantile_cohorts", "DEFAULT_WINDOW", "DEVICE_EXEC_SOURCES",
           "spans", "budget"]

# module re-exports under their documented names
spans = _spans_mod
budget = _budget_mod

# module global: None = servescope off (THE fast-path predicate; the
# batcher guards every hook with `if _ss._SS is not None:`)
_SS = None


class _ServeScope:
    """Marker object holding enable-time options (the perfscope /
    commscope / devicescope module-global discipline)."""

    def __init__(self, sample_every: int, window: int | None = None):
        self.sample_every = max(1, int(sample_every))
        self.budget = LatencyBudget(window=window)


def _resolve_sample(sample) -> int:
    """``MXTPU_SERVESCOPE_SAMPLE`` / ``enable(sample=)`` resolution:
    a rate in (0, 1] maps to a stride (0.1 -> 10), >= 1 is the stride
    itself; malformed values fall back to 1 (trace everything) — the
    hot path never raises over an env typo."""
    if sample is None:
        from ..settings import env_str
        sample = env_str("MXTPU_SERVESCOPE_SAMPLE", "1")
    try:
        v = float(sample)
    except (TypeError, ValueError):
        return 1
    if v >= 1.0:
        return int(round(v))
    if v > 0.0:
        return max(1, int(round(1.0 / v)))
    return 1


def enable(sample=None, window: int | None = None):
    """Arm request-lifecycle tracing on the serving path. ``sample``:
    rate in (0, 1] or an explicit every-Nth stride (default: the
    ``MXTPU_SERVESCOPE_SAMPLE`` env, else every request). Re-enabling
    starts a fresh :class:`LatencyBudget` (the attribution window is
    per arm, like a devicescope capture)."""
    global _SS
    _SS = _ServeScope(_resolve_sample(sample), window=window)
    _prof.set_gauge("servescope.sample_every", _SS.sample_every,
                    "servescope")
    return _SS


def disable():
    global _SS
    _SS = None


def enabled() -> bool:
    return _SS is not None


def enable_from_env():
    """MXTPU_SERVESCOPE=1 arms servescope at import (like
    MXTPU_PERFSCOPE / MXTPU_DEVICESCOPE)."""
    if os.environ.get("MXTPU_SERVESCOPE", "") == "1":
        enable()


def sample_every() -> int:
    """The armed stride (1 when off — callers use the predicate)."""
    ss = _SS
    return ss.sample_every if ss is not None else 1


def current_budget():
    ss = _SS
    return ss.budget if ss is not None else None


def attribution() -> dict | None:
    """The settled tail-latency attribution (None when off)."""
    ss = _SS
    return ss.budget.attribution() if ss is not None else None


def attribution_brief() -> dict | None:
    """The /healthz-sized p99 summary (None when off or no traffic)."""
    ss = _SS
    return ss.budget.brief() if ss is not None else None


def bench_extra() -> dict | None:
    """The ``extra.servescope`` payload for artifact json: the full
    attribution plus the sampling header. None when servescope is off
    (the section is simply absent, like an unarmed commscope)."""
    ss = _SS
    if ss is None:
        return None
    doc = ss.budget.attribution()
    doc["sample_every"] = ss.sample_every
    return doc
