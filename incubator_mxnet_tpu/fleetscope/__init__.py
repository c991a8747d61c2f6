"""mxtpu.fleetscope — cross-process distributed tracing for the fleet.

The NINTH observability layer (docs/observability.md): the first eight
explain what ONE process does, but a served request now crosses a real
HTTP wire (fleet Router → replica ModelServer) and a training step
crosses M ranks — and no per-process scope can see the hop. Fleetscope
joins them, in three parts (docs/fleetscope.md):

* **trace-context propagation** (:mod:`.context`) — the Router mints
  (or accepts from the client) a W3C-traceparent ``trace_id``,
  forwards a child context on the proxied ``POST /predict``, and the
  replica threads it into its servescope request span and the
  ``serving.batch`` event — one request is ONE trace: router admit →
  wire → replica queue_wait → coalesce → device_exec → respond;
* **clock-aligned collection** (:mod:`.collector`) — a collector on
  the router (rank 0 uses the elastic TCP wire instead) periodically
  pulls each process's counters, ``mxtpu.events`` tail, and health
  flags over the existing ``diagnostics.export`` HTTP surface,
  estimating per-process clock offset from request/response midpoints
  (± rtt/2), into bounded per-process rings; events carry a ``mono``
  companion (``mxtpu.events/2``) so an NTP step can't reorder a
  process's own records in the merge;
* **merged views that get spent** — ``mxdiag.py trace <id>`` renders
  one request's cross-process span tree with the wire gap (router
  wall minus replica wall) explicit, ``mxdiag.py pod`` renders the
  per-replica aggregate with skew and straggler flags (report-only
  context for the router's least-loaded score), and
  ``tools/serve_load.py`` writes ``extra.fleetscope`` (trace-join
  rate, per-replica spread, wire-gap percentiles) into artifact json,
  validated by ``tools/trace_check.py``.

Cost model (the house off-path discipline): off = ONE predicate —
every hot-path hook guards with ``if fleetscope._FS is not None:``;
nothing is parsed, minted, or emitted until :func:`enable` ran.
Malformed headers are counted (``fleetscope.ctx_malformed``) and
re-minted, never guessed. ``MXTPU_FLEETSCOPE=1`` arms at import.
"""
from __future__ import annotations

import os

from ..profiler.counters import counter as _counter
from . import collector as _collector_mod
from . import context as _context_mod
from .collector import (Collector, estimate_offset, events_tail,
                        join_traces, merge_process_events)
from .context import TraceContext, mint, mint_span_id, parse

__all__ = ["enable", "disable", "enabled", "enable_from_env",
           "TraceContext", "mint", "mint_span_id", "parse",
           "Collector", "estimate_offset", "events_tail",
           "merge_process_events", "join_traces",
           "context", "collector"]

# module re-exports under their documented names
context = _context_mod
collector = _collector_mod

# module global: None = fleetscope off (THE fast-path predicate; the
# router/server/batcher guard every hook with
# `if _fleetscope._FS is not None:`)
_FS = None


class _FleetScope:
    """Marker object holding enable-time state: the context accounting
    counters every hop shares (created once at arm time — accepting a
    header on the hot path is a parse + at most one increment)."""

    def __init__(self):
        self.c_minted = _counter("fleetscope.ctx_minted", "fleetscope")
        self.c_accepted = _counter("fleetscope.ctx_accepted",
                                   "fleetscope")
        self.c_malformed = _counter("fleetscope.ctx_malformed",
                                    "fleetscope")
        self.c_propagated = _counter("fleetscope.ctx_propagated",
                                     "fleetscope")

    def accept(self, header, mint_on_missing: bool = True):
        """The one entry point a hop uses on an incoming request.

        * well-formed header → accepted context (counted);
        * malformed header → counted ``ctx_malformed``, then a FRESH
          trace is minted when ``mint_on_missing`` (the root hop) or
          None is returned (a mid-trace hop must not invent a root);
        * absent header → minted (root hop) or None (mid-trace hop).

        Returned contexts are the UPSTREAM view: callers derive their
        own span via :meth:`TraceContext.child` before emitting."""
        if header is not None:
            ctx = parse(header)
            if ctx is not None:
                self.c_accepted.increment()
                return ctx
            self.c_malformed.increment()
        if mint_on_missing:
            self.c_minted.increment()
            return mint()
        return None


def enable():
    """Arm cross-process tracing. Idempotent: re-enabling keeps the
    registry counters (they are process-lifetime accounting, not a
    window)."""
    global _FS
    if _FS is None:
        _FS = _FleetScope()
    return _FS


def disable():
    global _FS
    _FS = None


def enabled() -> bool:
    return _FS is not None


def enable_from_env():
    """MXTPU_FLEETSCOPE=1 arms fleetscope at import (like
    MXTPU_SERVESCOPE / MXTPU_DEVICESCOPE)."""
    if os.environ.get("MXTPU_FLEETSCOPE", "") == "1":
        enable()


enable_from_env()
