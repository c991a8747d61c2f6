"""mxtpu.diagnostics — always-on observability for production runs.

The monitoring counterpart to :mod:`incubator_mxnet_tpu.profiler` (which
is on-demand tracing): cheap always-live telemetry in the
Dapper/Prometheus mold, three pillars —

* **device-memory accounting** (:mod:`.memory`) — a per-Context
  allocation ledger hooked into NDArray creation/free and the bulk
  deferred paths, with per-layer attribution via Gluon Block scopes and
  reconciliation against the XLA allocator:
  ``diagnostics.memory_summary()``;
* **metrics export** (:mod:`.export`) — a sampler thread snapshotting
  the counters/gauges registry + memory stats at a configurable
  interval, exported as Prometheus text (HTTP endpoint or textfile) and
  newline-JSON, so ``trainer.dispatches_per_step``, ``bulk.*``, jit
  cache hit-rates and KVStore bytes become scrapeable time series;
* **flight recorder** (:mod:`.flight`) — a bounded ring of recent
  events (op dispatches, bulk flushes, collective launches, compile
  spans, env/config snapshot) flushed to disk by an excepthook/SIGTERM
  handler on crash; pretty-print dumps with ``tools/mxdiag.py``.

Quick start::

    from incubator_mxnet_tpu import diagnostics as diag
    diag.enable()                      # ledger + flight recorder
    diag.start_sampler(interval_ms=100, jsonl_path="metrics.jsonl",
                       prom_path="metrics.prom")
    ...train...
    print(diag.format_memory_summary())
    diag.dump_flight("end_of_run.json")

Env knobs (see docs/diagnostics.md): ``MXTPU_DIAG=1`` auto-enables at
import; ``MXTPU_DIAG_DIR`` (dump/export directory), ``MXTPU_DIAG_SAMPLE_MS``
(sampler interval; 0 = no sampler), ``MXTPU_FLIGHT_CAPACITY`` (ring size).
"""
from __future__ import annotations

import os

from .memory import (enable_memory, disable_memory, memory_enabled,
                     reset_memory, memory_summary, format_memory_summary,
                     reconcile)
from .flight import (FlightRecorder, enable_flight_recorder,
                     disable_flight_recorder, flight_enabled, record,
                     crash_dump, last_dump_path)
from .flight import dump as dump_flight
from .export import (sample, prometheus_text, MetricsSampler, start_sampler,
                     stop_sampler, sampler_running, start_http, stop_http)

__all__ = [
    "enable", "disable", "enabled", "enable_from_env",
    # memory
    "enable_memory", "disable_memory", "memory_enabled", "reset_memory",
    "memory_summary", "format_memory_summary", "reconcile",
    # flight
    "FlightRecorder", "enable_flight_recorder", "disable_flight_recorder",
    "flight_enabled", "record", "dump_flight", "crash_dump",
    "last_dump_path",
    # export
    "sample", "prometheus_text", "MetricsSampler", "start_sampler",
    "stop_sampler", "sampler_running", "start_http", "stop_http",
]


def enable(memory: bool = True, flight: bool = True,
           dump_on_crash: bool = True, flight_capacity: int = 4096,
           sampler_interval_ms: int = 0, diag_dir: str | None = None):
    """One-call arming of the always-on layer: the memory ledger, the
    flight recorder (with crash dumps), and — when
    ``sampler_interval_ms > 0`` — the metrics sampler writing
    ``metrics.jsonl`` / ``metrics.prom`` under ``diag_dir``."""
    from ..settings import env_str
    diag_dir = diag_dir or env_str("MXTPU_DIAG_DIR", "/tmp")
    if memory:
        enable_memory()
    if flight:
        enable_flight_recorder(capacity=flight_capacity,
                               dump_on_crash=dump_on_crash,
                               dump_dir=diag_dir)
    if sampler_interval_ms > 0:
        os.makedirs(diag_dir, exist_ok=True)
        start_sampler(
            interval_ms=sampler_interval_ms,
            jsonl_path=os.path.join(diag_dir, "metrics.jsonl"),
            prom_path=os.path.join(diag_dir, "metrics.prom"))


def disable():
    """Tear down everything this module turned on."""
    stop_sampler()
    stop_http()
    disable_flight_recorder()
    disable_memory()


def enabled() -> bool:
    return memory_enabled() or flight_enabled() or sampler_running()


def enable_from_env():
    """Honor MXTPU_DIAG=1 (called from package import)."""
    if os.environ.get("MXTPU_DIAG", "0") in ("1", "true", "on"):
        from ..settings import env_int
        enable(
            flight_capacity=env_int("MXTPU_FLIGHT_CAPACITY", 4096),
            sampler_interval_ms=env_int("MXTPU_DIAG_SAMPLE_MS", 0))
