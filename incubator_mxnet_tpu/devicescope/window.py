"""Windowed device-timeline capture: a bounded N-step jax-profiler trace.

A :class:`CaptureWindow` wraps a few steps of the steady train loop in
``jax.profiler.trace`` (via the :mod:`..profiler.tpu` bridge) and turns
the artifact into the measured summary :mod:`.ingest` derives. The
lifecycle is built for a hot loop that must not care about profiling:

* ``start()`` — rotate old artifact dirs (keep the newest
  ``MXTPU_DEVICESCOPE_KEEP``, default 3, so repeated runs never
  grow the dir unboundedly), snapshot the gap-taxonomy counters
  (``io.wait_ms`` + ``trainloop.dispatch_ms``), start the device trace.
  A profiler that is already tracing (``profile_xla``, a concurrent
  window) or unavailable DECLINES the window — counted, never raised —
  and every later call is a no-op.
* ``step(n, dispatch_ms=...)`` — the loop's per-dispatch mark; on the
  Nth captured step the trace stops *immediately* (keeping the window
  bounded no matter how long the run is) but ingestion is DEFERRED: the
  artifact parse runs lazily at the first ``summary()`` call, after the
  steady phase, so the capture's in-loop cost is the tracing overhead
  plus one ``stop_trace`` — not a JSON parse in the middle of the
  measured region.
* ``stop()`` — idempotent early stop (loop ended before N steps; the
  context-manager exit calls it).

The module-global active window is what instrumented executors
(:meth:`TrainLoop.run_chunk`) mark, so ``devicescope.capture()`` works
around ``loop.fit(...)`` with no user-side marking.
"""
from __future__ import annotations

import os
import shutil
import time

from ..profiler import tpu as _tpu
from ..profiler.counters import (counter as _counter,
                                 counters as _registry_snapshot,
                                 set_gauge as _set_gauge)
from . import ingest as _ingest

__all__ = ["CaptureWindow", "base_dir", "rotate_dirs", "DEFAULT_KEEP"]

DEFAULT_KEEP = 3

# counters the gap taxonomy reads as window-scoped deltas; the io stage
# walls (read/decode/put) split the input_starved bucket into
# disk-vs-decode-vs-transfer attribution (ingest.input_starved_split)
_TRACKED = {"io_wait_ms": "io/io.wait_ms",
            "io_read_ms": "io/io.read_ms",
            "io_decode_ms": "io/io.decode_ms",
            "io_put_ms": "io/io.put_ms",
            "dispatch_ms": "trainloop/trainloop.dispatch_ms"}


def base_dir() -> str:
    from ..settings import env_str
    return env_str("MXTPU_DEVICESCOPE_DIR", "/tmp/mxtpu_devicescope")


def _env_keep() -> int:
    from ..settings import env_int
    return max(1, env_int("MXTPU_DEVICESCOPE_KEEP", DEFAULT_KEEP,
                          on_error="default"))


def rotate_dirs(base: str, keep: int | None = None) -> int:
    """Delete the oldest ``win_*`` capture dirs under ``base`` so at
    most ``keep - 1`` remain (the caller is about to create one more).
    Returns how many were removed. Best-effort, never raises."""
    keep = _env_keep() if keep is None else max(1, int(keep))
    removed = 0
    try:
        subdirs = [os.path.join(base, d) for d in os.listdir(base)
                   if d.startswith("win_")
                   and os.path.isdir(os.path.join(base, d))]
        subdirs.sort(key=os.path.getmtime)
        while len(subdirs) > keep - 1:
            victim = subdirs.pop(0)
            shutil.rmtree(victim, ignore_errors=True)
            removed += 1
    except Exception:  # noqa: BLE001 — rotation is housekeeping
        pass
    return removed


_seq = [0]


class CaptureWindow:
    """One bounded capture window. States: created → active →
    stopped (→ ingested) | declined."""

    def __init__(self, steps: int = 10, logdir: str | None = None):
        self.requested_steps = max(1, int(steps))
        if logdir is None:
            _seq[0] += 1
            logdir = os.path.join(
                base_dir(),
                f"win_{os.getpid()}_{_seq[0]:03d}_{int(time.time())}")
        self.logdir = logdir
        self.steps_done = 0
        self.dispatch_ms = 0.0        # caller-accumulated dispatch wall
        self.workload = None          # who stepped it: "train"/"serving"
                                      # ("mixed" if both) — consumers
                                      # joining against a window must
                                      # check this, not just freshness
        self.wall_ms = None
        self.state = "created"
        self.completed_at = None      # time.monotonic() at trace stop —
                                      # budgets only reconcile against
                                      # windows completed AFTER they began
        self.trace_file = None
        self._t0 = None
        self._snap0 = {}
        self._counters_delta = {}
        self._summary = None

    # -- lifecycle --------------------------------------------------------
    def start(self):
        if self.state != "created":
            return self
        self._snap0 = self._snapshot()
        if not _tpu.start_device_trace(self.logdir):
            # already tracing (profile_xla / a concurrent window) or a
            # stripped profiler build: decline, don't break the loop.
            # NOTHING was created on disk (jax makes the logdir itself),
            # so a declined window can never count against — or evict
            # real artifacts from — the rotation budget below
            self.state = "declined"
            _counter("devicescope.declined", "devicescope").increment()
            return self
        # trim the oldest artifact dirs now that THIS capture is real:
        # keep-1 survivors + the dir jax writes at stop = keep total
        rotate_dirs(os.path.dirname(self.logdir) or base_dir())
        self._t0 = time.perf_counter()
        self.state = "active"
        from . import _set_active
        _set_active(self)
        return self

    def step(self, n: int = 1, dispatch_ms: float = 0.0, sync=None,
             workload: str | None = None):
        """Mark n train steps (one dispatch). Stops the trace the
        moment the requested step count is reached.

        ``sync``: optional zero-arg barrier called ONLY when this mark
        triggers the stop, BEFORE the trace closes. Through an async
        dispatch path the host mark runs ahead of the device (dispatch
        returns at enqueue), so without a barrier the window could
        close with its own steps still in flight and under-count busy
        time. Pass a host value fetch of the step's result (the
        latest loss — steps chain through params, so that
        one fetch completes them all). Never raises.

        ``workload``: identity stamp ("train"/"serving") so consumers
        that join against the last window (servescope's device_exec
        upgrade) can tell whose dispatches it measured — a fresh
        window is not enough when train and serve share a process.
        Steppers with different stamps degrade the window to "mixed"."""
        if self.state != "active":
            return
        if workload is not None:
            self.workload = (workload if self.workload in (None, workload)
                             else "mixed")
        self.steps_done += max(1, int(n))
        self.dispatch_ms += float(dispatch_ms or 0.0)
        if self.steps_done >= self.requested_steps:
            if sync is not None:
                try:
                    sync()
                except Exception:  # noqa: BLE001 — a failed barrier
                    pass           # costs accuracy, never the run
            self._stop_trace()

    def stop(self):
        """Idempotent early stop (context-manager exit / loop end)."""
        if self.state == "active":
            self._stop_trace()
        return self

    def _stop_trace(self):
        self.wall_ms = (time.perf_counter() - self._t0) * 1e3
        self.completed_at = time.monotonic()
        _tpu.stop_device_trace()
        snap1 = self._snapshot()
        self._counters_delta = {
            k: max(0.0, snap1.get(k, 0.0) - self._snap0.get(k, 0.0))
            for k in _TRACKED}
        # the caller-accumulated dispatch wall (FusedTrainStep loops have
        # no dispatch counter) adds to the counter-based delta
        self._counters_delta["dispatch_ms"] += self.dispatch_ms
        self.state = "stopped"
        _counter("devicescope.windows", "devicescope").increment()
        _counter("devicescope.steps_captured",
                 "devicescope").increment(self.steps_done)
        from . import _set_active, _set_last
        _set_active(None)
        _set_last(self)

    # -- context manager --------------------------------------------------
    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- results ----------------------------------------------------------
    @property
    def active(self) -> bool:
        return self.state == "active"

    @property
    def complete(self) -> bool:
        """True when the window captured its full requested step count."""
        return self.state == "stopped" \
            and self.steps_done >= self.requested_steps

    def summary(self):
        """The ingested measured summary (lazy: the artifact is parsed
        on first access, cached after). None until the window stopped,
        or when it declined."""
        if self.state != "stopped":
            return None
        if self._summary is None:
            self._summary = self._ingest()
        return self._summary

    def _ingest(self):
        try:
            events, self.trace_file = _ingest.load_trace_events(self.logdir)
            program_map, programs, comms = {}, [], []
            try:
                from . import program_map as _pm
                program_map = _pm()
                from .. import perfscope as _ps
                programs = _ps.programs()
            except Exception:  # noqa: BLE001
                pass
            try:
                from ..commscope import extract as _cse
                comms = _cse.programs()
            except Exception:  # noqa: BLE001
                pass
            s = _ingest.summarize(
                events, self.wall_ms, self.steps_done,
                counters_delta=self._counters_delta,
                program_map=program_map, programs=programs,
                comms_programs=comms)
            s["window"] = {
                "path": self.logdir,
                "trace_file": self.trace_file,
                "steps": self.steps_done,
                "requested_steps": self.requested_steps,
                "wall_ms": round(self.wall_ms, 4)
                if self.wall_ms is not None else None,
                "complete": self.complete,
            }
            s.setdefault("reconciliation", None)
            if s.get("error"):
                _counter("devicescope.ingest_errors",
                         "devicescope").increment()
            if s.get("busy_fraction") is not None:
                _set_gauge("devicescope.busy_fraction",
                           s["busy_fraction"], "devicescope")
            ps = s.get("per_step") or {}
            for key, gauge in (("device_busy_ms",
                                "devicescope.device_busy_ms"),
                               ("collective_ms",
                                "devicescope.collective_ms"),
                               ("idle_ms", "devicescope.idle_ms")):
                if isinstance(ps.get(key), (int, float)):
                    _set_gauge(gauge, ps[key], "devicescope")
            return s
        except Exception as e:  # noqa: BLE001 — measurement must never
            _counter("devicescope.ingest_errors",    # break the run
                     "devicescope").increment()
            return {"window": {"path": self.logdir, "trace_file": None,
                               "steps": self.steps_done,
                               "requested_steps": self.requested_steps,
                               "wall_ms": self.wall_ms,
                               "complete": self.complete},
                    "busy_fraction": None, "per_step": None,
                    "top_ops": [], "gaps": None, "reconciliation": None,
                    "error": f"{type(e).__name__}: {e}"[:200]}

    @staticmethod
    def _snapshot():
        snap = _registry_snapshot()
        out = {}
        for key, full in _TRACKED.items():
            v = snap.get(full)
            out[key] = float(v) if isinstance(v, (int, float)) else 0.0
        return out
