"""mxtpu.healthmon — cross-rank training health.

The third observability pillar: :mod:`..profiler` traces one process on
demand, :mod:`..diagnostics` monitors one process always-on; healthmon
correlates ACROSS ranks and watches for the distributed failure modes
that per-process telemetry can't see — slow ranks dragging every
collective, silent NaN divergence, hangs that look like "training is
just slow". Three pieces (see docs/observability.md):

* **cross-rank collective timeline** (:mod:`.skew`) — per-rank
  step/collective EWMAs exchanged periodically over the existing
  distributed wire (allgather on sync clusters, the rank-0 TCP server
  for dist_async), yielding ``healthmon.collective_skew_ms`` and
  slowest-rank attribution in the shared counters registry;
* **training watchdogs** (:mod:`.watchdog`) — NaN/Inf sentinel on loss
  (+ opt-in every-N-steps gradient global-norm), EWMA step-time
  regression detector, and a stall thread that triggers a
  flight-recorder dump with per-rank last-known state;
* **structured event log** (:mod:`.events`) — ``mxtpu.events/2`` JSONL
  with run_id/rank/step correlation ids (+ a wall/monotonic timestamp
  pair for NTP-step-safe cross-process merges), threaded through Trainer step
  phases, kvstore collectives, serving batches, and every watchdog
  alert; merge per-rank files with ``tools/mxdiag.py merge``.

Quick start (identical on every rank)::

    import incubator_mxnet_tpu as mx
    mx.distributed.init(...)
    mx.healthmon.enable()          # events -> $MXTPU_HM_DIR/events_rank<r>.jsonl
    ...training loop with gluon.Trainer...   # hooks are automatic
    mx.healthmon.observe_loss(float(loss))   # NaN sentinel (host scalar)
    mx.healthmon.disable()

Loops that don't use Trainer call :func:`mark_step` once per step.

Env knobs: ``MXTPU_HEALTHMON=1`` auto-enables at import — note that at
import time no cluster exists yet, so on multi-process runs either
launch via tools/launch.py (which exports MXTPU_PROCESS_ID +
MXTPU_RUN_ID, giving every rank its correct identity without touching
the jax backend) or call :func:`enable` after ``mx.distributed.init()``
as in the quick start; ``MXTPU_RUN_ID`` (cross-rank correlation id —
set it from the launcher; otherwise rank 0 publishes one through the
coordination KV), ``MXTPU_HM_DIR`` (event-log
directory, default ``MXTPU_DIAG_DIR``/tmp), ``MXTPU_HM_STALL_S`` (stall
deadline, default 300, 0 = off), ``MXTPU_HM_EXCHANGE_EVERY`` (skew
exchange cadence in steps, default 10, 0 = off),
``MXTPU_HM_GRAD_NORM_EVERY`` (gradient-norm sentinel cadence, default
0 = off — it forces a device sync), ``MXTPU_HM_ON_NAN`` (``alert`` |
``raise``).
"""
from __future__ import annotations

import os
import threading
import time

from ..profiler.counters import counter as _counter, set_gauge as _set_gauge
from ..diagnostics import flight as _flight
from . import events as _events
from .events import SCHEMA as EVENTS_SCHEMA
from .skew import CollectiveTimeline
from .watchdog import NaNSentinel, StepTimeRegression, StallWatchdog

__all__ = ["HealthMonitor", "enable", "disable", "enabled", "current",
           "observe_loss", "mark_step", "enable_from_env", "status",
           "EVENTS_SCHEMA", "events", "skew", "watchdog"]

# module global: None = healthmon off (THE fast-path predicate; trainer/
# kvstore/serving guard their hooks with `if _hm._HM is not None:`)
_HM = None


def _coordination_client():
    """The jax coordination-service client IF a cluster has been formed,
    else None. Read from distributed global state, NOT via
    jax.process_count(): that call MATERIALIZES the backend, and doing
    so at import time (MXTPU_HEALTHMON=1) would make every rank's later
    mx.distributed.init() fail with 'initialize() must be called before
    any JAX computations'."""
    try:
        from jax._src import distributed as _jd
        return _jd.global_state.client
    except Exception:   # noqa: BLE001 — private surface may move
        return None


def _default_rank() -> int:
    """This process's rank without touching the backend: the launcher's
    MXTPU_PROCESS_ID wins (valid even before distributed.init), then a
    formed cluster's process_index, else 0."""
    from ..settings import env_str
    env = env_str("MXTPU_PROCESS_ID")
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    if _coordination_client() is not None:
        import jax
        return jax.process_index()
    return 0


def _resolve_run_id(rank: int) -> str:
    """One id shared by every rank of a run. Launcher-set MXTPU_RUN_ID
    wins; on a formed cluster rank 0 publishes one through the
    coordination KV (one-time traffic — the sustained-RPC segfault the
    async PS wire avoids does not apply); fallback is process-local."""
    from ..settings import env_str
    rid = env_str("MXTPU_RUN_ID")
    if rid:
        return rid
    try:
        c = _coordination_client()
        if c is not None:
            key = "mxtpu_hm/run_id"
            if rank == 0:
                rid = f"run-{int(time.time())}-{os.getpid():x}"
                c.key_value_set_bytes(key, rid.encode(),
                                      allow_overwrite=True)
                return rid
            return c.blocking_key_value_get_bytes(key, 60_000).decode()
    except Exception:   # noqa: BLE001 — correlation id is best-effort
        pass
    return f"run-{int(time.time())}-{os.getpid()}"


def _env_float(name, default):
    # watchdog cadence knobs degrade on a typo, never crash enable()
    from ..settings import env_float
    return float(env_float(name, default, on_error="default"))


def _devicescope_window_path():
    """Artifact dir of the last completed devicescope capture window,
    or None — attached to stall/NaN alerts so the post-mortem has the
    measured device timeline, not just host state. Never raises."""
    try:
        from .. import devicescope as _ds
        return _ds.last_window_path()
    except Exception:   # noqa: BLE001 — alerting must never crash
        return None


class HealthMonitor:
    """One per process; owns the timeline, sentinels, watchdog thread,
    and the structured event log. Constructed via :func:`enable`."""

    def __init__(self, run_id=None, rank=None, hm_dir=None,
                 events_path=None, stall_timeout_s=None,
                 exchange_every=None, grad_norm_every=None, on_nan=None,
                 regress_factor=2.0, ewma_alpha=0.3,
                 straggler_factor=2.0, stall_check_interval_s=None):
        self.rank = int(rank if rank is not None else _default_rank())
        self.run_id = run_id or _resolve_run_id(self.rank)
        from ..settings import env_str
        self.hm_dir = hm_dir or env_str(
            "MXTPU_HM_DIR", env_str("MXTPU_DIAG_DIR", "/tmp"))
        self.exchange_every = int(
            exchange_every if exchange_every is not None
            else _env_float("MXTPU_HM_EXCHANGE_EVERY", 10))
        self.grad_norm_every = int(
            grad_norm_every if grad_norm_every is not None
            else _env_float("MXTPU_HM_GRAD_NORM_EVERY", 0))
        stall_timeout_s = (stall_timeout_s if stall_timeout_s is not None
                           else _env_float("MXTPU_HM_STALL_S", 300))
        on_nan = on_nan or env_str("MXTPU_HM_ON_NAN", "alert")

        self.step = 0                 # completed steps
        self._step_t0 = None          # perf_counter at step_begin
        self._prev_end = None         # perf_counter at previous step_end
        self._coll_ms = 0.0           # this step's collective time
        self._coll_lock = threading.Lock()

        self.timeline = CollectiveTimeline(
            rank=self.rank, alpha=ewma_alpha,
            straggler_factor=straggler_factor)
        self.nan = NaNSentinel(self._alert, on_nan=on_nan)
        self.regress = StepTimeRegression(self._alert,
                                          factor=regress_factor,
                                          alpha=ewma_alpha)
        path = events_path or os.path.join(
            self.hm_dir, f"events_rank{self.rank}.jsonl")
        self.events = _events.open_log(path, self.run_id, self.rank)
        self.watchdog = None
        if stall_timeout_s and stall_timeout_s > 0:
            self.watchdog = StallWatchdog(
                stall_timeout_s, self._on_stall,
                check_interval_s=stall_check_interval_s)
            self.watchdog.start()
        self.events.emit("lifecycle", "healthmon.enable", args={
            "stall_timeout_s": stall_timeout_s,
            "exchange_every": self.exchange_every,
            "grad_norm_every": self.grad_norm_every, "on_nan": on_nan})

    # -- alert fan-out: counter + flight breadcrumb + structured event ----
    def _alert(self, name: str, args: dict, step=None):
        if name.startswith("nan_"):
            family = "healthmon.nan_alerts"
        elif name == "stall":
            family = "healthmon.stall_alerts"
        else:
            family = "healthmon.step_time_regressions"
        if name == "stall" or name.startswith("nan_"):
            # post-mortem breadcrumb: the last completed devicescope
            # capture window (if any run made one) holds the DEVICE
            # timeline for the steps before things went wrong — the
            # host-state dump alone can't show a wedged collective lane
            p = _devicescope_window_path()
            if p:
                args = dict(args, devicescope_window=p)
        _counter(family, "healthmon").increment()
        if _flight._REC is not None:
            _flight.record("alert", "healthmon." + name, args)
        self.events.emit("alert", "healthmon." + name,
                         step=self.step if step is None else step,
                         args=args)
        # verdict → action: a registered resilience supervisor acts on
        # this alert (stall → supervised restart; docs/resilience.md).
        # One predicate when no supervisor is armed — and the recovery
        # policy's own failure must never mask the alert that fired it.
        from .. import resilience as _resilience
        if _resilience._RS is not None:
            try:
                _resilience.on_health_alert(
                    name, args, step=self.step if step is None else step)
            except SystemExit:
                raise
            except Exception as e:   # noqa: BLE001
                _counter("healthmon.recovery_hook_errors",
                         "healthmon").increment()
                self.events.emit(
                    "alert", "healthmon.recovery_hook_error",
                    step=self.step if step is None else step,
                    args={"error": f"{type(e).__name__}: {e}"[:300]})

    def _on_stall(self, age_s: float):
        """StallWatchdog callback: alert, then flush the flight ring with
        the per-rank last-known state attached (the post-mortem for a
        job that will likely be SIGKILLed shortly after)."""
        args = {"age_s": round(age_s, 1), "last_step": self.step,
                "deadline_s": self.watchdog.deadline_s}
        if self.timeline.last_table:
            args["last_known_ranks"] = self.timeline.last_table
        self._alert("stall", args)
        if _flight._REC is not None:
            path = os.path.join(self.hm_dir,
                                f"mxtpu_stall_{os.getpid()}.json")
            try:
                _flight.dump(reason="healthmon.stall", path=path)
            except Exception:   # noqa: BLE001 — alerting must not crash
                pass

    # -- hot hooks (trainer / custom loops) -------------------------------
    def step_begin(self):
        self._step_t0 = time.perf_counter()

    def step_end(self, kv=None, batch_size=None, loss=None,
                 phases=None):
        """One training step completed. Updates EWMAs/watchdogs, emits
        the step event, and — every `exchange_every` steps — runs the
        cross-rank exchange (a collective on sync clusters: every rank
        must reach the same step count, which lockstep training gives)."""
        now = time.perf_counter()
        self.step += 1
        _counter("healthmon.steps", "healthmon").increment()
        with self._coll_lock:
            coll, self._coll_ms = self._coll_ms, 0.0
        if self._prev_end is not None:
            step_ms = (now - self._prev_end) * 1e3
        elif self._step_t0 is not None:
            step_ms = (now - self._step_t0) * 1e3
        else:
            step_ms = None
        self._prev_end = now
        if loss is not None:
            self.observe_loss(loss)
        if step_ms is not None:
            self.regress.observe(step_ms, step=self.step)
            self.timeline.record_step(self.step, step_ms, coll)
        if self.watchdog is not None:
            self.watchdog.beat()
        args = {"coll_ms": round(coll, 3)}
        if step_ms is not None:
            args["step_ms"] = round(step_ms, 3)
        if batch_size is not None:
            args["batch_size"] = int(batch_size)
        if phases:
            args.update({k: round(float(v), 3) for k, v in phases.items()})
        self.events.emit("trainer", "step", step=self.step, args=args)
        if self.exchange_every > 0 and \
                self.step % self.exchange_every == 0:
            try:
                summary = self.timeline.exchange(
                    self.step, kv=kv, nan_alerts=self.nan.alerts)
            except Exception as e:  # noqa: BLE001 — telemetry exchange
                # must never take the training loop down, but its OWN
                # failure must be observable (a failed collective here
                # can leave sync ranks' collective streams misaligned —
                # the operator needs the breadcrumb that says where)
                _counter("healthmon.exchange_errors",
                         "healthmon").increment()
                err = {"error": f"{type(e).__name__}: {e}"[:300],
                       "step": self.step}
                self.events.emit("alert", "healthmon.exchange_error",
                                 step=self.step, args=err)
                if _flight._REC is not None:
                    _flight.record("alert", "healthmon.exchange_error",
                                   err)
                return
            self.events.emit("healthmon", "skew_report", step=self.step,
                             args=summary)
            if _flight._REC is not None:
                _flight.record("healthmon", "skew_report", summary)

    def record_collective(self, op: str, dur_ms: float):
        """kvstore hook: one collective-surface call took `dur_ms`."""
        with self._coll_lock:
            self._coll_ms += dur_ms
        if self.events is not None:
            self.events.emit("collective", "kvstore." + op,
                             step=self.step,
                             args={"ms": round(dur_ms, 3)})

    def observe_loss(self, value, step=None) -> bool:
        """NaN/Inf sentinel on a host-side loss scalar. Returns True when
        the alert fired (and raises instead under on_nan='raise')."""
        return self.nan.check(value, step=step if step is not None
                              else self.step, source="loss")

    def maybe_check_grad_norm(self, params) -> float | None:
        """Opt-in gradient global-norm sentinel: every
        `grad_norm_every` steps compute ||g||_2 over all dense grads
        (ONE device sync — that cost is why this defaults off), publish
        the gauge, and run the NaN sentinel on it."""
        if self.grad_norm_every <= 0 or \
                (self.step + 1) % self.grad_norm_every != 0:
            return None
        import jax.numpy as jnp
        from ..ndarray import sparse as _sparse
        total = None
        for p in params:
            g = p.grad()
            if isinstance(g, _sparse.RowSparseNDArray):
                continue            # lazy-row grads keep their own path
            s = jnp.sum(jnp.square(g._data.astype(jnp.float32)))
            total = s if total is None else total + s
        if total is None:
            return None
        norm = float(jnp.sqrt(total))
        _set_gauge("healthmon.grad_global_norm", round(norm, 6),
                            "healthmon")
        self.nan.check(norm, step=self.step + 1, source="grad_norm")
        return norm

    # -- lifecycle --------------------------------------------------------
    def close(self):
        if self.watchdog is not None:
            self.watchdog.stop()
        self.events.emit("lifecycle", "healthmon.disable",
                         args={"steps": self.step})
        # close OUR log; clear the module global only when it is ours
        # (a caller may have re-pointed the module log since)
        if _events.current_log() is self.events:
            _events.close_log()
        else:
            self.events.close()


# ---------------------------------------------------------------------------
# module surface
# ---------------------------------------------------------------------------

def enable(**kwargs) -> HealthMonitor:
    """Arm healthmon (replacing any prior monitor). Kwargs mirror
    :class:`HealthMonitor`; unset ones fall back to the env knobs."""
    global _HM
    # clear BEFORE constructing: if the new monitor fails (bad dir,
    # etc.) healthmon must read as disabled — the alternative (closing
    # the old monitor but leaving _HM pointing at it) would keep
    # enabled() True while the event log is closed and the watchdog
    # stopped, i.e. telemetry silently dead
    # mxlint: disable=thread-shared-mutation -- GIL-atomic rebind of the
    # arming global; every reader snapshots _HM once (the `_HM is None`
    # discipline), and enable() runs before any monitored thread exists
    old, _HM = _HM, None
    if old is not None:
        old.close()
    # mxlint: disable=thread-shared-mutation -- same GIL-atomic rebind
    _HM = HealthMonitor(**kwargs)
    return _HM


def disable():
    global _HM
    if _HM is not None:
        _HM.close()
        # mxlint: disable=thread-shared-mutation -- GIL-atomic rebind;
        # readers snapshot _HM once, in-flight hooks finish on the old
        # (closed-tolerant) monitor object
        _HM = None


def enabled() -> bool:
    return _HM is not None


def current():
    return _HM


def observe_loss(value, step=None) -> bool:
    """Module-level NaN sentinel (no-op False when healthmon is off)."""
    hm = _HM
    if hm is None:
        return False
    return hm.observe_loss(value, step=step)


def mark_step(kv=None, batch_size=None, loss=None):
    """Step hook for loops that don't go through gluon.Trainer (fused
    train steps, custom loops): call once per completed step."""
    hm = _HM
    if hm is not None:
        hm.step_end(kv=kv, batch_size=batch_size, loss=loss)


def status() -> dict:
    """Operator-facing health summary: watchdog/sentinel counts plus —
    because detection without action is an obituary — the resilience
    block (who acts on the verdicts: last checkpoint step, recovery
    totals, rollback-in-progress). Deep ``/healthz`` embeds this."""
    from ..profiler.counters import counters as _snap
    from .. import resilience as _resilience
    c = _snap()
    return {
        "enabled": _HM is not None,
        "steps": _HM.step if _HM is not None else None,
        "stall_alerts": c.get("healthmon/healthmon.stall_alerts", 0),
        "nan_alerts": c.get("healthmon/healthmon.nan_alerts", 0),
        "step_time_regressions": c.get(
            "healthmon/healthmon.step_time_regressions", 0),
        "resilience": _resilience.status(),
    }


def enable_from_env():
    """Honor MXTPU_HEALTHMON=1 (called from package import)."""
    if os.environ.get("MXTPU_HEALTHMON", "0") in ("1", "true", "on"):
        enable()


from . import skew, watchdog, events   # noqa: E402,F401 — re-export
