"""mxtpu.resilience — elastic, self-healing training.

The subsystem that ACTS on healthmon's verdicts (docs/resilience.md;
the "who acts on which verdict" column in docs/observability.md).
healthmon (PR 5) + devicescope (PR 10) made every distributed failure
mode visible — named straggler, NaN within one step, stall post-mortem
with the measured device timeline attached — and then the job died
anyway, making every verdict an obituary. Four pieces close the loop,
MegaScale-style (recovery as an ops-cost multiplier):

* **periodic async sharded checkpoints**
  (:class:`~.checkpoint.CheckpointManager`) — params + optimizer state
  + lr/step counter + RNG key + data cursor every N steps; the training
  thread pays one device→host copy at a step boundary, a worker thread
  does sha256 manifests + orbax serialization + ATOMIC rename (a torn
  write is never a valid checkpoint), bounded last-K rotation;
* **restart-from-last-good** (:class:`~.policy.Supervisor`) —
  in-process rollback on NaN (restore last-good, skip/re-read the
  poison batch, bounded retries with backoff, then escalate), process-
  level resume from the manifest (data cursor included — consumed
  batches are not replayed), stall → supervised restart via
  :data:`~.policy.RESTART_EXIT_CODE`;
* **elastic rank leave/join** (:class:`~.elastic.ElasticGroup`) — a
  membership layer over the existing rank-0 TCP wire + coordination
  KV: a preempted rank is evicted at the round deadline and the
  survivors re-form at the smaller world size and roll back to
  last-good instead of dying; re-join is admitted at the next
  checkpoint boundary;
* **a chaos harness that proves it** (tools/chaos_cluster.py) —
  NaN injection, mid-step rank kill,
  torn checkpoint, frozen rank: training must converge THROUGH each
  fault with the recovery visible on all three surfaces (counters,
  flight breadcrumbs, ``mxtpu.events/1`` records — rendered by
  ``tools/mxdiag.py recover``).

Cost contract: with resilience disarmed nothing here runs — the only
hot-path residue is one ``is None`` predicate in healthmon's alert
fan-out and the optional ``resilience=`` argument on
``TrainLoop.fit``; zero ``resilience.*`` counters exist. Armed, the
steady-state cost is one loss fetch per chunk (fault detection) and
one device→host copy per checkpoint cadence.

Env knobs: ``MXTPU_RESILIENCE_EVERY`` (checkpoint cadence in steps,
default 50), ``MXTPU_RESILIENCE_KEEP`` (rotation, default 3),
``MXTPU_RESILIENCE_ON_STALL`` (``none`` | ``exit``),
``MXTPU_ELASTIC_SYNC_TIMEOUT`` (round deadline s, default 10),
``MXTPU_ELASTIC_ADDR`` (member rendezvous, ``host:port``).
"""
from __future__ import annotations

from ..profiler.counters import (counter as _counter,
                                 counters as _counters_snap)
from .checkpoint import CheckpointManager, _breadcrumb, _emit
from .elastic import ElasticGroup, GroupClosed
from .policy import RESTART_EXIT_CODE, RecoveryEscalated, Supervisor

__all__ = ["CheckpointManager", "Supervisor", "ElasticGroup",
           "GroupClosed", "RecoveryEscalated", "RESTART_EXIT_CODE",
           "supervised", "current", "status", "bench_extra",
           "record_recovery", "on_health_alert"]

# module global: None = no supervisor armed (THE fast-path predicate —
# healthmon's alert fan-out guards its one call here with it)
_RS = None


def _register(sup):
    global _RS
    _RS = sup


def _unregister(sup):
    global _RS
    if _RS is sup:
        _RS = None


def supervised() -> bool:
    return _RS is not None


def current():
    return _RS


def on_health_alert(name, args, step=None):
    """healthmon's verdict → recovery-policy routing (called from
    HealthMonitor._alert when a supervisor is registered)."""
    sup = _RS
    if sup is not None:
        sup.on_health_alert(name, args, step=step)


def record_recovery(action, args=None, step=None):
    """Three-surface recovery record for policies outside
    :class:`Supervisor` (the elastic chaos worker's departure rollback,
    a custom loop's resume): ``resilience.recoveries_total`` counter +
    flight breadcrumb + ``resilience.<action>`` event."""
    _counter("resilience.recoveries_total",
                      "resilience").increment()
    args = dict(args or {})
    _breadcrumb(action, args)
    _emit("resilience", "resilience." + action, step=step, args=args)


def _snap(prefix="resilience/"):
    return {k[len(prefix):]: v for k, v in _counters_snap().items()
            if k.startswith(prefix)}


def status():
    """Operator-facing summary for deep ``/healthz`` and healthmon's
    status block: checkpoint freshness, recovery totals, and whether a
    rollback is mid-flight. Cheap (one counters snapshot)."""
    c = _snap()
    return {
        "supervised": _RS is not None,
        "last_checkpoint_step": c.get("resilience.last_checkpoint_step"),
        "recoveries_total": c.get("resilience.recoveries_total", 0),
        "rollback_in_progress":
            bool(c.get("resilience.rollback_in_progress", 0)),
        "rollbacks": c.get("resilience.rollbacks", 0),
        "resumes": c.get("resilience.resumes", 0),
        "corrupt_checkpoints": c.get("resilience.corrupt_checkpoints", 0),
        "rank_departures": c.get("resilience.rank_departures", 0),
        "steps_lost_last": c.get("resilience.steps_lost_last", 0),
    }


def bench_extra(manager=None):
    """The ``extra.resilience`` block for training artifact json
    (validated by tools/trace_check.py check_resilience_extra):
    checkpoint cadence + save cost percentiles + recovery accounting."""
    c = _snap()
    if not c and manager is None:
        return None

    def _hist(name):
        h = c.get(name)
        if not isinstance(h, dict):
            return None
        return {"count": h.get("count", 0),
                "p50_ms": h.get("p50"), "p95_ms": h.get("p95")}

    out = {
        "enabled": True,
        "checkpoints_saved": c.get("resilience.checkpoints_saved", 0),
        "last_checkpoint_step": c.get("resilience.last_checkpoint_step"),
        "recoveries_total": c.get("resilience.recoveries_total", 0),
        "rollbacks": c.get("resilience.rollbacks", 0),
        "resumes": c.get("resilience.resumes", 0),
        "rank_departures": c.get("resilience.rank_departures", 0),
        "steps_lost_last": c.get("resilience.steps_lost_last", 0),
        "steps_lost_total": c.get("resilience.steps_lost_total", 0),
        "save": _hist("resilience.save_ms"),
        "copy": _hist("resilience.copy_ms"),
    }
    if manager is not None:
        out["every"] = manager.every
        out["keep"] = manager.keep
        out["dir"] = manager.directory
    return out
