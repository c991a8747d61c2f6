"""The supervised recovery policy — the act-on-verdicts half of
mxtpu.resilience (docs/resilience.md has the full state machine).

healthmon (PR 5) detects: the NaN sentinel fires within one step, the
stall watchdog dumps the flight ring, the EWMA flags the regression —
and then the job dies anyway. :class:`Supervisor` closes that loop for
a :class:`~..trainloop.TrainLoop`:

* **in-process rollback** — a non-finite loss in a chunk rolls params/
  optimizer state/lr step/rng back to the last GOOD checkpoint
  (draining any in-flight save first), skips the poison batch (or
  re-reads it under ``skip_poison=False`` for transient faults), and
  retries with backoff; ``max_retries`` consecutive faults escalate to
  :class:`RecoveryEscalated` — bounded, never an infinite rollback
  loop burning the reservation.
* **process-level resume** — ``drive()`` on a directory that already
  holds checkpoints restores the last good one (falling back past torn
  ones — parallel/checkpoint.py), reads the data cursor from its
  manifest, and skips the already-consumed batches, so a restarted
  process continues instead of replaying.
* **stall → restart** — the stall watchdog's alert routes here (one
  predicate in healthmon's fan-out): the request is counted + evented,
  and under ``on_stall='exit'`` (``MXTPU_RESILIENCE_ON_STALL``) the
  process exits with :data:`RESTART_EXIT_CODE` so a launcher/chaos
  harness restarts it into the resume path above. An in-process
  "un-wedge" does not exist — a stuck collective is stuck; the honest
  action is a clean restart from last-good.

Every recovery lands on all three surfaces at once: ``resilience.*``
counters, a flight breadcrumb, and an ``mxtpu.events/1`` record —
``tools/mxdiag.py recover`` renders the timeline.

Detection cost: supervised mode fetches each chunk's losses to host
(the NaN check needs scalars), i.e. one device sync per chunk — the
same sync the un-supervised loop pays only at fit() end. That is THE
overhead of arming resilience; disabled, nothing here runs.
"""
from __future__ import annotations

import os
import time

import numpy as np

from ..profiler.counters import (counter as _counter,
                                 set_gauge as _set_gauge)
from .checkpoint import CheckpointManager, _breadcrumb, _emit

__all__ = ["Supervisor", "RecoveryEscalated", "RESTART_EXIT_CODE"]

# exit status a stall-escalated process dies with: distinguishable from
# a crash (nonzero) and from success, so a supervising launcher knows
# "restart me into the resume path" (tools/chaos_cluster.py's freeze
# scenario watches for it)
RESTART_EXIT_CODE = 96


class RecoveryEscalated(RuntimeError):
    """Bounded retries exhausted — the fault is not transient and not a
    single poison batch; a human (or a higher-level scheduler) owns the
    next move."""


class Supervisor:
    """Resilient driver for a TrainLoop.

        loop = TrainLoop(net, loss, trainer)
        sup = Supervisor("/ckpts/run1", every=50, keep=3)
        losses = sup.drive(loop, train_iter, steps=500)

    or, equivalently, ``loop.fit(train_iter, steps=500,
    resilience="/ckpts/run1")``.

    Parameters: ``every``/``keep`` forward to
    :class:`~.checkpoint.CheckpointManager`; ``max_retries`` bounds
    CONSECUTIVE faults before escalation; ``backoff_s`` is the base of
    the exponential retry backoff; ``skip_poison=True`` advances past
    the faulting chunk's batches (a poison batch), ``False`` re-reads
    the same chunk (a transient fault); ``on_stall`` is ``'none'``
    (record only) or ``'exit'`` (die with RESTART_EXIT_CODE for the
    launcher to restart)."""

    def __init__(self, ckpt_dir, every=None, keep=None, max_retries=2,
                 backoff_s=0.05, skip_poison=True, on_stall=None):
        self.ckpt_dir = os.path.abspath(ckpt_dir)
        self.every = every
        self.keep = keep
        self.max_retries = int(max_retries)
        self.backoff_s = float(backoff_s)
        self.skip_poison = bool(skip_poison)
        from ..settings import env_str
        self.on_stall = (on_stall or env_str(
            "MXTPU_RESILIENCE_ON_STALL", "none")).lower()
        if self.on_stall not in ("none", "exit"):
            raise ValueError(f"on_stall must be 'none' or 'exit', "
                             f"got {self.on_stall!r}")
        self.manager = None
        self._c_recoveries = _counter(
            "resilience.recoveries_total", "resilience")
        self._c_rollbacks = _counter("resilience.rollbacks",
                                              "resilience")
        self._c_resumes = _counter("resilience.resumes",
                                            "resilience")
        self._c_steps_lost = _counter(
            "resilience.steps_lost_total", "resilience")
        self._c_escalations = _counter(
            "resilience.retries_exhausted", "resilience")
        self._c_restarts = _counter(
            "resilience.restarts_requested", "resilience")

    # -- healthmon verdict routing ---------------------------------------
    def on_health_alert(self, name, args, step=None):
        """Called by healthmon's alert fan-out while this supervisor is
        registered. NaN verdicts are acted on by the drive loop itself
        (it sees the loss first); the stall watchdog's verdict is acted
        on HERE — the loop thread is the thing that is stuck."""
        if name != "stall":
            return
        self._c_restarts.increment()
        info = {"age_s": args.get("age_s"), "on_stall": self.on_stall,
                "last_checkpoint_step":
                    self.manager.last_saved_step if self.manager else None}
        _breadcrumb("restart_requested", info)
        _emit("resilience", "resilience.restart_requested", step=step,
              args=info)
        if self.on_stall == "exit":
            # the loop thread is wedged (that is what a stall IS) — a
            # graceful unwind cannot run. Die with the restart code so
            # the launcher restarts into the resume path — but from a
            # SEPARATE thread after a beat, so healthmon's own stall
            # handler (which called us) finishes writing the flight
            # post-mortem first.
            import threading

            def _die():
                time.sleep(1.0)
                try:
                    from ..healthmon import events as _events
                    log = _events.current_log()
                    if log is not None:
                        log.close()
                except Exception:   # noqa: BLE001
                    pass
                os._exit(RESTART_EXIT_CODE)

            threading.Thread(target=_die, daemon=True,
                             name="mxtpu-resilience-restart").start()

    # -- the drive loop ---------------------------------------------------
    def drive(self, loop, data, steps=None, cycle=True):
        """Run ``loop`` to a TARGET of ``steps`` total optimizer updates
        (a resumed run counts its restored updates toward the target),
        checkpointing every N and recovering per the policy. Returns the
        per-step losses of the chunks that SURVIVED (rolled-back chunks'
        losses are discarded with their updates)."""
        from .. import resilience as _rs
        from ..io.prefetch import DevicePrefetcher

        if steps is None:
            raise ValueError("resilient fit is steps-driven: pass steps=")
        k = loop.chunk
        if steps < k:
            raise ValueError(f"steps={steps} is less than one chunk "
                             f"of {k}")

        self.manager = CheckpointManager(self.ckpt_dir, loop.step,
                                         every=self.every, keep=self.keep)
        _rs._register(self)
        try:
            return self._drive(loop, data, int(steps), cycle,
                               DevicePrefetcher)
        finally:
            _rs._unregister(self)
            self.manager.close()

    def _build_from_probe(self, loop, data):
        """Compile the step from the source's first batch WITHOUT
        consuming an update (restore needs a built step). Returns the
        source to keep feeding from: the probe batch is given back by
        reset()/re-iteration where the source supports it, and CHAINED
        back in front of a one-shot iterator/generator (which has no
        rewind — dropping the probe there would silently lose the first
        unconsumed batch of a cursor resume)."""
        import itertools

        from ..io.prefetch import _split_batch
        from ..ndarray import NDArray
        it = None
        if hasattr(data, "next"):
            first = data.next()
        else:
            it = iter(data)
            first = next(it)
        x, y = _split_batch(first)
        if y is None:
            raise ValueError("resilient fit needs labeled batches")
        as_nd = (lambda a: a if isinstance(a, NDArray)
                 else NDArray(np.asarray(a)))
        loop.step.ensure_built(as_nd(x), as_nd(y))
        if hasattr(data, "reset"):
            data.reset()
            return data
        if it is None or it is data:
            # .next()-style source without reset(), or a one-shot
            # iterator: the probe consumed a real batch with no way to
            # rewind — chain it back in front
            return itertools.chain([first], data if it is None else it)
        return data

    def _drive(self, loop, data, target, cycle, DevicePrefetcher):
        from ..parallel import checkpoint as _ckpt
        k = loop.chunk
        # same steps= semantics as the un-supervised fit: whole chunks
        # only, remainder dropped — arming resilience must not change
        # how many updates fit(steps=N) performs
        target = (target // k) * k
        cursor = 0
        if _ckpt.list_steps(self.ckpt_dir):
            # process-level resume: restart-from-last-good
            data = self._build_from_probe(loop, data)
            n, cur = self.manager.restore_last_good()
            cursor = int(cur or 0)
            self._c_resumes.increment()
            self._c_recoveries.increment()
            info = {"restored_step": n, "cursor": cursor,
                    "dir": self.ckpt_dir}
            _breadcrumb("resume", info)
            _emit("resilience", "resilience.resume", step=n, args=info)
            self._beat_watchdog()
            # restore_last_good just full-digest-verified the newest
            # checkpoint, the probe built the step, and the watchdog is
            # fresh — the first-chunk guard below would only repeat all
            # three (for a multi-GB sharded checkpoint, last_good()'s
            # re-hash doubles resume-time disk I/O)
            resumed = True
        else:
            resumed = False
        history = []            # [(first_step, losses_np)]
        faults = 0
        pending = None          # re-read chunk under skip_poison=False
        with DevicePrefetcher(
                data, depth=loop.prefetch_depth, chunk=k,
                sharding=lambda: loop.step._stacked_sharding,
                cycle=cycle, skip=cursor) as pf:
            guarded = resumed
            while loop.step._num_update < target:
                if pending is not None:
                    xs, ys = pending
                    pending = None
                else:
                    try:
                        xs, ys = next(pf)
                    except StopIteration:
                        raise ValueError(
                            f"data source exhausted at update "
                            f"{loop.step._num_update} of {target} and "
                            f"cannot be rewound") from None
                    cursor += k
                if not guarded:
                    # a pre-flight checkpoint of the CURRENT state (step
                    # 0, or the resumed step if its save was pruned):
                    # rollback is then ALWAYS possible, even for a fault
                    # in the very first chunk
                    guarded = True
                    loop.step.ensure_built(_first_micro(xs),
                                           _first_micro(ys))
                    if self.manager.last_good() is None:
                        self.manager.save_now(cursor=cursor - k,
                                              block=True)
                    self._beat_watchdog()
                start = loop.step._num_update + 1
                losses = loop.run_chunk(xs, ys).asnumpy()
                if np.isfinite(losses).all():
                    faults = 0
                    history.append((start, losses))
                    self.manager.maybe_save(cursor=cursor)
                    self._mark_healthmon(float(losses[-1]))
                    continue
                # ---- fault: non-finite loss inside this chunk --------
                # the verdict surface first: healthmon's NaN sentinel
                # fires (counter + flight + event) so the timeline shows
                # FAULT -> ACTION, not an unexplained rollback; its
                # on_nan='raise' is subsumed by supervision (rollback IS
                # the raise handler here)
                bad = losses[~np.isfinite(losses)]
                self._observe_nan(float(bad[0]) if bad.size else
                                  float("nan"),
                                  step=loop.step._num_update)
                faults += 1
                if faults > self.max_retries:
                    self._escalate(loop.step._num_update, faults)
                to_step, history = self._rollback(
                    loop, history, reason="nan_loss",
                    fault_step=loop.step._num_update, attempt=faults)
                if not self.skip_poison:
                    pending = (xs, ys)   # transient fault: re-read
        # run end: final checkpoint so a later process resumes from here
        self.manager.save_now(cursor=cursor, block=True)
        if not history:
            return np.zeros((0,), np.float32)
        return np.concatenate([h for _, h in history])

    def _beat_watchdog(self):
        """Recovery progress is not a stall: a restore, shape-probe
        compile, or guard save legitimately outlasts a tight stall
        deadline, and firing mid-recovery would restart a process that
        is already recovering. Re-arm the deadline when one completes."""
        try:
            from .. import healthmon as _hm
            hm = _hm.current()
            if hm is not None and hm.watchdog is not None:
                hm.watchdog.beat()
        except Exception:   # noqa: BLE001 — telemetry only
            pass

    def _observe_nan(self, value, step=None):
        try:
            from .. import healthmon as _hm
            _hm.observe_loss(value, step=step)
        except FloatingPointError:
            pass
        except Exception:   # noqa: BLE001 — telemetry only
            pass

    def _mark_healthmon(self, value):
        """One healthmon mark per survived chunk: beats the stall
        watchdog (a healthy supervised loop must not look stalled),
        feeds the step-time EWMA/event stream, and ticks the NaN
        sentinel with the already-fetched loss scalar. Under
        supervision a non-finite value triggers ROLLBACK, not the
        sentinel's on_nan='raise'."""
        try:
            from .. import healthmon as _hm
            hm = _hm.current()
            if hm is not None:
                hm.step_end(loss=value)
        except FloatingPointError:
            pass
        except Exception:   # noqa: BLE001 — telemetry only
            pass

    def _rollback(self, loop, history, reason, fault_step, attempt):
        _set_gauge("resilience.rollback_in_progress", 1,
                            "resilience")
        try:
            to_step, _cur = self.manager.restore_last_good()
            self._beat_watchdog()
            steps_lost = max(0, fault_step - to_step)
            self._c_rollbacks.increment()
            self._c_recoveries.increment()
            self._c_steps_lost.increment(steps_lost)
            _set_gauge("resilience.steps_lost_last", steps_lost,
                                "resilience")
            args = {"reason": reason, "from_step": fault_step,
                    "to_step": to_step, "steps_lost": steps_lost,
                    "attempt": attempt,
                    "skip_poison": self.skip_poison}
            _breadcrumb("rollback", args)
            _emit("resilience", "resilience.rollback", step=fault_step,
                  args=args)
            # rolled-back updates take their losses with them: the
            # returned history is the trajectory that SURVIVED
            history = [(s, l) for s, l in history
                       if s + len(l) - 1 <= to_step]
            if attempt > 1 and self.backoff_s > 0:
                time.sleep(self.backoff_s * (2 ** (attempt - 2)))
            return to_step, history
        finally:
            _set_gauge("resilience.rollback_in_progress", 0,
                                "resilience")

    def _escalate(self, at_step, faults):
        self._c_escalations.increment()
        args = {"step": at_step, "consecutive_faults": faults,
                "max_retries": self.max_retries}
        _breadcrumb("escalation", args)
        _emit("alert", "resilience.escalation", step=at_step, args=args)
        raise RecoveryEscalated(
            f"resilience: {faults} consecutive faults at step {at_step} "
            f"exceeded max_retries={self.max_retries} — not a transient "
            f"or single poison batch; escalating")


def _first_micro(stacked):
    """First micro-batch of a stacked (k, batch, ...) chunk as an
    NDArray (for ensure_built's shape probe)."""
    from ..ndarray import NDArray
    if isinstance(stacked, NDArray):
        return NDArray(stacked._data[0])
    return NDArray(np.asarray(stacked)[0])
