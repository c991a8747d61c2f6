"""Periodic async sharded checkpoints — the save half of
mxtpu.resilience (docs/resilience.md).

The training thread's cost per due boundary is ONE device→host copy
(`parallel.checkpoint._host_tree` — jax.device_get at a step boundary,
where the donated buffers are between programs and safe to read); the
sha256 digesting, orbax serialization, manifest, atomic rename, and
rotation all run on a single worker thread. A save still in flight when
the next boundary comes due is SKIPPED (counted), so the queue depth is
bounded at one and a slow disk degrades checkpoint cadence, never step
time — the save-is-async contract tests/test_resilience.py pins.

Telemetry (domain ``resilience``): ``checkpoints_saved`` /
``checkpoints_pruned`` / ``saves_skipped`` / ``save_errors`` counters,
``last_checkpoint_step`` gauge, ``copy_ms`` / ``save_ms`` histograms
(boundary copy vs worker serialization — the ``extra.resilience``
save p50/p95 read the latter), plus a ``resilience.checkpoint_saved``
event per completed save.
"""
from __future__ import annotations

import os
import queue as _queue
import threading
import time

from ..parallel import checkpoint as _ckpt
from ..profiler.counters import (counter as _counter,
                                 histogram as _histogram,
                                 set_gauge as _set_gauge)

__all__ = ["CheckpointManager"]


def _emit(kind, name, step=None, args=None):
    """Structured event, if a healthmon event log is open (no-op
    otherwise — resilience works with or without healthmon)."""
    try:
        from ..healthmon import events as _events
        _events.emit(kind, name, step=step, args=args)
    except Exception:   # noqa: BLE001 — telemetry must not block saving
        pass


def _breadcrumb(name, args):
    try:
        from ..diagnostics import flight as _flight
        if _flight._REC is not None:
            _flight.record("resilience", name, args)
    except Exception:   # noqa: BLE001
        pass


class CheckpointManager:
    """Bounded-rotation async checkpointer for a FusedTrainStep (or a
    TrainLoop — anything exposing ``.step``/being a step).

        mgr = CheckpointManager(dir, step, every=50, keep=3)
        ...
        loss = step(x, y)
        mgr.maybe_save(cursor=batches_consumed)    # due? copy + enqueue
        ...
        mgr.close()                                # drain + final state

    every : checkpoint cadence in optimizer steps
            (``MXTPU_RESILIENCE_EVERY``, default 50; 0 disables periodic
            saves — ``save_now`` still works).
    keep  : bounded rotation of last-K GOOD checkpoints
            (``MXTPU_RESILIENCE_KEEP``, default 3).
    """

    def __init__(self, directory, step, every=None, keep=None):
        step = getattr(step, "step", step)   # accept a TrainLoop
        self._step = step
        self.directory = os.path.abspath(directory)
        from ..settings import env_float
        self.every = int(env_float("MXTPU_RESILIENCE_EVERY", 50.0,
                                   call_site=every))
        self.keep = int(env_float("MXTPU_RESILIENCE_KEEP", 3.0,
                                  call_site=keep))
        if self.keep < 1:
            raise ValueError(f"keep must be >= 1, got {self.keep}")
        os.makedirs(self.directory, exist_ok=True)
        self._c_saved = _counter("resilience.checkpoints_saved",
                                          "resilience")
        self._c_pruned = _counter("resilience.checkpoints_pruned",
                                           "resilience")
        self._c_skipped = _counter("resilience.saves_skipped",
                                            "resilience")
        self._c_errors = _counter("resilience.save_errors",
                                           "resilience")
        self._h_copy = _histogram("resilience.copy_ms",
                                           "resilience")
        self._h_save = _histogram("resilience.save_ms",
                                           "resilience")
        self._q = _queue.Queue(maxsize=1)    # bounded: at most 1 in flight
        self._idle = threading.Event()
        self._idle.set()
        self._stop = False
        self.last_saved_step = None
        self._last_enqueued = None
        self._last_error = None
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="mxtpu-resilience-ckpt")
        self._thread.start()

    # -- training-thread side ---------------------------------------------
    def due(self, step_num=None):
        """True when the step count CROSSED a cadence boundary since the
        last enqueued save — not just when it lands exactly on one: a
        chunked loop advances num_update by k per call, and requiring
        divisibility would stretch the effective cadence to
        lcm(every, k)."""
        n = self._step._num_update if step_num is None else int(step_num)
        if self.every <= 0 or n <= 0:
            return False
        ref = self._last_enqueued or 0
        return n // self.every > ref // self.every

    def maybe_save(self, cursor=None, step_num=None):
        """Call once per completed optimizer step (or chunk boundary).
        If the step count crossed the cadence, snapshot and enqueue.
        Returns True when a save was enqueued."""
        n = self._step._num_update if step_num is None else int(step_num)
        if not self.due(n):
            return False
        if n == self._last_enqueued:
            return False           # chunk boundaries can land on the same n
        return self.save_now(cursor=cursor, step_num=n, block=False)

    def save_now(self, cursor=None, step_num=None, block=True):
        """Snapshot (boundary device→host copy, the only blocking part)
        and hand the host tree to the worker. With ``block=False`` an
        in-flight save makes this a counted skip instead of a wait."""
        n = self._step._num_update if step_num is None else int(step_num)
        if not block and not self._idle.is_set():
            self._c_skipped.increment()
            return False
        if block:
            self.wait()
        t0 = time.perf_counter()
        tree = _ckpt._host_tree(self._step)
        self._h_copy.observe((time.perf_counter() - t0) * 1e3)
        meta = {"num_update": int(n)}
        if cursor is not None:
            meta["cursor"] = int(cursor)
        self._idle.clear()
        self._last_enqueued = n
        self._q.put((n, tree, meta))
        return True

    def wait(self, timeout=None):
        """Block until no save is in flight (tests / shutdown / before a
        rollback reads last-good)."""
        return self._idle.wait(timeout)

    # -- worker side ------------------------------------------------------
    def _worker(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            n, tree, meta = item
            t0 = time.perf_counter()
            try:
                path = self._gated_save(n, tree, meta)
                ms = (time.perf_counter() - t0) * 1e3
                self._h_save.observe(ms)
                self._c_saved.increment()
                self.last_saved_step = n
                _set_gauge("resilience.last_checkpoint_step", n,
                                    "resilience")
                args = {"path": path, "save_ms": round(ms, 3),
                        "cursor": meta.get("cursor")}
                _breadcrumb("checkpoint_saved", dict(args, step=n))
                _emit("resilience", "resilience.checkpoint_saved",
                      step=n, args=args)
                self._prune()
            except Exception as e:   # noqa: BLE001 — a failed save must
                # degrade durability, not kill training; but loudly
                self._c_errors.increment()
                self._last_error = f"{type(e).__name__}: {e}"
                _breadcrumb("save_error",
                            {"step": n, "error": self._last_error[:300]})
                _emit("alert", "resilience.save_error", step=n,
                      args={"error": self._last_error[:300]})
            finally:
                self._idle.set()

    def _gated_save(self, n, tree, meta):
        """On the XLA:CPU client, hold the process-wide transfer gate for
        the whole orbax serialization: that client is unsafe against
        concurrent client work (io/pipeline.py's safety model), and the
        donating-dispatch window on the training thread is also inside
        the gate there — so the save window and every XLA window are
        mutually excluded. The tree is already host numpy; training only
        stalls if a put/dispatch collides with an in-flight save, so the
        save stays async in the common case. Other backends save
        ungated (concurrency is the point of the worker thread)."""
        from ..io.pipeline import TRANSFER_GATE, _defer_put_needed
        if _defer_put_needed():
            with TRANSFER_GATE:
                return _ckpt.save_tree(self.directory, n, tree, meta=meta)
        return _ckpt.save_tree(self.directory, n, tree, meta=meta)

    def _prune(self):
        import shutil
        steps = _ckpt.list_steps(self.directory)
        for n in steps[:-self.keep] if len(steps) > self.keep else []:
            shutil.rmtree(_ckpt._step_path(self.directory, n),
                          ignore_errors=True)
            self._c_pruned.increment()

    # -- restore side -----------------------------------------------------
    def last_good(self):
        """Newest step number whose checkpoint verifies (None if none).
        Does NOT drain in-flight saves — call wait() first when that
        matters (the rollback path does)."""
        for n in reversed(_ckpt.list_steps(self.directory)):
            status, _ = _ckpt.verify_checkpoint(
                _ckpt._step_path(self.directory, n))
            if status in ("ok", "legacy"):
                return n
        return None

    def restore_last_good(self):
        """Drain in-flight saves, then restore the newest good
        checkpoint into the live step (falling back past corrupt ones —
        parallel/checkpoint.py owns that policy). Returns
        ``(restored_step, cursor)``; raises if nothing restorable."""
        self.wait()
        n = _ckpt.restore_train_step(self.directory, self._step)
        # a rollback moves num_update BELOW the save high-water mark:
        # re-anchor the cadence there so replayed steps checkpoint on
        # schedule instead of waiting to re-cross the old mark
        self._last_enqueued = n
        man = _ckpt.read_manifest(_ckpt._step_path(self.directory, n))
        cursor = None
        if man and isinstance(man.get("meta"), dict):
            c = man["meta"].get("cursor")
            cursor = int(c) if isinstance(c, int) else None
        return n, cursor

    # -- lifecycle --------------------------------------------------------
    def close(self):
        """Drain pending saves and stop the worker. Idempotent."""
        if self._stop:
            return
        self._stop = True
        self.wait()
        self._q.put(None)
        self._thread.join(timeout=30)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
