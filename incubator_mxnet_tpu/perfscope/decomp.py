"""Step-time decomposition: where does a training step's wall time go?

Wall-clock step time alone cannot distinguish a chip working from a chip
waiting — through an async dispatch path (XLA's dependency engine)
the host returns at enqueue, so a
62 ms step could be 60 ms of MXU work or 10 ms of work behind 50 ms of
input starvation. :class:`StepBudget` combines the signals the earlier
observability layers already export into one per-step budget::

    step_ms = device_compute + collective + input_wait + host_gap + other

* **device_compute** — measured by a post-steady probe
  (:meth:`probe_device_time`): a few extra steps each terminated by a
  host value fetch (a device barrier, like ``block_until_ready()``),
  so the synchronized per-step wall minus the measured host dispatch share is the device
  time. When an ``mxtpu.devicescope`` capture window completed for the
  run, the window's MEASURED device busy time replaces the probe value
  and the budget's provenance upgrades to ``measured(profile)`` (the
  probe stays beside it in the reconciliation block); the probe is the
  portable fallback that works with no window on the CPU tier-1 path.
* **collective** — delta of the ``kvstore.collective_ms`` counter over
  the steady phase (zero on single-process runs).
* **input_wait** — delta of ``io.wait_ms`` (DevicePrefetcher's consumer
  starvation counter) over the steady phase.
* **host_gap** — the host's per-step dispatch share: wall time spent
  INSIDE the step/chunk dispatch call (accumulated by the caller, or by
  ``trainloop.dispatch_ms`` in whole-loop mode). This is the time the
  device may sit idle between programs because the host hasn't enqueued
  the next one.
* **other** — the signed residual, clamped at zero: what the model
  cannot attribute (allocator stalls, GC, untimed host work). A large
  ``other`` is itself a finding.

Everything lands in ``perfscope.*`` gauges through the shared registry
(so /metrics, flight dumps and artifact json carry it with zero wiring) and
in the dict :meth:`finish` returns (``extra.perfscope.decomposition``).
"""
from __future__ import annotations

import time

from ..profiler.counters import (counters as _registry_snapshot,
                                 observe as _observe,
                                 set_gauge as _set_gauge)

__all__ = ["StepBudget", "probe_device_time", "counter_value"]


def counter_value(full_name: str) -> float:
    """Current numeric value of a registry metric (0.0 when absent)."""
    v = _registry_snapshot().get(full_name)
    return float(v) if isinstance(v, (int, float)) else 0.0


def probe_device_time(sync_step_fn, iters: int = 5) -> dict:
    """Measure synchronized per-step wall time: run ``sync_step_fn``
    (one step ENDING IN A HOST FETCH) ``iters`` times. Returns
    {"median_ms", "min_ms", "max_ms", "iters"}. The median is robust to
    a single scheduler burp on a 1-core box; each observation also lands
    in the ``perfscope.device_step_ms`` histogram so the distribution is
    exported, not just the point estimate."""
    times = []
    for _ in range(max(1, int(iters))):
        t0 = time.perf_counter()
        sync_step_fn()
        ms = (time.perf_counter() - t0) * 1e3
        times.append(ms)
        _observe("perfscope.device_step_ms", ms, "perfscope")
    times.sort()
    n = len(times)
    median = times[n // 2] if n % 2 else 0.5 * (times[n // 2 - 1]
                                                + times[n // 2])
    return {"median_ms": median, "min_ms": times[0], "max_ms": times[-1],
            "iters": n}


class StepBudget:
    """Accumulate the steady-phase signals and settle the budget.

    Usage::

        budget = StepBudget()
        budget.begin()                      # snapshot counters
        for _ in range(steps):
            t = time.perf_counter()
            loss = step(x, y)               # async dispatch
            budget.add_dispatch(time.perf_counter() - t)
        loss_val = float(loss)              # fetch = end of steady wall
        budget.end(steps=steps, steady_s=dt)
        probe = budget.probe(lambda: float(step(x, y)))   # sync probe
        decomp = budget.finish()            # the budget dict + gauges
    """

    def __init__(self, steps_per_dispatch: int = 1):
        self.steps_per_dispatch = max(1, int(steps_per_dispatch))
        self._dispatch_s = 0.0
        self._snap0 = {}
        self._snap1 = {}
        self._steps = 0
        self._steady_s = 0.0
        self._probe = None
        self._begin_monotonic = None

    _TRACKED = ("io/io.wait_ms", "mxtpu/kvstore.collective_ms",
                "trainloop/trainloop.dispatch_ms")

    def _snapshot(self):
        snap = _registry_snapshot()
        return {k: float(snap.get(k) or 0.0) for k in self._TRACKED}

    def begin(self):
        self._snap0 = self._snapshot()
        # steady-phase start marker: the devicescope reconciliation only
        # accepts capture windows completed AFTER this point — a window
        # from an earlier run in the same process measured someone
        # else's steady phase
        self._begin_monotonic = time.monotonic()
        return self

    def add_dispatch(self, seconds: float):
        """One dispatch call's host wall time (covers steps_per_dispatch
        micro-steps in chunked mode)."""
        self._dispatch_s += float(seconds)

    def end(self, steps: int, steady_s: float):
        self._steps = max(1, int(steps))
        self._steady_s = float(steady_s)
        self._snap1 = self._snapshot()

    def probe(self, sync_step_fn, iters: int = 5,
              steps_per_call: int | None = None) -> dict:
        """Run the synchronized device-time probe; ``steps_per_call``
        divides the measured wall when one call drives a whole chunk."""
        p = probe_device_time(sync_step_fn, iters=iters)
        div = max(1, int(steps_per_call or self.steps_per_dispatch))
        p = dict(p, median_ms=p["median_ms"] / div,
                 min_ms=p["min_ms"] / div, max_ms=p["max_ms"] / div,
                 steps_per_call=div)
        self._probe = p
        return p

    def _delta(self, key: str) -> float:
        return max(0.0, self._snap1.get(key, 0.0)
                   - self._snap0.get(key, 0.0))

    @staticmethod
    def _in_program_collectives() -> bool:
        """True when a multi-device mesh means the step's collectives
        run inside the jit program (where the kvstore counter cannot
        see them). Checks the process-global registry AND the last
        published layout (publish_param_stats runs with the executor's
        ACTUAL mesh, so an explicit ``mesh=`` FusedTrainStep — which
        never registers one — is still seen)."""
        try:
            from ..parallel import sharding as _sh
            mesh = _sh.get_mesh()
            if mesh is not None and int(getattr(mesh, "size", 1)) > 1:
                return True
            shape = (_sh.summary() or {}).get("mesh")
            if isinstance(shape, dict) and shape:
                n = 1
                for s in shape.values():
                    n *= int(s)
                return n > 1
            return False
        except Exception:  # noqa: BLE001
            return False

    @staticmethod
    def _commscope_estimate():
        """The steady train program's per-step collective estimate from
        mxtpu.commscope, or None when commscope is unarmed / captured
        nothing."""
        try:
            from .. import commscope as _cs
            if _cs._CS is None:
                return None
            return _cs.step_estimate()
        except Exception:  # noqa: BLE001
            return None

    def finish(self, model_flops_per_step=None, dtype="float32") -> dict:
        """Settle the budget and publish the ``perfscope.*`` gauges.

        With ``model_flops_per_step`` the result also carries the MFU
        decomposition: achieved MFU plus the counterfactual MFU with
        each non-compute component removed — the "what would fixing X
        buy" table ``mxdiag.py perf`` prints."""
        from . import cost as _cost
        steps = self._steps
        step_ms = self._steady_s / steps * 1e3
        input_wait = self._delta("io/io.wait_ms") / steps
        collective = self._delta("mxtpu/kvstore.collective_ms") / steps
        # collective PROVENANCE: the kvstore counter only times the
        # explicit-collective path. Under a GSPMD mesh the collectives
        # are compiler-inserted INSIDE the jit program, the counter
        # reads ~0, and reporting `collective: 0.0` as if measured would
        # silently fold all-reduce/all-gather time into device_compute —
        # exactly the attribution lie this field pins down:
        #   measured     kvstore counter (or a genuinely unsharded run)
        #   estimated    commscope's static-HLO link-time estimate for
        #                the steady train program (marked, never a
        #                measurement)
        #   unavailable  sharded in-program mode with commscope unarmed:
        #                the component is unknown, NOT zero
        collective_source = "measured"
        collective_est = None
        if collective <= 0.0:
            # the captured train program's OWN mesh is the primary
            # signal — it is correct even for an explicit mesh= executor
            # that never touched the registry; the registry/last-layout
            # check is the fallback for commscope-off runs
            est = self._commscope_estimate()
            if est is not None and est.get("devices", 1) > 1 \
                    and est.get("hlo_available", True) \
                    and isinstance(est.get("est_ms"), (int, float)):
                # hlo_available=False means commscope LOOKED and could
                # not read the program: that zero is ignorance, and
                # must fall through to "unavailable", not masquerade
                # as an estimated empty inventory
                collective = min(float(est["est_ms"]), step_ms)
                collective_source = "estimated"
                collective_est = est
            elif self._in_program_collectives() \
                    or (est is not None and est.get("devices", 1) > 1):
                collective_source = "unavailable"
        # host dispatch share: caller-accumulated wall, plus the whole-
        # loop executor's own dispatch counter when that path ran. On a
        # SYNCHRONOUS backend (XLA:CPU blocks in the jit call) this
        # includes the device compute itself, so it bounds host_gap from
        # above but is never attributed wholesale.
        disp_ms = (self._dispatch_s * 1e3
                   + self._delta("trainloop/trainloop.dispatch_ms")) / steps
        if self._probe is not None:
            # synchronized per-step wall IS the device-paced step time;
            # clip at the steady wall — the probe's extra host fetch can
            # only overstate it, and in steady state the device cannot
            # have been busy longer than the wall per step
            device = min(self._probe["median_ms"], step_ms)
            if collective_source == "estimated":
                # the probe's wall CONTAINS the in-program collectives;
                # peel the estimate out so the two components don't
                # double-count the same milliseconds
                device = max(0.0, device - collective)
        else:
            # no probe: peel the measured host/input/collective shares
            # off the wall and attribute the middle to the device
            device = max(0.0, step_ms - min(disp_ms, step_ms)
                         - input_wait - collective)
        budget_source = "probe" if self._probe is not None else "residual"
        # devicescope reconciliation: when a completed capture window
        # measured the device timeline, the MEASURED busy/collective
        # numbers replace the probe/estimate (provenance upgraded to
        # measured(profile)); the analytic values stay beside them in
        # the reconciliation block, and a >25% disagreement fires the
        # loud drift warning (docs/devicescope.md). With no window this
        # whole branch is one predicate and the budget settles exactly
        # as above — pinned by tests both ways.
        reconciliation = None
        try:
            from .. import devicescope as _ds
            upd = _ds.budget_overrides(
                step_ms=step_ms, device=device, collective=collective,
                collective_source=collective_source,
                source=budget_source, since=self._begin_monotonic)
        except Exception:  # noqa: BLE001 — measurement must never
            upd = None                 # destroy the settled budget
        if upd is not None:
            device = upd["device_compute_ms"]
            collective = upd["collective_ms"]
            collective_source = upd["collective_source"]
            budget_source = upd["source"]
            reconciliation = upd["reconciliation"]
            # prefetch wait can OVERLAP measured device busy (that is
            # the prefetcher's whole point), but the budget is a wall-
            # time accounting identity: the measured device/collective
            # claims are the strong ones, so input_wait keeps only the
            # share the device was actually idle for — otherwise an
            # input-starved-but-overlapped run sums past step_ms and
            # trace_check rejects the artifact as malformed
            input_wait = min(input_wait,
                             max(0.0, step_ms - device - collective))
        # host gap: steady time neither the device nor input/collective
        # explains, capped by the host time actually measured inside
        # dispatch calls (a gap the host didn't spend can't be its fault)
        remaining = step_ms - device - input_wait - collective
        host_gap = max(0.0, min(remaining, disp_ms))
        other = step_ms - (device + collective + input_wait + host_gap)
        decomp = {
            "step_ms": round(step_ms, 4),
            "device_compute_ms": round(device, 4),
            "collective_ms": round(collective, 4),
            "collective_source": collective_source,
            "collective_est": collective_est,
            "input_wait_ms": round(input_wait, 4),
            "host_gap_ms": round(host_gap, 4),
            "other_ms": round(max(0.0, other), 4),
            "residual_ms": round(other, 4),     # signed, pre-clamp
            "dispatch_ms": round(disp_ms, 4),   # raw host-dispatch share
            "steps": steps,
            "probe": self._probe,
            "source": budget_source,
            "reconciliation": reconciliation,
        }
        comp_sum = (decomp["device_compute_ms"] + decomp["collective_ms"]
                    + decomp["input_wait_ms"] + decomp["host_gap_ms"]
                    + decomp["other_ms"])
        decomp["sum_ms"] = round(comp_sum, 4)
        decomp["coverage"] = round(comp_sum / step_ms, 4) if step_ms else None
        for key in ("step_ms", "device_compute_ms", "collective_ms",
                    "input_wait_ms", "host_gap_ms", "other_ms"):
            _set_gauge("perfscope." + key, decomp[key], "perfscope")
        if model_flops_per_step:
            peaks = _cost.device_peaks()
            pk = _cost.peak_flops_for(dtype, peaks)
            f = float(model_flops_per_step)

            def mfu_at(ms):
                # no utilisation without a peak (unknown device_kind)
                return round(f / (ms * 1e-3) / pk, 6) if pk and ms > 0 \
                    else None

            mfu = mfu_at(step_ms)
            decomp["mfu"] = mfu
            _set_gauge("perfscope.mfu", mfu or 0.0, "perfscope")
            decomp["mfu_if_removed"] = {
                comp: mfu_at(step_ms - decomp[comp + "_ms"])
                for comp in ("collective", "input_wait", "host_gap", "other")
            }
            decomp["mfu_device_only"] = mfu_at(decomp["device_compute_ms"])
            decomp["peak_flops"] = pk
            decomp["model_flops_per_step"] = f
        return decomp
