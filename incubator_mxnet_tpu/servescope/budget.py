"""LatencyBudget — tail-latency attribution for the serving path.

The serving analogue of perfscope's :class:`StepBudget`: where the step
budget decomposes one steady *training* step, the latency budget
decomposes the request latency *distribution* — per compiled bucket —
into the five lifecycle components :mod:`.spans` measures, and answers
the question the aggregate p99 histogram cannot: *which part of the
pipeline IS the tail?*

Attribution is computed from a bounded reservoir of recent spans (the
last ``MXTPU_SERVESCOPE_WINDOW`` responded requests, default 4096, per
bucket and overall) rather than from histogram interpolation, so the
published numbers keep the spans' exact sum identity:

* **component distributions** — independent p50/p95/p99 of each
  component (the dashboard view; these do NOT sum to the e2e
  percentiles and are not meant to);
* **quantile-cohort attribution** — for each of p50/p95/p99, the mean
  component split over the requests whose e2e latency sits AT that
  quantile (the nearest-rank cohort). Cohort means sum exactly to the
  cohort's mean e2e, which by construction sits at the quantile — so
  "p99 is 83% queue_wait" is an accounting fact about the actual tail
  requests, not a model.

Each bucket's row joins the verdicts the other scopes already hold for
its AOT executable (both captures ride the serving compile for free):
perfscope's roofline verdict and commscope's resharding verdict — the
"accidental all-gather on the serve path" ROADMAP names as the p99
catastrophe. When a devicescope capture window completed over serving
dispatches AFTER this budget began (the PR 10 stale-window rule), the
``device_exec`` component's provenance upgrades to
``measured(profile)`` with the measured-vs-host-wall drift beside it;
otherwise it stays ``host_wall`` (the executable call is synchronous at
the host once outputs convert, so the wall is measured, not estimated —
but it includes transfer, which only a device timeline can separate).
"""
from __future__ import annotations

import collections
import threading
import time
import warnings

from .. import profiler as _prof
from .spans import COMPONENTS

__all__ = ["LatencyBudget", "quantile_cohorts", "DEFAULT_WINDOW",
           "DEVICE_EXEC_SOURCES"]

DEFAULT_WINDOW = 4096

# provenance taxonomy for the device_exec component (mirrors the step
# budget's collective_source discipline)
DEVICE_EXEC_SOURCES = ("host_wall", "measured(profile)")

# attribution quantiles and the cohort width (fraction of n) around each
_QUANTILES = (0.50, 0.95, 0.99)


def _env_window() -> int:
    from ..settings import env_int
    return max(64, env_int("MXTPU_SERVESCOPE_WINDOW", DEFAULT_WINDOW,
                           on_error="default"))


def _nearest_rank(n: int, q: float) -> int:
    """0-based nearest-rank index of quantile q in a sorted length-n
    sequence."""
    import math
    return min(n - 1, max(0, math.ceil(q * n) - 1))


def quantile_cohorts(entries, neighborhood: float = 0.10) -> dict:
    """Per-quantile cohort attribution over a list of component dicts.

    ``entries``: dicts with ``e2e_ms`` + the five COMPONENTS. For each
    quantile the cohort is the requests sitting AT the quantile: up to
    ``max(1, n//100)`` entries starting at the nearest-rank index,
    value-capped at ``(1 + neighborhood)`` x the quantile itself — so a
    lone 10x outlier above p99, or a bimodal jump right at the
    quantile, can never smear the attribution (the cohort degrades to
    the single quantile request, whose components sum to its e2e
    exactly). Returns::

        {"p99": {"e2e_ms": <nearest-rank e2e>, "cohort": k,
                 "components": {name: mean ms}, "sum_ms": <mean e2e>,
                 "top_component": name, "top_share": 0..1}, ...}

    ``sum_ms`` equals the cohort's mean e2e exactly (the spans' sum
    identity survives the mean), and the value cap bounds
    |sum_ms - e2e_ms| / e2e_ms by ``neighborhood`` BY CONSTRUCTION —
    the acceptance criterion's 15% is structural, not statistical."""
    n = len(entries)
    if n == 0:
        return {}
    by_e2e = sorted(entries, key=lambda c: c["e2e_ms"])
    width = max(1, n // 100)
    out = {}
    for q in _QUANTILES:
        i = _nearest_rank(n, q)
        cap = by_e2e[i]["e2e_ms"] * (1.0 + neighborhood)
        cohort = [by_e2e[i]]
        for c in by_e2e[i + 1:i + width]:
            if c["e2e_ms"] > cap:
                break
            cohort.append(c)
        k = len(cohort)
        comps = {key: sum(c[key] for c in cohort) / k for key in COMPONENTS}
        total = sum(comps.values())
        top = max(comps, key=comps.get)
        out[f"p{int(q * 100)}"] = {
            "e2e_ms": round(by_e2e[i]["e2e_ms"], 4),
            "cohort": k,
            "components": {key: round(v, 4) for key, v in comps.items()},
            "sum_ms": round(total, 4),
            "top_component": top,
            "top_share": round(comps[top] / total, 4) if total > 0 else None,
        }
    return out


def _dist(values) -> dict:
    """p50/p95/p99/mean/max of a value list (nearest-rank, no
    interpolation — these are real observations)."""
    if not values:
        return {"p50": None, "p95": None, "p99": None, "mean": None,
                "max": None}
    vs = sorted(values)
    n = len(vs)
    return {"p50": round(vs[_nearest_rank(n, 0.50)], 4),
            "p95": round(vs[_nearest_rank(n, 0.95)], 4),
            "p99": round(vs[_nearest_rank(n, 0.99)], 4),
            "mean": round(sum(vs) / n, 4),
            "max": round(vs[-1], 4)}


_ADVICE = {
    "queue_wait_ms": "the dispatch pipeline is saturated - raise "
                     "max_batch or add replicas, not the kernel",
    "coalesce_delay_ms": "the batch window is the tail - lower "
                         "max_delay_ms",
    "pad_overhead_ms": "bucket padding dominates - add a bucket nearer "
                       "the typical batch size",
    "device_exec_ms": "the executable itself is the tail - see the "
                      "bucket's roofline verdict",
    "respond_ms": "the host-side response path (unpad/serialize/fulfil) "
                  "is the tail",
}


class LatencyBudget:
    """Accumulates responded spans' components and settles the
    attribution. One instance per servescope arm; the batcher's
    dispatcher thread is the only writer on the hot path, but the lock
    keeps multi-server processes honest (it is per observation, off the
    device-exec critical path)."""

    def __init__(self, window: int | None = None):
        self._window = window or _env_window()
        self._lock = threading.Lock()
        self._overall = collections.deque(maxlen=self._window)
        self._per_bucket = {}
        self._real_slots = {}
        self._count = 0
        # stale-window reference for the devicescope upgrade (PR 10's
        # rule: a window completed BEFORE this budget began measured
        # someone else's traffic)
        self._began_monotonic = time.monotonic()
        self._drift_warned = False

    def observe(self, span, comp: dict):
        """One responded span's settled components (from spans.finish)."""
        entry = {k: comp[k] for k in COMPONENTS}
        entry["e2e_ms"] = comp["e2e_ms"]
        b = int(span.bucket or 0)
        with self._lock:
            self._count += 1
            self._overall.append(entry)
            dq = self._per_bucket.get(b)
            if dq is None:
                dq = self._per_bucket[b] = collections.deque(
                    maxlen=self._window)
                self._real_slots[b] = [0, 0]     # [real, slots]
            dq.append(entry)
            rs = self._real_slots[b]
            rs[0] += int(span.real or 0)
            rs[1] += b

    # -- verdict joins -----------------------------------------------------
    @staticmethod
    def _bucket_verdicts() -> dict:
        """bucket -> {roofline verdict, resharding verdict} joined from
        the perfscope/commscope program tables by the serving compile
        site's program name (kind == "serving_bucket"). Never raises;
        an unjoined bucket reports None, never a guess."""
        out = {}
        try:
            from .. import perfscope as _ps
            for p in _ps.programs():
                if p.get("kind") == "serving_bucket" \
                        and p.get("bucket") is not None:
                    out.setdefault(int(p["bucket"]), {})["verdict"] = \
                        p.get("verdict")
        except Exception:  # noqa: BLE001
            pass
        try:
            from .. import commscope as _cs
            for p in _cs.programs():
                if p.get("kind") != "serving_bucket":
                    continue
                # commscope records carry the program name, not the
                # bucket extra — the bucket is the ":b<k>" suffix of
                # the serving compile site's name (frozen.program_name)
                b = p.get("bucket")
                if b is None:
                    name = str(p.get("name") or "")
                    if ":b" in name:
                        tail = name.rsplit(":b", 1)[1]
                        if tail.isdigit():
                            b = int(tail)
                if b is None:
                    continue
                slot = out.setdefault(int(b), {})
                slot["resharding_collectives"] = \
                    p.get("resharding_collectives")
                slot["hlo_available"] = p.get("hlo_available")
                slot["collective_count"] = \
                    (p.get("totals") or {}).get("count")
        except Exception:  # noqa: BLE001
            pass
        return out

    def _device_window(self):
        """(source, window-info) for the device_exec provenance. The
        upgrade requires devicescope armed, a completed window newer
        than this budget, and a measured per-step busy time; the
        measured-vs-host-wall drift rides along, warning once past
        devicescope's shared threshold."""
        try:
            from .. import devicescope as _ds
            if _ds._DS is None:
                return "host_wall", None
            w = _ds.last_window()
            if w is None or w.completed_at is None \
                    or w.completed_at < self._began_monotonic:
                return "host_wall", None
            # workload identity, not just freshness: a fresh window
            # stepped by the TRAIN loop (train and serve share a
            # process) measured someone else's dispatches — upgrading
            # from it would compare train-step busy time against the
            # serving exec wall and warn about phantom drift
            if getattr(w, "workload", None) != "serving":
                return "host_wall", None
            s = w.summary()
            per = (s or {}).get("per_step") or {}
            busy = per.get("device_busy_ms")
            if not isinstance(busy, (int, float)) or busy <= 0:
                return "host_wall", None
            host = (w.dispatch_ms / w.steps_done) if w.steps_done else None
            drift = (abs(busy - host) / host
                     if host and host > 1e-9 else None)
            info = {"path": w.logdir,
                    "dispatches": w.steps_done,
                    "measured_busy_ms_per_dispatch": round(busy, 4),
                    "host_wall_ms_per_dispatch":
                        round(host, 4) if host is not None else None,
                    "drift": round(drift, 4) if drift is not None else None,
                    "drift_warning": bool(
                        drift is not None
                        and drift > _ds.DRIFT_THRESHOLD)}
            if info["drift_warning"] and not self._drift_warned:
                self._drift_warned = True
                _prof.counter("servescope.device_drift_warnings",
                              "servescope").increment()
                warnings.warn(
                    f"servescope: measured device busy per dispatch "
                    f"({busy:.3f} ms) and the host exec wall "
                    f"({host:.3f} ms) disagree by more than "
                    f"{_ds.DRIFT_THRESHOLD:.0%} — the host wall is "
                    f"paying transfer/dispatch the device never saw; "
                    f"trust the measured window (docs/servescope.md)",
                    stacklevel=3)
            return "measured(profile)", info
        except Exception:  # noqa: BLE001 — measurement must never break
            return "host_wall", None

    # -- settlement --------------------------------------------------------
    def _group(self, entries, extra=None) -> dict:
        out = {"count": len(entries),
               "e2e_ms": _dist([c["e2e_ms"] for c in entries]),
               "component_dist": {k: _dist([c[k] for c in entries])
                                  for k in COMPONENTS},
               "attribution": quantile_cohorts(entries)}
        if extra:
            out.update(extra)
        return out

    def attribution(self) -> dict:
        """The settled attribution: overall + per-bucket groups, bucket
        verdicts, device_exec provenance, and the one-line advice the
        p99 cohort supports."""
        with self._lock:
            overall = list(self._overall)
            per_bucket = {b: list(dq) for b, dq in self._per_bucket.items()}
            fills = {b: (rs[0] / rs[1] if rs[1] else None)
                     for b, rs in self._real_slots.items()}
            total = self._count
        verdicts = self._bucket_verdicts()
        source, window = self._device_window()
        doc = {
            "requests": total,
            "window": self._window,
            "components": list(COMPONENTS),
            "device_exec_source": source,
            "device_window": window,
            "overall": self._group(overall),
            "per_bucket": {},
        }
        for b in sorted(per_bucket):
            v = verdicts.get(b, {})
            doc["per_bucket"][str(b)] = self._group(per_bucket[b], extra={
                "bucket": b,
                "fill": round(fills[b], 4) if fills.get(b) else None,
                "verdict": v.get("verdict"),
                "resharding_collectives": v.get("resharding_collectives"),
                "hlo_available": v.get("hlo_available"),
            })
        doc["advice"] = self._advice(doc)
        return doc

    @staticmethod
    def _advice(doc) -> str | None:
        """The mxdiag one-liner: which bucket's p99 cohort is worst,
        which component owns it, what to do about it."""
        worst = None
        for key, grp in doc["per_bucket"].items():
            att = (grp.get("attribution") or {}).get("p99")
            if not att or att.get("top_share") is None:
                continue
            if worst is None or att["e2e_ms"] > worst[1]["e2e_ms"]:
                worst = (grp.get("bucket", key), att)
        if worst is None:
            att = (doc["overall"].get("attribution") or {}).get("p99")
            if not att or att.get("top_share") is None:
                return None
            worst = (None, att)
        bucket, att = worst
        top = att["top_component"]
        where = f" at bucket {bucket}" if bucket is not None else ""
        return (f"p99 is {att['top_share']:.0%} "
                f"{top.replace('_ms', '')}{where} - "
                f"{_ADVICE.get(top, top)}")

    def brief(self) -> dict | None:
        """The /healthz-sized summary: overall p99 cohort only."""
        with self._lock:
            overall = list(self._overall)
        if not overall:
            return None
        att = quantile_cohorts(overall).get("p99")
        if not att:
            return None
        return {"e2e_p99_ms": att["e2e_ms"],
                "top_component": att["top_component"],
                "top_share": att["top_share"],
                "requests_traced": len(overall)}
