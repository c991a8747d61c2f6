"""Runtime watermark timeline: a bounded ring of allocator samples.

Each sample is taken at an existing step mark (TrainLoop.run_chunk,
the serving batcher) and records what the XLA
allocator says each device holds RIGHT NOW — ``bytes_in_use`` and
``peak_bytes_in_use`` from ``device.memory_stats()`` via the
normalized :func:`profiler.device_memory_stats` helper — plus the host
RSS. Backends whose devices report nothing (XLA:CPU returns None) are
recorded ``{"available": false}`` per device and counted
``memscope.samples_unavailable``; the host RSS is still real there,
which is exactly the number that bounds a CPU tier-1 run.

The ring is bounded (``MXTPU_MEMSCOPE_RING``, default 256, oldest
evicted) so an armed long run cannot grow it; the summary feeds the
p50/p95/peak gauges and the headroom fraction, and the last few
samples — the *tail* — are what an OOM post-mortem attaches as "what
memory did in the steps before death".
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque

from ..profiler.counters import counter as _counter, \
    set_gauge as _set_gauge

__all__ = ["WatermarkRing", "host_rss_bytes"]


def host_rss_bytes():
    """Current resident set size of this process in bytes, or None.
    /proc/self/statm is current truth; ru_maxrss (the fallback) is a
    peak, still useful as an upper bound on exotic platforms."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:  # noqa: BLE001
        pass
    try:
        import resource
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:  # noqa: BLE001
        return None


def _pct(vals, q):
    """Nearest-rank percentile over a small sample list, None on
    empty."""
    if not vals:
        return None
    s = sorted(vals)
    return s[min(len(s) - 1, int(round(q * (len(s) - 1))))]


class WatermarkRing:
    """The bounded per-step allocator-sample timeline."""

    def __init__(self, limit=256):
        try:
            self.limit = max(1, int(limit))
        except (TypeError, ValueError):
            self.limit = 256
        self._ring = deque(maxlen=self.limit)
        self._lock = threading.Lock()
        self.samples_total = 0

    def reset(self):
        with self._lock:
            self._ring.clear()
            self.samples_total = 0

    # -- ingestion ---------------------------------------------------------
    def sample(self, step=None, workload=None):
        """Take one sample. Never raises — this sits on the hot step
        path of armed runs."""
        try:
            return self._sample(step, workload)
        except Exception:  # noqa: BLE001 — sampling never breaks a step
            return None

    def _sample(self, step, workload):
        from ..profiler import device_memory_stats
        devices = {}
        available = False
        try:
            import jax
            local = jax.local_devices()
        except Exception:  # noqa: BLE001
            local = []
        for d in local:
            st = device_memory_stats(d)
            if not st or st.get("available") is False:
                devices[str(d)] = {"available": False}
                continue
            available = True
            devices[str(d)] = {
                "available": True,
                "bytes_in_use": st.get("bytes_in_use"),
                "peak_bytes_in_use": st.get("peak_bytes_in_use"),
                "bytes_limit": st.get("bytes_limit")}
        rec = {"step": None if step is None else int(step),
               "t": time.monotonic(),
               "workload": workload,
               "host_rss_bytes": host_rss_bytes(),
               "devices": devices,
               "available": available}
        with self._lock:
            self._ring.append(rec)
            self.samples_total += 1
        _counter("memscope.samples", "memscope").increment()
        if not available:
            _counter("memscope.samples_unavailable",
                     "memscope").increment()
        else:
            in_use = sum(d.get("bytes_in_use") or 0
                         for d in devices.values() if d.get("available"))
            peak = max((d.get("peak_bytes_in_use") or 0
                        for d in devices.values() if d.get("available")),
                       default=0)
            _set_gauge("memscope.bytes_in_use", in_use, "memscope")
            _set_gauge("memscope.peak_bytes_in_use", peak, "memscope")
        if rec["host_rss_bytes"]:
            _set_gauge("memscope.host_rss_bytes", rec["host_rss_bytes"],
                       "memscope")
        return rec

    # -- reporting ---------------------------------------------------------
    def snapshot(self) -> list:
        with self._lock:
            return [dict(r) for r in self._ring]

    def latest(self):
        with self._lock:
            return dict(self._ring[-1]) if self._ring else None

    def tail(self, n=8) -> list:
        with self._lock:
            return [dict(r) for r in list(self._ring)[-int(n):]]

    def summary(self) -> dict:
        """p50/p95/peak over the ring for device bytes and host RSS,
        plus the bound bookkeeping trace_check pins (``ring`` <=
        ``ring_limit`` even when ``samples`` exceeds it)."""
        snap = self.snapshot()
        dev_in_use, dev_peak, rss = [], [], []
        for r in snap:
            if r.get("available"):
                devs = [d for d in r.get("devices", {}).values()
                        if isinstance(d, dict) and d.get("available")]
                dev_in_use.append(sum(d.get("bytes_in_use") or 0
                                      for d in devs))
                dev_peak.append(max((d.get("peak_bytes_in_use") or 0
                                     for d in devs), default=0))
            if r.get("host_rss_bytes"):
                rss.append(r["host_rss_bytes"])
        out = {"samples": self.samples_total, "ring": len(snap),
               "ring_limit": self.limit,
               "available": bool(dev_in_use),
               "device": None, "host_rss": None,
               "tail": self.tail(8)}
        if dev_in_use:
            out["device"] = {"p50": _pct(dev_in_use, 0.50),
                             "p95": _pct(dev_in_use, 0.95),
                             "peak": max(dev_peak) if dev_peak else None,
                             "latest": dev_in_use[-1]}
            _set_gauge("memscope.bytes_p50", out["device"]["p50"],
                       "memscope")
            _set_gauge("memscope.bytes_p95", out["device"]["p95"],
                       "memscope")
        if rss:
            out["host_rss"] = {"p50": _pct(rss, 0.50),
                               "p95": _pct(rss, 0.95),
                               "peak": max(rss), "latest": rss[-1]}
        return out
