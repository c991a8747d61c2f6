"""Counters/gauges registry (parity: mx.profiler.Counter).

A :class:`Counter` is a named monotonically-adjustable value grouped under
a domain. The registry is always live (reads/writes are independent of
whether tracing is running) so subsystems can share one stats path —
`Monitor` publishes per-tensor stats here, the fused step publishes its
dispatch gauges, the jit cache publishes hit/miss counts. `dump()`
folds the registry into the Chrome trace as counter ('C') events so
values show up in chrome://tracing.

Thread-safety contract: the diagnostics sampler thread reads the registry
while engine worker threads and the training loop write it, so every
mutation (`increment`/`decrement`/`set_value`) and every snapshot takes
the ONE module lock — a single uncontended lock acquire per op, which is
cheap enough for the always-on path (verified by the concurrency test in
tests/test_diagnostics.py: N threads x M increments land exactly N*M).

Each counter carries a `kind`: "counter" (monotonic, incremented),
"gauge" (latest-value, written via `set_value`/`set_gauge`), or
"histogram" (:class:`Histogram` — Prometheus-style cumulative buckets
with `observe()`, used for serving latency distributions). Exporters
(diagnostics/export.py) use the kind for Prometheus TYPE lines and
validators use it to check monotonicity of time series (for histograms,
monotonicity of the observation count).
"""
from __future__ import annotations

import bisect
import threading

__all__ = ["Counter", "Histogram", "counter", "histogram", "observe",
           "counters", "set_gauge", "reset_counters",
           "registry_snapshot", "counter_kinds"]

_registry: "dict[str, Counter]" = {}
_lock = threading.Lock()


class Counter:
    """A named value in the registry. `increment`/`decrement` for counts,
    `set_value` for gauges (latest-value semantics). All mutations are
    atomic under the registry lock."""

    __slots__ = ("name", "domain", "value", "kind")

    def __init__(self, name: str, domain: str = "mxtpu", value=0):
        self.name = name
        self.domain = domain
        self.value = value
        self.kind = "counter"

    @property
    def full_name(self) -> str:
        return f"{self.domain}/{self.name}"

    def increment(self, delta=1):
        with _lock:
            self.value += delta
            return self.value

    def decrement(self, delta=1):
        with _lock:
            self.value -= delta
            return self.value

    def set_value(self, value):
        with _lock:
            self.value = value
            self.kind = "gauge"

    def __repr__(self):
        return f"Counter({self.full_name}={self.value})"


# Default bounds target request latencies in MILLISECONDS: sub-ms eager
# dispatch up through multi-second compiles, ~4 buckets per decade.
DEFAULT_HISTOGRAM_BOUNDS = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
    250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0, 30000.0, 60000.0)


class Histogram:
    """A named latency/size distribution in the registry (kind
    "histogram"): fixed upper bounds, cumulative bucket counts on
    snapshot (the Prometheus `le` convention), plus sum/count/min/max and
    interpolated percentile estimates. `observe()` is one lock acquire,
    same always-on cost contract as `Counter.increment`."""

    __slots__ = ("name", "domain", "kind", "bounds", "_counts",
                 "_sum", "_count", "_min", "_max")

    def __init__(self, name: str, domain: str = "mxtpu", bounds=None):
        self.name = name
        self.domain = domain
        self.kind = "histogram"
        self.bounds = tuple(sorted(bounds or DEFAULT_HISTOGRAM_BOUNDS))
        self._counts = [0] * (len(self.bounds) + 1)   # last = +Inf overflow
        self._sum = 0.0
        self._count = 0
        self._min = None
        self._max = None

    @property
    def full_name(self) -> str:
        return f"{self.domain}/{self.name}"

    def observe(self, value):
        v = float(value)
        with _lock:
            self._counts[bisect.bisect_left(self.bounds, v)] += 1
            self._sum += v
            self._count += 1
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v

    @staticmethod
    def _percentile(counts, bounds, n, mn, mx, q):
        """Linear interpolation inside the bucket holding quantile q
        (0..1), clamped to the observed min/max so estimates never exceed
        the true extremes. Pure function of a copied counts list."""
        if n == 0:
            return None
        target = q * n
        cum = 0
        for i, c in enumerate(counts):
            prev, cum = cum, cum + c
            if cum >= target and c:
                lo = bounds[i - 1] if i > 0 else \
                    (mn if mn is not None else 0.0)
                hi = bounds[i] if i < len(bounds) else \
                    (mx if mx is not None else lo)
                est = lo + (hi - lo) * (target - prev) / c
                if mn is not None:
                    est = max(est, mn)
                if mx is not None:
                    est = min(est, mx)
                return est
        return mx

    @property
    def value(self) -> dict:
        """Exporter-facing snapshot: cumulative buckets keyed by their
        upper bound (Prometheus `le`), totals, and percentile estimates.
        JSON-serializable; `counters()`/flight dumps embed it whole.

        LOCK-FREE by design: registry snapshot functions hold the module
        lock while reading `.value`, and the flight recorder's
        signal-handler path reads it with NO lock — so this must never
        acquire `_lock`. The counts list is copied in one C-level slice
        (GIL-atomic), and count/+Inf derive from that same copy, so the
        snapshot is internally consistent and monotone across reads."""
        counts = list(self._counts)
        mn, mx, total = self._min, self._max, self._sum
        n = 0
        cum = 0
        buckets = {}
        for bound, c in zip(self.bounds, counts):
            cum += c
            buckets[repr(float(bound))] = cum
        n = cum + counts[-1]
        buckets["+Inf"] = n
        return {
            "count": n,
            "sum": total,
            "min": mn,
            "max": mx,
            "buckets": buckets,
            "p50": self._percentile(counts, self.bounds, n, mn, mx, 0.50),
            "p95": self._percentile(counts, self.bounds, n, mn, mx, 0.95),
            "p99": self._percentile(counts, self.bounds, n, mn, mx, 0.99),
        }

    def __repr__(self):
        return f"Histogram({self.full_name}, n={self._count})"


def counter(name: str, domain: str = "mxtpu") -> Counter:
    """Get-or-create the counter `domain/name`."""
    key = f"{domain}/{name}"
    c = _registry.get(key)
    if c is None:
        with _lock:
            c = _registry.setdefault(key, Counter(name, domain))
    if isinstance(c, Histogram):
        # symmetric with histogram()'s guard: fail HERE with the real
        # cause, not later with AttributeError on .increment/.set_value
        raise TypeError(f"{key} is already registered as a histogram")
    return c


def histogram(name: str, domain: str = "mxtpu", bounds=None) -> Histogram:
    """Get-or-create the histogram `domain/name`."""
    key = f"{domain}/{name}"
    h = _registry.get(key)
    if h is None:
        with _lock:
            h = _registry.setdefault(key, Histogram(name, domain, bounds))
    if not isinstance(h, Histogram):
        raise TypeError(f"{key} is already registered as a {h.kind}")
    return h


def observe(name: str, value, domain: str = "mxtpu") -> None:
    """One-shot histogram observation: get-or-create and record."""
    histogram(name, domain).observe(value)


def set_gauge(name: str, value, domain: str = "mxtpu") -> None:
    """One-shot gauge write: get-or-create and set latest value."""
    counter(name, domain).set_value(value)


def counters() -> dict:
    """Snapshot of the registry: {domain/name: value}."""
    with _lock:
        return {k: c.value for k, c in _registry.items()}


def registry_snapshot() -> dict:
    """Consistent snapshot with kinds: {domain/name: (value, kind)} —
    the exporter-facing view (one lock acquire for the whole registry)."""
    with _lock:
        return {k: (c.value, c.kind) for k, c in _registry.items()}


def counter_kinds() -> dict:
    """{domain/name: 'counter'|'gauge'} for every registered metric."""
    with _lock:
        return {k: c.kind for k, c in _registry.items()}


def reset_counters():
    with _lock:
        _registry.clear()


def _counter_events() -> list:
    """Chrome 'C' events for every registered counter (called by dump).
    Histograms surface as numeric series (count + percentiles) since
    chrome://tracing counter tracks only plot numbers."""
    from . import _now_us
    ts = _now_us()
    events = []
    with _lock:
        for c in _registry.values():
            if c.kind == "histogram":
                v = c.value
                args = {"count": v["count"]}
                if v["p50"] is not None:
                    args["p50"] = v["p50"]
                    args["p99"] = v["p99"]
            else:
                args = {"value": c.value}
            events.append({"name": c.full_name, "cat": c.domain, "ph": "C",
                           "pid": 0, "ts": ts, "args": args})
    return events
