"""mxtpu.profiler — TPU-native profiling & metrics subsystem.

Parity surface: python/mxnet/profiler.py (`set_config` / `set_state` /
`start` / `stop` / `pause` / `resume` / `dump` / `dumps`), emitting
Chrome-trace-event JSON loadable in chrome://tracing / Perfetto, plus an
aggregate-stats backend (per-op count/total/min/max — the reference
`profiler.dumps()` table) and a counters/gauges registry (see
``profiler.counters``) that every telemetry layer publishes into.

Three event sources feed one recorder:

* **imperative ops** — a hook on the ndarray ``_apply`` funnel times each
  eager op, synchronizing on the outputs so durations are device-compute
  times, not dispatch times (``profile_imperative``);
* **layer scopes** — the hot layers (autograd tape, host engine,
  gluon.Trainer phases, KVStore collectives, HybridBlock jit cache,
  symbolize) open :class:`Scope` regions around their work. Each hook is
  a single module-flag predicate (``profiler._ACTIVE``) when profiling is
  off — no dict lookups, no string formatting, no allocation;
* **user scopes** — ``with profiler.Scope("region"):`` (alias
  ``record_function``) times arbitrary regions; user scopes synchronize
  the device on exit by default so the number is wall-true.

TPU bridge: when the default backend is TPU (see
:mod:`incubator_mxnet_tpu.profiler.tpu`), every scope additionally enters
``jax.profiler.TraceAnnotation``, running profiler or not, so host-side
regions line up with the XLA device trace of any jax trace session, and ``set_config(profile_xla=True)`` drives
``jax.profiler.start_trace`` for a full TensorBoard/Perfetto capture.

Off-path contract: when profiling is disabled the ndarray funnel checks
one module-global (``_op_hook is None``) and every layer hook checks one
module-global bool — verified by the <5% microloop-overhead assertion in
``tests/test_profiler.py``.
"""
from __future__ import annotations

import json
import threading
import time

from .counters import (Counter, Histogram, counter, histogram, observe,
                       counters, reset_counters, set_gauge,
                       registry_snapshot, counter_kinds, _counter_events)
from . import tpu as _tpu

__all__ = ["set_config", "set_state", "start", "stop", "pause", "resume",
           "dump", "dumps", "reset", "aggregate_stats", "Scope", "scope",
           "record_function", "Counter", "Histogram", "counter",
           "histogram", "observe", "counters", "set_gauge",
           "reset_counters", "registry_snapshot", "counter_kinds",
           "device_memory_stats"]

# --------------------------------------------------------------------------
# State. `_ACTIVE` is THE fast-path predicate: hot layers guard their
# instrumentation with `if _prof._ACTIVE:` and nothing else. It is True
# exactly while profiling is running and not paused.
# --------------------------------------------------------------------------
_ACTIVE = False
_RUNNING = False
# True only while a profile_xla device trace WE started is running —
# set_state must never stop a trace owned by someone else (a
# devicescope capture window holds the one-per-process jax trace)
_xla_trace_owned = False

_config = {
    "filename": "profile.json",
    "aggregate_stats": True,
    # reference set_config knobs — profile_all turns everything on
    "profile_all": False,
    "profile_imperative": True,   # eager op timing via the _apply hook
    "profile_api": True,          # engine / kvstore / trainer scopes
    "profile_symbolic": True,     # symbolize / jit cache events
    "profile_memory": False,      # attach device memory stats to dump()
    "continuous_dump": False,     # accepted for parity; dump() is explicit
    "dump_period": 1.0,           # accepted for parity
    # XLA device trace (TensorBoard/Perfetto), beyond the reference surface
    "profile_xla": False,
    "xla_logdir": "/tmp/mxtpu_xla_trace",
}

_records: list[dict] = []            # chrome trace events (X phase)
_agg: dict[str, list] = {}           # name -> [count, total_us, min_us, max_us]
_lock = threading.Lock()             # guards _agg merges from engine threads
_t0 = time.perf_counter()
_tls = threading.local()             # per-thread scope nesting depth


def _now_us() -> float:
    return (time.perf_counter() - _t0) * 1e6


def _emit(name: str, cat: str, ts_us: float, dur_us: float, args=None):
    """Record one complete ('X') event and fold it into the aggregate."""
    ev = {"name": name, "cat": cat, "ph": "X", "pid": 0,
          "tid": threading.get_ident() & 0xFFFF, "ts": ts_us, "dur": dur_us}
    if args:
        ev["args"] = args
    _records.append(ev)
    if _config["aggregate_stats"]:
        with _lock:
            ent = _agg.get(name)
            if ent is None:
                _agg[name] = [1, dur_us, dur_us, dur_us]
            else:
                ent[0] += 1
                ent[1] += dur_us
                if dur_us < ent[2]:
                    ent[2] = dur_us
                if dur_us > ent[3]:
                    ent[3] = dur_us


def _instant(name: str, cat: str, args=None):
    """Record an instant ('i') event — used for cache hit/miss marks."""
    ev = {"name": name, "cat": cat, "ph": "i", "pid": 0,
          "tid": threading.get_ident() & 0xFFFF, "ts": _now_us(), "s": "t"}
    if args:
        ev["args"] = args
    _records.append(ev)


# --------------------------------------------------------------------------
# Configuration / lifecycle
# --------------------------------------------------------------------------

def set_config(**kwargs):
    """set_config(profile_all=..., filename=..., aggregate_stats=..., ...).

    Accepts the reference kwargs; unknown ones are ignored (everything here
    runs through the same eager/jit funnel, so e.g. ``profile_process`` has
    no distinct meaning). ``profile_all=True`` enables every source."""
    for k, v in kwargs.items():
        if k in _config:
            _config[k] = v


def _imperative_on() -> bool:
    return _config["profile_all"] or _config["profile_imperative"]


def _install_hooks(on: bool):
    from .. import ndarray as _nd
    _nd._op_hook = _op_hook if (on and _imperative_on()) else None


def set_state(state: str = "stop"):
    """'run' starts collection, 'stop' ends it. Idempotent."""
    assert state in ("run", "stop")
    global _RUNNING, _ACTIVE
    was_running = _RUNNING
    _RUNNING = state == "run"
    _ACTIVE = _RUNNING
    _install_hooks(_RUNNING)
    if _config["profile_xla"] and was_running != _RUNNING:
        global _xla_trace_owned
        if _RUNNING:
            # jax allows ONE trace per process: if a devicescope
            # capture window (or anyone else) is already tracing,
            # start returns False and this session must NOT stop the
            # trace it failed to start — stopping would kill the
            # window's capture mid-flight while it still counts steps
            _xla_trace_owned = _tpu.start_device_trace(
                _config["xla_logdir"])
        elif _xla_trace_owned:
            _tpu.stop_device_trace()
            _xla_trace_owned = False


def start():
    """Parity: profiler.start() — begin collecting."""
    set_state("run")


def stop():
    """Parity: profiler.stop() — end collecting (does not clear records)."""
    set_state("stop")


def pause():
    """Suspend collection without tearing down the run (parity: pause)."""
    global _ACTIVE
    if _RUNNING:
        _ACTIVE = False
        _install_hooks(False)


def resume():
    global _ACTIVE
    if _RUNNING:
        _ACTIVE = True
        _install_hooks(True)


def reset():
    """Clear recorded events and aggregate stats (not the counters)."""
    _records.clear()
    with _lock:
        _agg.clear()


# --------------------------------------------------------------------------
# Imperative op hook (installed on ndarray._op_hook while active)
# --------------------------------------------------------------------------

def _op_hook(fn, raws, name):
    import jax
    if any(isinstance(r, jax.core.Tracer) for r in raws):
        # inside a jit/eval_shape trace of a hybridized block: not a device
        # execution, don't record (times would be Python tracing time)
        return fn(*raws)
    start_t = time.perf_counter()
    outs = fn(*raws)
    jax.block_until_ready(outs)
    dur = time.perf_counter() - start_t
    _emit(name or getattr(fn, "__name__", "op"), "operator",
          (start_t - _t0) * 1e6, dur * 1e6)
    return outs


# --------------------------------------------------------------------------
# Scopes
# --------------------------------------------------------------------------

class Scope:
    """Context manager timing a named region (reference: profiler scopes /
    frame markers; torch alias: ``record_function``).

    ``sync=True`` (the default for user code) drains device work on exit so
    the duration is wall-true; internal layer hooks pass ``sync=False`` to
    avoid perturbing the async pipeline. Records nothing when profiling is
    off or paused, so scopes can stay in production loops: all that is
    left then is the TPU bridge's TraceAnnotation, which a jax trace
    session picks up and which otherwise costs under a microsecond.
    ``step_num`` makes that annotation a StepTraceAnnotation."""

    __slots__ = ("name", "cat", "sync", "step_num", "_start", "_active",
                 "_depth", "_ann")

    def __init__(self, name: str = "<unk>", cat: str = "scope",
                 sync: bool = True, step_num: int | None = None):
        self.name = name
        self.cat = cat
        self.sync = sync
        self.step_num = step_num
        self._active = False
        self._ann = None

    def __enter__(self):
        self._active = _ACTIVE
        if self._active:
            self._depth = getattr(_tls, "depth", 0)
            _tls.depth = self._depth + 1
        self._ann = _tpu.annotation(self.name, self.step_num)
        if self._ann is not None:
            self._ann.__enter__()
        if self._active:
            self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._active:
            if self.sync:
                from .. import ndarray as _nd
                _nd.waitall()
            dur = time.perf_counter() - self._start
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        if self._active:
            _tls.depth = self._depth
            _emit(self.name, self.cat, (self._start - _t0) * 1e6, dur * 1e6,
                  args={"depth": self._depth})
            self._active = False
        return False


# aliases: `with profiler.scope("x"):` (old mxtpu surface) and
# `with profiler.record_function("x"):` (torch-style, per the issue)
scope = Scope
record_function = Scope


# --------------------------------------------------------------------------
# Dump / aggregate backends
# --------------------------------------------------------------------------

def dump(finished: bool = True, filename: str | None = None) -> str:
    """Write the Chrome trace-event JSON to `filename` (default: the
    configured one). Returns the path written."""
    path = filename or _config["filename"]
    events = [{"name": "process_name", "ph": "M", "pid": 0,
               "args": {"name": "mxtpu"}}]
    events.extend(_records)
    events.extend(_counter_events())
    payload = {"traceEvents": events, "displayTimeUnit": "ms"}
    if _config["profile_memory"] or _config["profile_all"]:
        try:
            payload["deviceMemory"] = device_memory_stats()
        except Exception:
            pass
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


def aggregate_stats() -> dict:
    """Per-name aggregate: {name: {count, total_us, min_us, max_us,
    avg_us}} — the machine-readable form of `dumps()`."""
    with _lock:
        return {name: {"count": c, "total_us": tot, "min_us": mn,
                       "max_us": mx, "avg_us": tot / c}
                for name, (c, tot, mn, mx) in _agg.items()}


def dumps(reset: bool = False) -> str:
    """Aggregate-stats table (reference `profiler.dumps()` format)."""
    with _lock:
        items = sorted(_agg.items(), key=lambda kv: -kv[1][1])
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Min(us)':>12}"
                 f"{'Avg(us)':>12}{'Max(us)':>12}"]
        for name, (c, tot, mn, mx) in items:
            lines.append(f"{name[:39]:<40}{c:>8}{tot / 1e3:>12.3f}"
                         f"{mn:>12.1f}{tot / c:>12.1f}{mx:>12.1f}")
    out = "\n".join(lines)
    if reset:
        globals()["reset"]()
    return out


# normalized key -> spellings observed across jaxlib versions/backends
# (the memscope watermark ring and mxdiag consume the normalized names)
_MEMSTATS_KEYS = {
    "bytes_in_use": ("bytes_in_use",),
    "peak_bytes_in_use": ("peak_bytes_in_use", "max_bytes_in_use"),
    "bytes_limit": ("bytes_limit", "bytes_reservable_limit"),
    "largest_alloc_size": ("largest_alloc_size", "largest_allocation"),
}


def device_memory_stats(device=None):
    """XLA allocator counters for a device (bytes_in_use, peak_bytes_in_use,
    ...), key spellings normalized across jaxlib versions, plus
    ``"available": True``. Reference analogue: gpu memory profile /
    storage stats.

    Backends whose devices lack ``memory_stats()`` or return None for
    it (XLA:CPU) degrade to a counted ``{"available": False}`` instead
    of raising — every consumer (memscope's watermark ring, the dump
    payload) branches on the one flag rather than on exceptions."""
    try:
        if device is None:
            import jax
            device = jax.local_devices()[0]
        fn = getattr(device, "memory_stats", None)
        stats = fn() if callable(fn) else None
    except Exception:  # noqa: BLE001 — backend-dependent surface
        stats = None
    if not stats:
        try:
            from .counters import counter as _ctr
            _ctr("memscope.stats_unavailable", "memscope").increment()
        except Exception:  # noqa: BLE001
            pass
        return {"available": False}
    out = dict(stats)
    for norm, spellings in _MEMSTATS_KEYS.items():
        if norm in out:
            continue
        for s in spellings:
            if s in stats:
                out[norm] = stats[s]
                break
    out["available"] = True
    return out


# set-up's account and the package's one compile listener (build.py); last,
# since its spans are Scopes
from . import build  # noqa: E402
