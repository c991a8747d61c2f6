"""The program's account of its own set-up, and jax's compile phases.

One listener on ``jax.monitoring``, registered when the profiler is
imported (the package's only compile listener), takes jax's time spans of
the three phases a program passes before it first runs, each with the
jitted function's name:

* trace: ``/jax/core/compile/jaxpr_trace_duration``;
* lower: ``/jax/core/compile/jaxpr_to_mlir_module_duration``;
* load: ``/jax/core/compile/backend_compile_duration`` (the cache key and
  the persistent cache's read and deserialisation, or a fresh compile on
  a miss);

and the persistent cache's ``cache_retrieval_time_sec``, ``cache_hits``
and ``cache_misses``. It keeps two views in ``profiler.counters()``:

* lifetime counters, domain ``compile``: ``requests`` (loads),
  ``seconds`` (their seconds), ``cache_hits``, ``cache_misses``;
* the set-up account, gauges of domain ``setup``: the fused step's
  program (``train_step``; ``scan_fn`` for ``run_k``) in ``step_trace_s``,
  ``step_lower_s``, ``step_load_s`` and ``step_cache_read_s`` (the part of
  its load that read the cache), every other program in
  ``other_programs_s`` and ``other_programs`` (its loads); and the self
  time of the set-up spans (:class:`span`): ``init_s`` (with
  ``init_leaves`` and ``init_bytes``, the parameters made), ``shapes_s``,
  ``states_s``, ``build_s``.

Seconds are a partition of set-up's wall time: an interval inside another
(a jit traced inside the step's trace, an initializer compiled inside
``mxtpu.setup.init``) counts once, for the outer compile interval or, in a
span, apart from the span's own time. The account closes itself at the end
of the first dispatch of a fused step that compiled nothing
(:func:`dispatch_began` / :func:`dispatch_ended`): ``setup/closed`` is
then 1 and no gauge of the domain moves again, whatever the process
compiles or initialises later. docs/profiler.md, "Set-up's spans and
counters".
"""
from __future__ import annotations

import threading
import time
import weakref

import jax

from . import Scope
from .counters import counter, set_gauge

__all__ = ["span", "made", "dispatch_began", "dispatch_ended", "close",
           "restart", "STEP_PROGRAMS", "GAUGES"]

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
LOAD = "/jax/core/compile/backend_compile_duration"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_misses"}

# the jitted functions of parallel/trainer_step.py's FusedTrainStep
STEP_PROGRAMS = frozenset({"train_step", "scan_fn"})
_STEP_GAUGE = {TRACE: "step_trace_s", LOWER: "step_lower_s",
               LOAD: "step_load_s"}
GAUGES = ("init_s", "init_leaves", "init_bytes", "shapes_s", "states_s",
          "build_s", "step_trace_s", "step_lower_s", "step_load_s",
          "step_cache_read_s", "other_programs_s", "other_programs",
          "closed")

closed = False      # FusedTrainStep reads this once per dispatch
requests = 0        # loads since the process started
_totals = dict.fromkeys(GAUGES, 0)
_generation = 0     # spans and containers of an earlier account are ignored
_lock = threading.Lock()
_tls = threading.local()
_dispatched = weakref.WeakSet()     # jitted functions called in this account


class _Interval:
    """A closed interval of set-up and what it counted for its gauge;
    `children` are the intervals closed inside a span while it was open."""

    __slots__ = ("start", "end", "gauge", "counted", "children", "generation")

    def __init__(self, start, gauge, children=()):
        self.start = start
        self.end = start
        self.gauge = gauge
        self.counted = 0.0
        self.children = children
        self.generation = _generation


def _add(gauge, value):
    total = _totals[gauge] + value
    _totals[gauge] = total
    set_gauge(gauge, total, "setup")


def _retract(item):
    """An outer compile interval took `item` in: it no longer counts."""
    _add(item.gauge, -item.counted)
    for child in item.children:
        _retract(child)


def _stack():
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _container():
    """The innermost open span of this account in this thread, else the
    thread's top level."""
    for item in reversed(_stack()):
        if item is not None and item.generation == _generation:
            return item
    root = getattr(_tls, "root", None)
    if root is None or root.generation != _generation:
        root = _tls.root = _Interval(0.0, None, [])
    return root


def _place(item, start):
    """Put a closed interval into its container: what closed inside it
    before it (it started later) is retracted, and an overlap with the
    interval before it is cut off, so that no second is counted twice."""
    siblings = _container().children
    while siblings and siblings[-1].start >= start:
        _retract(siblings.pop())
    if siblings:
        start = max(start, siblings[-1].end)
    siblings.append(item)
    return start


# -- the listener ---------------------------------------------------------

def _on_time_span(event, start, end, fun_name="", **_):
    global requests
    if event not in _STEP_GAUGE:
        return
    if event == LOAD:
        counter("requests", "compile").increment()
        counter("seconds", "compile").increment(end - start)
        read, _tls.read = getattr(_tls, "read", 0.0), 0.0
    name = fun_name
    while name.endswith(")") and "(" in name:     # jit(train_step)
        name = name[name.index("(") + 1:-1]
    step = name in STEP_PROGRAMS
    gauge = _STEP_GAUGE[event] if step else "other_programs_s"
    with _lock:
        if event == LOAD:
            requests += 1
        if closed:
            return
        item = _Interval(start, gauge)
        item.start = _place(item, start)
        item.end = max(end, item.start)
        item.counted = item.end - item.start
        _add(gauge, item.counted)
        if event == LOAD:
            if step:
                _add("step_cache_read_s", read)
            else:
                _add("other_programs", 1)


def _on_duration(event, seconds, **_):
    if event == CACHE_READ:
        # inside the load it belongs to, which reports when it ends
        _tls.read = getattr(_tls, "read", 0.0) + seconds


def _on_event(event, **_):
    name = CACHE_EVENTS.get(event)
    if name is not None:
        counter(name, "compile").increment()


jax.monitoring.register_event_time_span_listener(_on_time_span)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


# -- spans ----------------------------------------------------------------

class span:
    """``with span("mxtpu.setup.init", "init_s"):`` a region of set-up: a
    ``profiler.Scope(name, "setup", sync=False)`` (on a TPU a
    TraceAnnotation, on the device trace's clock), and its self time (its
    duration less its child spans and the compile intervals inside it) in
    the gauge. `outermost`: inside a span of the same gauge it is no span
    at all (initialisers that call each other)."""

    __slots__ = ("name", "gauge", "outermost", "_scope")

    def __init__(self, name, gauge, outermost=False):
        self.name = name
        self.gauge = gauge
        self.outermost = outermost

    def __enter__(self):
        stack = _stack()
        if self.outermost and any(item is not None and item.gauge == self.gauge
                                  for item in stack):
            stack.append(None)
            return self
        self._scope = Scope(self.name, "setup", sync=False)
        self._scope.__enter__()
        stack.append(_Interval(time.time(), self.gauge, []))
        return self

    def __exit__(self, *exc):
        item = _stack().pop()
        if item is None:
            return False
        item.end = time.time()
        self._scope.__exit__(*exc)
        if closed or item.generation != _generation:
            return False
        with _lock:
            inner = sum(c.end - c.start for c in item.children)
            item.counted = max(0.0, item.end - item.start - inner)
            _place(item, item.start)
            _add(item.gauge, item.counted)
        return False


def made(raw):
    """A parameter's array was made (init_leaves, init_bytes)."""
    if not closed:
        with _lock:
            _add("init_leaves", 1)
            _add("init_bytes", raw.size * raw.dtype.itemsize)


# -- the fused step's dispatch ----------------------------------------------

def dispatch_began(jitted):
    """Before a fused step's call of `jitted` while the account is open:
    the first call of each jitted function is the span `mxtpu.setup.build`
    (it traces, lowers and loads the program). Returns what
    :func:`dispatch_ended` takes."""
    build = None
    if jitted not in _dispatched:
        _dispatched.add(jitted)
        build = span("mxtpu.setup.build", "build_s").__enter__()
    return requests, build


def dispatch_ended(began):
    """After the call: the account closes if the call loaded nothing."""
    before, build = began
    if build is not None:
        build.__exit__(None, None, None)
    if requests == before:
        close()


def close():
    """End set-up: publish every gauge with ``closed`` 1; nothing of the
    domain moves after this."""
    global closed, _generation
    with _lock:
        if closed:
            return
        closed = True
        _generation += 1
        _tls.root = None
        _totals["closed"] = 1
        for gauge, value in _totals.items():
            set_gauge(gauge, value, "setup")


def restart():
    """Open a fresh account from zero: what the process builds next is
    set-up again (a second model trained from scratch in one process)."""
    global closed, _generation
    with _lock:
        closed = False
        _generation += 1
        _tls.root = None
        _dispatched.clear()
        for gauge in _totals:
            _totals[gauge] = 0
            set_gauge(gauge, 0, "setup")


restart()
