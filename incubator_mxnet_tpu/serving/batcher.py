"""DynamicBatcher — request coalescing under a latency/size policy.

The Clipper/ORCA dynamic-batching pattern rebuilt over FrozenModel's
bucketed executables: single-sample requests enter a bounded thread-safe
queue; one dispatcher thread coalesces whatever is waiting into the
smallest compiled bucket that fits, bounded by

* ``max_batch``    — never batch more than this many requests, and
* ``max_delay_ms`` — never hold the FIRST request of a batch longer than
  this before dispatching (the tail-latency knob).

Admission control is explicit and total — a request is never silently
dropped:

* **validation** at submit: shape/dtype mismatch and
  larger-than-largest-bucket inputs raise :class:`InvalidInputError`
  immediately (client error, nothing enqueued);
* **backpressure** at submit: a full queue raises
  :class:`QueueFullError` (fail-fast, the Clipper deadline-aware
  shedding move) instead of stacking unbounded latency;
* **deadlines**: each request carries `enqueue time + timeout`; the
  dispatcher rejects expired requests with
  :class:`DeadlineExceededError` *before* spending device time on them,
  and the waiting client is woken with that error;
* **drain**: ``stop(drain=True)`` stops admissions
  (:class:`ServerClosedError`) but completes every request already
  accepted before the dispatcher exits.

Telemetry (always-on, through ``profiler.counters`` so the diagnostics
sampler/flight recorder see serving traffic for free): request/response/
reject counters, batch count + coalesced-size counter (their ratio is
the batch-fill), a queue-depth gauge, and `serving.latency_ms` /
`serving.batch_exec_ms` histograms.
"""
from __future__ import annotations

import collections
import threading
import time

import numpy as np

from .. import fleetscope as _fs
from .. import profiler as _prof
from .. import servescope as _ss
from ..diagnostics import flight as _flight
from ..healthmon import events as _events
from .errors import (DeadlineExceededError, QueueFullError,
                     ServerClosedError)

__all__ = ["DynamicBatcher", "Request"]


def _c(name):
    return _prof.counter(name, "serving")


class Request:
    """One in-flight prediction: the dispatcher fulfils it (result or
    error) and sets the event; the submitting thread blocks in `wait`."""

    __slots__ = ("x", "enqueued_at", "deadline", "batch_size",
                 "batch_id", "batch_index", "span", "trace_id",
                 "_event", "_result", "_error")

    def __init__(self, x, timeout_ms):
        self.x = x
        self.enqueued_at = time.perf_counter()
        self.deadline = (self.enqueued_at + timeout_ms / 1e3
                         if timeout_ms else None)
        self.batch_size = None          # size of the batch that served us
        self.batch_id = None            # dispatch sequence number
        self.batch_index = None         # our row within that batch
        self.span = None                # servescope lifecycle span (sampled)
        self.trace_id = None            # fleetscope context (reply echo)
        self._event = threading.Event()
        self._result = None
        self._error = None

    def _fulfil(self, result=None, error=None):
        self._result = result
        self._error = error
        self._event.set()

    @property
    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout=None):
        """Block until served; returns the per-output list of np arrays
        (batch dim stripped) or raises the rejection error."""
        if not self._event.wait(timeout):
            raise DeadlineExceededError(
                "request not served within the client wait timeout")
        if self._error is not None:
            raise self._error
        return self._result


class DynamicBatcher:
    def __init__(self, model, max_batch=None, max_delay_ms=5.0,
                 queue_limit=256, default_timeout_ms=1000.0):
        self.model = model
        self.max_batch = int(max_batch or model.max_batch)
        if self.max_batch > model.max_batch:
            raise ValueError(
                f"max_batch={self.max_batch} exceeds the largest compiled "
                f"bucket {model.max_batch}")
        self.max_delay_s = float(max_delay_ms) / 1e3
        self.queue_limit = int(queue_limit)
        self.default_timeout_ms = float(default_timeout_ms)
        self._q = collections.deque()
        self._cond = threading.Condition()
        self._closed = False           # no new admissions
        self._stopped = False          # dispatcher must exit (after drain)
        self._thread = None
        self._dispatch_seq = 0         # only the dispatcher increments
        # liveness breadcrumbs for the deep /healthz: when did a predict
        # last succeed, and when did the dispatcher last attempt a batch
        self.last_response_ts = None   # wall time of last fulfilled batch
        self.last_batch_ts = None      # wall time of last dispatch attempt

    # -- lifecycle --------------------------------------------------------
    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return self
        self._closed = False
        self._stopped = False
        self._thread = threading.Thread(target=self._run,
                                        name="mxtpu-serving-batcher",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, drain: bool = True, timeout: float = 30.0):
        """Stop admissions; with `drain` (default) the dispatcher serves
        everything already queued before exiting, otherwise queued
        requests are rejected with ServerClosedError (still not silently
        dropped)."""
        with self._cond:
            self._closed = True
            if not drain:
                self._flush_closed_locked()
            self._stopped = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
        # drain backstop: with a dead / never-started dispatcher (or a
        # join that timed out) there is nobody left to serve what is
        # still queued — without this flush those clients hang in
        # req.wait() until their wait timeout. Every flushed request
        # gets a settled rejected_closed span, same as a reject at
        # submit.
        with self._cond:
            self._flush_closed_locked()
        _prof.set_gauge("serving.queue_depth", 0, "serving")

    def _flush_closed_locked(self):
        """Reject everything still queued after close (caller holds
        ``self._cond``): counter + settled span + ServerClosedError to
        the waiting client — the same taxonomy a reject-at-submit gets,
        so a drained-away request is never distinguishable from one
        that was turned away at the door."""
        now = time.perf_counter()
        while self._q:
            req = self._q.popleft()
            _c("serving.rejected_closed").increment()
            if req.span is not None:
                _ss.spans.reject(req.span, "rejected_closed", now)
            req._fulfil(error=ServerClosedError(
                "server stopped before this request was served"))
        _prof.set_gauge("serving.queue_depth", 0, "serving")

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    @property
    def queue_depth(self) -> int:
        return len(self._q)        # len(deque) is GIL-atomic; no lock

    # -- admission --------------------------------------------------------
    def submit(self, x, timeout_ms=None, traceparent=None) -> Request:
        """Enqueue one SINGLE-SAMPLE request (shape = model.input_shape,
        or (1,) + input_shape). Raises instead of queueing when invalid,
        closed, or over capacity.

        ``traceparent`` is an optional W3C trace-context header from the
        upstream hop (router or client); when fleetscope is armed the
        request's servescope span joins that trace (same trace_id, fresh
        span_id, parent = the upstream span). A replica never mints a
        root here — an absent header just means an untraced request."""
        x = np.asarray(x)
        if x.ndim == len(self.model.input_shape) + 1 and x.shape[0] == 1:
            x = x[0]
        _c("serving.requests").increment()
        try:
            self.model.validate(x)     # InvalidInputError on mismatch
        except Exception:
            _c("serving.rejected_invalid").increment()
            raise
        req = Request(np.ascontiguousarray(x),
                      self.default_timeout_ms if timeout_ms is None
                      else timeout_ms)
        ss = _ss._SS    # snapshot: disable() must not race the two reads
        if ss is not None:
            # sampled lifecycle span: admitted at the enqueue timestamp
            req.span = _ss.spans.begin(req.enqueued_at, ss.sample_every)
        fs = _fs._FS    # same snapshot discipline as servescope above
        if fs is not None and traceparent is not None:
            ctx = fs.accept(traceparent, mint_on_missing=False)
            if ctx is not None:
                req.trace_id = ctx.trace_id
                fs.c_propagated.increment()
                if req.span is not None:
                    req.span.trace_id = ctx.trace_id
                    req.span.parent_id = ctx.span_id
                    req.span.span_id = _fs.context.mint_span_id()
        with self._cond:
            if self._closed:
                _c("serving.rejected_closed").increment()
                if req.span is not None:
                    _ss.spans.reject(req.span, "rejected_closed",
                                     time.perf_counter())
                raise ServerClosedError("server is draining; not "
                                        "accepting new requests")
            if len(self._q) >= self.queue_limit:
                _c("serving.rejected_queue_full").increment()
                if req.span is not None:
                    _ss.spans.reject(req.span, "rejected_queue_full",
                                     time.perf_counter())
                raise QueueFullError(
                    f"request queue at capacity ({self.queue_limit})")
            self._q.append(req)
            self._on_admit(req)
            _prof.set_gauge("serving.queue_depth", len(self._q), "serving")
            self._cond.notify()
        return req

    def _on_admit(self, req):
        """Admission hook, called under ``self._cond`` right after the
        request lands in the queue. The base batcher does nothing; the
        continuous batcher stamps mid-flight admissions here."""

    def predict(self, x, timeout_ms=None):
        """Blocking submit-and-wait convenience."""
        req = self.submit(x, timeout_ms=timeout_ms)
        # the dispatcher enforces the queue deadline; the extra margin
        # here only guards against a dead dispatcher thread
        wait_s = ((timeout_ms or self.default_timeout_ms) / 1e3) + 30.0
        return req.wait(wait_s)

    # -- dispatch loop ----------------------------------------------------
    def _gather(self):
        """Wait for the first request, then coalesce until max_batch or
        the first request has waited max_delay. Returns [] at shutdown."""
        with self._cond:
            while not self._q:
                if self._stopped:
                    return []
                self._cond.wait(0.05)
            # servescope boundary between queue_wait and coalesce_delay:
            # from here on the dispatcher is assembling THIS batch —
            # any further waiting is the deliberate coalescing window,
            # not dispatcher backlog
            gather_start = time.perf_counter()
            first = self._q[0]
            dispatch_at = first.enqueued_at + self.max_delay_s
            while len(self._q) < self.max_batch:
                remaining = dispatch_at - time.perf_counter()
                if remaining <= 0 or self._stopped:
                    break
                self._cond.wait(remaining)
            batch = []
            while self._q and len(batch) < self.max_batch:
                batch.append(self._q.popleft())
            _prof.set_gauge("serving.queue_depth", len(self._q), "serving")
            if _ss._SS is not None:
                for req in batch:
                    if req.span is not None:
                        _ss.spans.mark_gather(req.span, gather_start)
            return batch

    def _run(self):
        while True:
            batch = self._gather()
            if not batch:
                with self._cond:
                    if self._stopped and not self._q:
                        return
                continue
            self._serve(batch)

    def _serve(self, batch):
        now = time.perf_counter()
        live = []
        for req in batch:
            if req.deadline is not None and now > req.deadline:
                if req.span is not None:
                    _ss.spans.reject(req.span, "rejected_deadline", now)
                req._fulfil(error=DeadlineExceededError(
                    f"deadline exceeded after "
                    f"{(now - req.enqueued_at) * 1e3:.1f} ms in queue"))
                _c("serving.rejected_deadline").increment()
            else:
                live.append(req)
        if not live:
            return
        self.last_batch_ts = time.time()
        bid = self._dispatch_seq
        self._dispatch_seq = bid + 1
        n = len(live)
        ss = _ss._SS    # snapshot: disable() mid-batch must not race
        spanned = (ss is not None
                   and any(r.span is not None for r in live))
        try:
            bucket = self.model.bucket_for(n)
            x = np.stack([r.x for r in live])
            timings = {} if spanned else None
            t0 = time.perf_counter()
            outs = self.model.predict_batch(x, timings=timings)
            t_done = time.perf_counter()
            exec_ms = (t_done - t0) * 1e3
        except Exception as e:  # noqa: BLE001 — a bad batch must not kill
            if spanned:         # the dispatcher; reject and keep serving
                terr = time.perf_counter()
                for req in live:
                    if req.span is not None:
                        _ss.spans.reject(req.span, "batch_error", terr)
            for req in live:
                req._fulfil(error=e if isinstance(e, Exception) else
                            RuntimeError(str(e)))
            _c("serving.batch_errors").increment()
            return
        if spanned:
            for req in live:
                if req.span is not None:
                    _ss.spans.mark_batch(req.span, bid, bucket, n,
                                         t0, t_done, timings)
        # a devicescope capture window over serving dispatches: one mark
        # per executed batch (predict_batch converts outputs to host
        # arrays, so the dispatch is already synced — no barrier needed)
        try:
            from .. import devicescope as _ds
            if _ds._DS is not None:
                win = _ds.active_window()
                if win is not None:
                    win.step(1, dispatch_ms=exec_ms, workload="serving")
        except Exception:  # noqa: BLE001 — measurement never breaks serving
            pass
        _c("serving.batches").increment()
        _c("serving.batched_requests").increment(n)
        _prof.observe("serving.batch_exec_ms", exec_ms, "serving")
        _prof.observe("serving.batch_size", float(n), "serving")
        bargs = {"n": n, "bucket": bucket, "batch_id": bid,
                 "exec_ms": round(exec_ms, 3)}
        if _fs._FS is not None:
            # member trace ids: which cross-process traces this coalesced
            # dispatch served (bounded — a batch never exceeds the largest
            # compiled bucket, but cap anyway so the record stays small)
            traces = [r.trace_id for r in live
                      if r.trace_id is not None][:64]
            if traces:
                bargs["traces"] = traces
        if _flight._REC is not None:
            _flight.record("serving", "serving.batch", dict(bargs))
        if _events._LOG is not None:
            _events.emit("serving", "serving.batch", args=bargs)
        self.last_response_ts = time.time()
        done = time.perf_counter()
        # a deadline that expired DURING batch execution is a rejection,
        # not a success: the deadline is the client's stated SLA, and a
        # result produced after it is past-deadline work — fulfilling it
        # as a 200 would hide exactly the tail the deadline exists to
        # bound (waiters do linger past the deadline, so they receive a
        # crisp DeadlineExceededError, not a silently late success).
        # Counted under its own name — these were lost entirely before
        # (neither a response nor any rejection counter).
        responded, late = [], []
        for i, req in enumerate(live):
            req.batch_size = n
            req.batch_id = bid
            req.batch_index = i
            if req.deadline is not None and done > req.deadline:
                late.append(req)
            else:
                responded.append((i, req))
        # telemetry BEFORE fulfil: a /stats (or load-harness snapshot) taken the
        # instant a client's predict() returns must already contain that
        # request — observing after _fulfil let percentiles/responses mix
        # epochs mid-read (the waiting client races the counter updates)
        for _, req in responded:
            _prof.observe("serving.latency_ms",
                          (done - req.enqueued_at) * 1e3, "serving")
        if responded:
            _c("serving.responses").increment(len(responded))
        if late:
            _c("serving.rejected_deadline_post_batch").increment(len(late))
        if spanned:
            for i, req in responded:
                if req.span is not None:
                    comp = _ss.spans.finish(req.span, done, batch_index=i)
                    ss.budget.observe(req.span, comp)
            for req in late:
                if req.span is not None:
                    _ss.spans.reject(req.span,
                                     "rejected_deadline_post_batch", done)
        for _, req in responded:
            req._fulfil(result=[o[req.batch_index] for o in outs])
        for req in late:
            req._fulfil(error=DeadlineExceededError(
                f"deadline exceeded during batch execution "
                f"({exec_ms:.1f} ms in bucket {bucket})"))

    # -- stats ------------------------------------------------------------
    @staticmethod
    def stats() -> dict:
        """Serving-domain counters + derived headline numbers (shared by
        /stats and tools/serve_load.py)."""
        snap = {k.split("/", 1)[1]: v
                for k, v in _prof.counters().items()
                if k.startswith("serving/")}
        batches = snap.get("serving.batches", 0)
        coalesced = snap.get("serving.batched_requests", 0)
        snap["batch_fill"] = (coalesced / batches) if batches else 0.0
        lat = snap.get("serving.latency_ms")
        if isinstance(lat, dict):
            snap["p50_ms"] = lat.get("p50")
            snap["p95_ms"] = lat.get("p95")
            snap["p99_ms"] = lat.get("p99")
        return snap
