"""ModelServer — stdlib HTTP front end over FrozenModel + DynamicBatcher.

Mirrors `diagnostics/export.py`'s server pattern (ThreadingHTTPServer in
a daemon thread, quiet logs, JSON bodies) so the whole serving stack —
like the rest of the observability layer — needs nothing outside the
standard library. The reference analogue is `mxnet-model-server`'s
frontend, collapsed to its essentials:

* ``POST /predict`` — body ``{"data": <nested list>, "timeout_ms": N?}``;
  200 with ``{"output": ..., "batch_size": n, "latency_ms": t}``, or the
  admission error's HTTP code (400 invalid, 429 queue full, 504
  deadline, 503 draining) with ``{"error": ..., "message": ...}``;
* ``GET /healthz`` — a DEEP health check, not an unconditional 200:
  ``{"status": "ok"|"degraded"|"draining", "checks": {...}}`` reporting
  batcher liveness, queue saturation, the age of the last successful
  predict, and the healthmon watchdog status. 200 only while genuinely
  able to serve; 503 when draining, when the dispatcher thread is dead,
  when the queue is saturated, or when requests are queued but no
  predict has completed within ``MXTPU_SERVING_STALL_S`` (default 30) —
  so load balancers stop routing to a wedged replica, not just a
  closing one;
* ``GET /stats`` — serving counters, batch-fill ratio, latency
  percentiles, queue depth, uptime and QPS.

Shutdown is a graceful drain: ``stop()`` flips /healthz to draining,
stops admissions, lets the batcher finish every accepted request, then
closes the listener.

Env knobs: MXTPU_SERVING_HOST / MXTPU_SERVING_PORT,
MXTPU_SERVING_MAX_BATCH, MXTPU_SERVING_MAX_DELAY_MS,
MXTPU_SERVING_QUEUE_LIMIT, MXTPU_SERVING_TIMEOUT_MS (see
docs/serving.md).
"""
from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

from .. import fleetscope as _fs
from .. import healthmon as _healthmon
from .. import profiler as _prof
from .. import resilience as _resilience
from .. import servescope as _ss
from .batcher import DynamicBatcher
from .errors import InvalidInputError, ServerClosedError, ServingError
from .frozen import FrozenModel

__all__ = ["ModelServer"]


def _env_float(name, default):
    from ..settings import env_float
    return float(env_float(name, default))


class ModelServer:
    """Serve a FrozenModel (or freeze a HybridBlock in place) over HTTP.

    ``ModelServer(net, input_shape=(1, 28, 28)).start()`` returns
    ``(host, port)``; port 0 (default) binds a free one.
    """

    def __init__(self, model, input_shape=None, host=None, port=None,
                 max_batch=None, max_delay_ms=None, queue_limit=None,
                 default_timeout_ms=None, batcher=None, **freeze_kwargs):
        if not isinstance(model, FrozenModel):
            if input_shape is None:
                raise ValueError("input_shape is required when passing an "
                                 "unfrozen block")
            model = FrozenModel(model, input_shape, **freeze_kwargs)
        self.model = model
        from ..settings import env_int, env_str
        self.host = host or env_str("MXTPU_SERVING_HOST", "127.0.0.1")
        self.port = env_int("MXTPU_SERVING_PORT", 0, call_site=port)
        # scheduler selection: "dynamic" (coalesce-then-dispatch, the
        # sporadic-traffic default) or "continuous" (iteration-level
        # slots, the fleet/sustained-load path — docs/serving.md)
        self.batcher_kind = env_str("MXTPU_SERVING_BATCHER", "dynamic",
                                    call_site=batcher)
        if self.batcher_kind not in ("dynamic", "continuous"):
            raise ValueError(f"batcher must be 'dynamic' or 'continuous',"
                             f" got {self.batcher_kind!r}")
        self._batcher_settings = {
            "max_batch": max_batch or
            env_int("MXTPU_SERVING_MAX_BATCH", 0) or None,
            "max_delay_ms": max_delay_ms if max_delay_ms is not None
            else _env_float("MXTPU_SERVING_MAX_DELAY_MS", 5.0),
            "queue_limit": queue_limit or
            env_int("MXTPU_SERVING_QUEUE_LIMIT", 256),
            "default_timeout_ms": default_timeout_ms
            if default_timeout_ms is not None
            else _env_float("MXTPU_SERVING_TIMEOUT_MS", 1000.0)}
        self.batcher = self._make_batcher(model)
        self._httpd = None
        self._started_at = None
        self._draining = False

    def _make_batcher(self, model):
        """One batcher of the server's configured kind over `model` —
        shared by construction and `swap_model` so a hot-swapped model
        serves under exactly the same scheduler + knobs."""
        if self.batcher_kind == "continuous":
            from ..fleet.continuous import ContinuousBatcher
            cls = ContinuousBatcher
        else:
            cls = DynamicBatcher
        return cls(model, **self._batcher_settings)

    # -- lifecycle --------------------------------------------------------
    def start(self):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        server = self

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # loopback p99 killer: headers and body leave as separate
            # small segments, and Nagle holds the second until the
            # first is ACKed — which the peer's delayed ACK sits on for
            # ~40 ms. TCP_NODELAY turns that stall into microseconds.
            disable_nagle_algorithm = True

            def _reply(self, code, obj):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                try:
                    if self.path.startswith("/healthz"):
                        code, doc = server.health()
                        self._reply(code, doc)
                    elif self.path.startswith("/stats"):
                        self._reply(200, server.stats())
                    else:
                        self._reply(404, {"error": "NotFound",
                                          "message": self.path})
                except Exception as e:  # noqa: BLE001
                    self._safe_500(e)

            def do_POST(self):
                try:
                    if not self.path.startswith("/predict"):
                        self._reply(404, {"error": "NotFound",
                                          "message": self.path})
                        return
                    length = int(self.headers.get("Content-Length") or 0)
                    try:
                        doc = json.loads(self.rfile.read(length) or b"{}")
                        if not isinstance(doc, dict) or "data" not in doc:
                            raise ValueError("body must be a JSON object "
                                             "with a 'data' key")
                        x = np.asarray(doc["data"],
                                       dtype=server.model.dtype)
                    except (ValueError, TypeError) as e:
                        raise InvalidInputError(str(e)) from e
                    t0 = time.perf_counter()
                    # fleetscope: the upstream hop's W3C trace context
                    # rides the standard header; read only while armed
                    # (off = this one predicate on the request path)
                    tp = (self.headers.get("traceparent")
                          if _fs._FS is not None else None)
                    # swap-safe admission: a hot swap may close the
                    # batcher we read between the read and the submit —
                    # when a NEW batcher has already been published,
                    # resubmit there instead of bouncing the client
                    # (zero dropped requests across a deploy); a real
                    # drain (batcher unchanged) still raises 503
                    for _ in range(8):
                        b = server.batcher
                        try:
                            req = b.submit(
                                x, timeout_ms=doc.get("timeout_ms"),
                                traceparent=tp)
                            break
                        except ServerClosedError:
                            if server.batcher is b:
                                raise
                    else:
                        raise ServerClosedError(
                            "server is swapping models faster than "
                            "requests can be admitted")
                    outs = req.wait(
                        (doc.get("timeout_ms")
                         or b.default_timeout_ms) / 1e3 + 30.0)
                    out = outs[0] if len(outs) == 1 else outs
                    reply = {
                        "output": (out.tolist() if isinstance(out, np.ndarray)
                                   else [o.tolist() for o in out]),
                        "batch_size": req.batch_size,
                        "batch_id": req.batch_id,
                        "batch_index": req.batch_index,
                        "latency_ms": round(
                            (time.perf_counter() - t0) * 1e3, 3)}
                    if req.trace_id is not None:
                        reply["trace_id"] = req.trace_id
                    self._reply(200, reply)
                except ServingError as e:
                    self._reply(e.code, e.to_json())
                except Exception as e:  # noqa: BLE001
                    self._safe_500(e)

            def _safe_500(self, e):
                try:
                    self._reply(500, {"error": type(e).__name__,
                                      "message": str(e)[:500]})
                except Exception:
                    pass

            def log_message(self, *a):   # stay quiet on stderr
                pass

        class _Server(ThreadingHTTPServer):
            # socketserver's default accept backlog is 5 — under a
            # concurrent-client burst the SYN queue overflows and
            # clients pay kernel retransmit timeouts (a measured 1s/3s
            # p99 quantization that has nothing to do with serving).
            # Size it like the admission queue: beyond this the 429
            # backpressure path is the bounded-latency answer.
            request_queue_size = max(128, self.batcher.queue_limit)

        self.batcher.start()
        self._httpd = _Server((self.host, self.port), _Handler)
        self.port = self._httpd.server_address[1]
        t = threading.Thread(target=self._httpd.serve_forever,
                             name="mxtpu-serving-http", daemon=True)
        t.start()
        self._started_at = time.time()
        self._draining = False
        _prof.set_gauge("serving.up", 1, "serving")
        return self.host, self.port

    def swap_model(self, model, input_shape=None, **freeze_kwargs):
        """Zero-downtime model hot-swap (the deploy primitive under
        `fleet.Router.deploy`): build and START the new model's batcher
        first, publish it atomically (`self.batcher` — the request
        handler re-reads it per request, and resubmits there if it
        raced the old one's close), then drain the old batcher so every
        request it had already accepted is served. At no instant is
        there no admitting batcher, so a swap drops zero requests even
        under concurrent load."""
        if not isinstance(model, FrozenModel):
            if input_shape is None:
                raise ValueError("input_shape is required when passing an "
                                 "unfrozen block")
            model = FrozenModel(model, input_shape, **freeze_kwargs)
        new_batcher = self._make_batcher(model).start()
        old = self.batcher
        self.model = model
        self.batcher = new_batcher
        _prof.counter("serving.model_swaps", "serving").increment()
        old.stop(drain=True)
        return model

    def stop(self, drain: bool = True):
        """Graceful shutdown: mark draining (healthz 503), stop
        admissions, finish accepted requests, then close the listener."""
        self._draining = True
        self.batcher.stop(drain=drain)
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        _prof.set_gauge("serving.up", 0, "serving")

    @property
    def address(self):
        return f"http://{self.host}:{self.port}"

    # -- deep health ------------------------------------------------------
    def health(self):
        """(http_code, body) for /healthz — the deep check. Policy:

        * draining → 503 "draining" (the graceful-shutdown signal);
        * dispatcher thread dead → 503 (accepted requests can never
          complete);
        * queue saturated (depth >= limit) → 503 (every new predict
          would be rejected 429 anyway — stop routing here);
        * requests queued but nothing served for MXTPU_SERVING_STALL_S
          → 503 (a wedged executable looks exactly like "slow");
        * otherwise 200, with the same observations reported so
          dashboards see saturation BEFORE it trips the threshold.

        The healthmon watchdog status rides along as a report-only
        section: a training-side stall in a co-hosted process is context
        for the operator, not a reason for the LB to drop this replica.
        """
        now = time.time()
        b = self.batcher
        depth = b.queue_depth
        saturation = depth / b.queue_limit if b.queue_limit else 0.0
        last_ts = b.last_response_ts
        age = (now - last_ts) if last_ts is not None else None
        stall_s = _env_float("MXTPU_SERVING_STALL_S", 30.0)
        checks = {
            "batcher_alive": b.running,
            "queue_depth": depth,
            "queue_limit": b.queue_limit,
            "queue_saturation": round(saturation, 3),
            "last_predict_age_s": (round(age, 3) if age is not None
                                   else None),
        }
        snap = _prof.counters()
        checks["healthmon"] = {
            "enabled": _healthmon.enabled(),
            "stall_alerts": snap.get(
                "healthmon/healthmon.stall_alerts", 0),
            "nan_alerts": snap.get("healthmon/healthmon.nan_alerts", 0),
        }
        # resilience (who ACTS on those verdicts): checkpoint freshness,
        # recovery totals, rollback-in-progress — report-only context
        # like the healthmon block (a co-hosted training run mid-rollback
        # is operator context, not an LB drop reason)
        checks["healthmon"]["resilience"] = _resilience.status()
        # commscope's last resharding verdict per compiled bucket: an
        # accidental all-gather on the serve path is a per-request p99
        # catastrophe (docs/commscope.md). Report-only, like healthmon —
        # a layout verdict is for the operator, not a reason for the LB
        # to drop an otherwise-serving replica — but flagged loudly.
        verdicts = self.model.comm_verdicts()
        if verdicts:
            flagged = sorted(b for b, v in verdicts.items()
                             if v.get("resharding_collectives"))
            checks["resharding"] = {
                "buckets": verdicts,
                "buckets_flagged": flagged,
            }
        # servescope's current p99 attribution: WHAT the tail is, not
        # just how tall (docs/servescope.md)
        brief = _ss.attribution_brief()
        if brief is not None:
            checks["servescope_p99"] = brief
        # memscope's live memory headroom (capacity x target vs current
        # in-use, docs/memscope.md). Report-only, same discipline as the
        # healthmon block: a "tight" verdict is admission/operator
        # context, not a reason for the LB to drop a serving replica.
        try:
            from .. import memscope as _memscope
            if _memscope._MS is not None:
                hs = _memscope.headroom_state()
                checks["memscope"] = {
                    "headroom_fraction": hs.get("headroom_fraction"),
                    "verdict": hs.get("verdict"),
                    "capacity_bytes": hs.get("capacity_bytes"),
                    "in_use_bytes": hs.get("in_use_bytes"),
                    "oom_events": _prof.counters().get(
                        "memscope/memscope.oom_events", 0),
                }
        except Exception:  # noqa: BLE001 — telemetry never breaks /healthz
            pass
        problems = []
        if not b.running:
            problems.append("batcher_dead")
        if depth >= b.queue_limit:
            problems.append("queue_saturated")
        # stalled = work is waiting and nothing has completed recently;
        # the reference point falls back to server start so a server
        # whose FIRST batch wedges is caught too
        progress_ref = max(x for x in (last_ts, b.last_batch_ts,
                                       self._started_at, 0.0)
                           if x is not None)
        if depth > 0 and (now - progress_ref) > stall_s:
            problems.append("predict_stalled")
        if self._draining:
            status = "draining"
        elif problems:
            status = "degraded"
        else:
            status = "ok"
        doc = {"status": status,
               "model": repr(self.model),
               "buckets": list(self.model.buckets),
               "checks": checks}
        if problems:
            doc["problems"] = problems
        return (200 if status == "ok" else 503), doc

    # -- stats ------------------------------------------------------------
    def stats(self) -> dict:
        """One consistent registry snapshot per call: every derived
        number (percentiles, fill, qps) comes from the SINGLE
        ``batcher.stats()`` read — a second read mid-traffic would mix
        epochs (the histogram and the response counter advancing
        between reads). Callers that also want the raw latency
        histogram read it from this same dict
        (``s["serving.latency_ms"]``), never from a fresh snapshot."""
        s = self.batcher.stats()
        uptime = (time.time() - self._started_at) if self._started_at \
            else 0.0
        s["uptime_s"] = round(uptime, 3)
        responses = s.get("serving.responses", 0)
        s["qps"] = round(responses / uptime, 3) if uptime > 0 else 0.0
        s["draining"] = self._draining
        s["buckets"] = list(self.model.buckets)
        s["max_batch"] = self.batcher.max_batch
        s["max_delay_ms"] = self.batcher.max_delay_s * 1e3
        s["queue_limit"] = self.batcher.queue_limit
        s["batcher"] = self.batcher_kind
        verdicts = self.model.comm_verdicts()
        if verdicts:
            s["resharding"] = verdicts
        brief = _ss.attribution_brief()
        if brief is not None:
            s["servescope"] = brief
        return s
