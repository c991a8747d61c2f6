"""ReplicaSet + Router — least-loaded dispatch and draining deploys.

The fleet layer applies the training-side ops discipline (PR 12's
resilience, PR 5's health exchange) to the request path:

* `ReplicaSet` constructs and owns N in-process replicas of one model
  — each its own `ModelServer` on its own port with its own batcher
  (continuous by default: the fleet is the sustained-load path) — and
  threads the shared `CompileCache` through every freeze so replica
  N+1 deserializes executables instead of recompiling them;
* `Router` is the single front door: a stdlib ThreadingHTTPServer that
  forwards ``POST /predict`` to the **least-loaded admitting replica**
  and exposes aggregate ``/healthz`` + ``/stats``. "Least-loaded" is
  scored from healthmon's deep ``/healthz`` — the live outstanding
  count the router itself maintains plus the polled queue depth — with
  a large penalty when the replica's last deep health flagged a
  resharding verdict on any bucket (an accidental all-gather per
  request is a p99 catastrophe; a layout-clean replica always wins);
* **draining deploys**: ``Router.deploy(factory)`` rolls the fleet one
  replica at a time — *drain* (stop routing there, wait for its
  outstanding forwards and queue to reach zero), *swap*
  (`ModelServer.swap_model`, itself zero-downtime), *readmit* (probe,
  then route again). At least one replica serves at every instant and
  no accepted request is ever dropped; each phase lands in the flight
  recorder and ``mxtpu.events/1`` as ``fleet.drain`` /
  ``fleet.swap`` / ``fleet.readmit`` records.

Health polling runs in one daemon thread at ``MXTPU_FLEET_POLL_S``
(default 0.25 s) over the real HTTP wire — the router sees exactly what
an external load balancer would. A replica leaves rotation after
``unhealthy_after`` consecutive poll failures (one dropped poll must
not flap it) and re-enters on the first 200.

Everything is counted in the governed ``fleet`` family
(mxlint/families.py): routed / routed_errors / retries /
no_replica_available, health_polls(+errors), drains / swaps /
readmits, compile-cache traffic, replica gauges, and a
``fleet.forward_ms`` histogram.
"""
from __future__ import annotations

import http.client
import json
import os
import socket
import subprocess
import sys
import threading
import time

from .. import fleetscope as _fs
from .. import profiler as _prof
from ..diagnostics import flight as _flight
from ..healthmon import events as _events
from .replica import Replica

__all__ = ["ReplicaSet", "Router"]


def _c(name):
    return _prof.counter(name, "fleet")


def _event(name, args):
    """Drain/swap/readmit breadcrumbs on both shared surfaces."""
    if _flight._REC is not None:
        _flight.record("fleet", name, args)
    if _events._LOG is not None:
        _events.emit("fleet", name, args=args)


class ReplicaSet:
    """Construct and own N replicas of one model.

    Two modes:

    * **in-process** (default): ``model_factory`` is called once per
      replica as ``model_factory(compile_cache=<the set's cache>)`` and
      must return a `FrozenModel` (build it with ``block.freeze(...,
      compile_cache=compile_cache)``). Every replica shares the
      parent's GIL — right for tests, wrong for throughput.
    * **spawned** (``spawn=True``, or pass a spec dict instead of a
      callable): each replica runs as its own
      ``python -m incubator_mxnet_tpu.fleet.worker`` process — its own
      GIL, real multi-core scaling. The spec is `fleet/worker.py`'s
      JSON contract (model-zoo name + freeze/server arguments; a
      closure cannot cross a process boundary). Replica 0 is spawned
      first so its compile-cache stores land before the rest warm up —
      the shared cache is what lets replica N+1 (and every respawn
      deploy) skip the XLA compiles replica 0 already paid for.
    """

    def __init__(self, model_factory, n=2, name="replica",
                 batcher="continuous", compile_cache=None, host=None,
                 server_kwargs=None, spawn=None):
        if int(n) < 1:
            raise ValueError(f"a fleet needs at least one replica, got {n}")
        if spawn is None:
            spawn = isinstance(model_factory, dict)
        self.spawn = bool(spawn)
        if self.spawn and not isinstance(model_factory, dict):
            raise TypeError("spawn=True needs a worker spec dict, "
                            "not a callable (closures cannot cross a "
                            "process boundary)")
        self.model_factory = model_factory
        self.spec = dict(model_factory) if self.spawn else None
        self.n = int(n)
        self.name = str(name)
        self.batcher = batcher
        if compile_cache is None and not self.spawn:
            from .cache import shared_cache
            compile_cache = shared_cache()
        self.compile_cache = compile_cache
        self.host = host
        self.server_kwargs = dict(server_kwargs or {})
        self.replicas = []

    def _worker_spec(self):
        spec = dict(self.spec)
        spec.setdefault("batcher", self.batcher)
        if self.server_kwargs:
            server = dict(self.server_kwargs)
            server.update(spec.get("server") or {})
            spec["server"] = server
        if self.compile_cache is not None:
            path = getattr(self.compile_cache, "path", self.compile_cache)
            spec.setdefault("cache_dir", str(path))
        if self.host:
            spec.setdefault("host", self.host)
        return spec

    def _check_workers_can_have_devices(self):
        """A spawned worker opens the accelerator for itself, and a TPU
        chip belongs to one process at a time — the first process takes
        every local chip. So on a TPU host at most ONE spawned worker can
        ever become ready, and none while this process holds the chips:
        say so now, instead of hanging on a ready line. Asked of a child
        process, so that this parent stays off the device its workers
        need. In-process replicas (`spawn=False`) are the form for one
        chip, and for several: one process can drive them all."""
        from .. import context
        if os.environ.get("JAX_PLATFORMS") == "cpu":
            return                      # CPU workers share nothing
        refusal = ("spawned fleet workers each need the accelerator to "
                   "themselves, and a TPU chip belongs to one process at "
                   "a time: {why}. Use in-process replicas (spawn=False)")
        if context.holds_accelerator():
            raise RuntimeError(refusal.format(
                why="this process already holds the chips"))
        platform, kind, count = context.devices_seen_by_a_child()
        if platform == "tpu" and self.n > 1:
            raise RuntimeError(refusal.format(
                why=f"{self.n} workers on a host where the first takes "
                    f"all {count} chip(s) ({kind})"))

    def _spawn_one(self, name, timeout=600.0):
        """Spawn one worker process and block on its readiness
        handshake (model freeze + warmup happen before the ready line,
        so a returned replica is immediately servable)."""
        import select
        from .worker import READY_TAG
        spec = self._worker_spec()
        # the package must be importable from the child no matter how
        # the parent put it on sys.path
        env = dict(os.environ)
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "incubator_mxnet_tpu.fleet.worker",
             "--spec", json.dumps(spec)],
            stdout=subprocess.PIPE, env=env, text=True)
        deadline = time.time() + timeout
        while time.time() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"fleet worker {name} exited rc={proc.returncode} "
                    f"before becoming ready")
            ready, _, _ = select.select([proc.stdout], [], [], 1.0)
            if not ready:
                continue
            line = proc.stdout.readline()
            if not line:
                continue
            if not line.startswith(READY_TAG):
                continue
            fields = dict(tok.split("=", 1) for tok in line.split()
                          if "=" in tok)
            rep = Replica(name, proc=proc, host=fields.get("host"),
                          port=int(fields.get("port", 0)),
                          diag_port=(int(fields["diag_port"])
                                     if "diag_port" in fields else None))
            rep.cache_stats = {
                k: int(fields.get(f"cache_{k}", 0))
                for k in ("hits", "misses", "stores")}
            return rep
        proc.kill()
        raise RuntimeError(f"fleet worker {name} not ready after "
                           f"{timeout:.0f}s")

    def start(self):
        """Freeze + start every replica; returns the replica list."""
        if self.spawn:
            self._check_workers_can_have_devices()
            # replica 0 alone first: its cache stores must land before
            # the rest warm up, or every replica pays the compile
            self.replicas.append(self._spawn_one(f"{self.name}0"))
            rest = list(range(1, self.n))
            results = {}

            def spawn_into(i):
                results[i] = self._spawn_one(f"{self.name}{i}")

            threads = [threading.Thread(target=spawn_into, args=(i,),
                                        daemon=True) for i in rest]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            missing = [i for i in rest if i not in results]
            if missing:
                self.stop(drain=False)
                raise RuntimeError(f"fleet workers {missing} failed to "
                                   f"spawn")
            self.replicas.extend(results[i] for i in rest)
        else:
            from ..serving.server import ModelServer
            for i in range(self.n):
                model = self.model_factory(
                    compile_cache=self.compile_cache)
                srv = ModelServer(model, host=self.host,
                                  batcher=self.batcher,
                                  **self.server_kwargs)
                srv.start()
                self.replicas.append(Replica(f"{self.name}{i}", srv))
        _prof.set_gauge("fleet.replicas", len(self.replicas), "fleet")
        return self.replicas

    def respawn(self, rep, spec=None):
        """Replace a spawned replica's worker process (the deploy
        primitive: replicas are cattle). Blue-green per replica: the
        fresh worker warms from the shared cache FIRST, then the old
        process is retired — the replica object keeps its fleet
        identity (name, health history slots) but points at the new
        process. The caller (Router.deploy) drains `rep` first."""
        if rep.proc is None:
            raise ValueError(f"{rep.name} is in-process — use "
                             f"ModelServer.swap_model, not respawn")
        if spec is not None:
            self.spec = dict(spec)
        fresh = self._spawn_one(rep.name)
        old = rep.proc
        rep.proc = fresh.proc
        rep._host, rep._port = fresh._host, fresh._port
        rep.diag_port = fresh.diag_port
        rep.cache_stats = fresh.cache_stats
        rep.last_health, rep.health_code = None, None
        rep.consecutive_failures = 0
        old.terminate()
        try:
            old.wait(timeout=30)
        except subprocess.TimeoutExpired:
            old.kill()
        return rep

    def stop(self, drain=True):
        for rep in self.replicas:
            if rep.server is not None:
                rep.server.stop(drain=drain)
            elif rep.proc is not None:
                # SIGTERM -> worker drains its batcher, then exits
                rep.proc.terminate()
        for rep in self.replicas:
            if rep.proc is not None:
                try:
                    rep.proc.wait(timeout=30 if drain else 10)
                except subprocess.TimeoutExpired:
                    rep.proc.kill()
        _prof.set_gauge("fleet.replicas", 0, "fleet")
        _prof.set_gauge("fleet.replicas_healthy", 0, "fleet")


class Router:
    """Least-loaded HTTP front door over a list of `Replica`s."""

    def __init__(self, replicas, host="127.0.0.1", port=0,
                 poll_interval_s=None, forward_retries=1,
                 unhealthy_after=2):
        self._rset = replicas if isinstance(replicas, ReplicaSet) else None
        if isinstance(replicas, ReplicaSet):
            replicas = replicas.replicas
        self.replicas = list(replicas)
        self.host = host
        self.port = int(port)
        from ..settings import env_float
        self.poll_interval_s = float(
            env_float("MXTPU_FLEET_POLL_S", 0.25,
                      call_site=poll_interval_s))
        self.forward_timeout_s = float(
            env_float("MXTPU_FLEET_FORWARD_TIMEOUT_S", 60.0))
        self.forward_retries = int(forward_retries)
        self.unhealthy_after = int(unhealthy_after)
        self._lock = threading.Lock()
        self._rr = 0                      # round-robin tie-break cursor
        self._local = threading.local()   # keep-alive conns per thread
        self._stop_evt = threading.Event()
        self._poller = None
        self._httpd = None
        self._started_at = None
        self.dispatch_counts = {r.name: 0 for r in self.replicas}

    # -- health polling ---------------------------------------------------
    def _poll_once(self):
        healthy = 0
        for rep in self.replicas:
            try:
                rep.probe(timeout=2.0)
                _c("fleet.health_polls").increment()
            except Exception:  # noqa: BLE001 — a dead replica must not
                _c("fleet.health_poll_errors").increment()   # kill polling
                rep.consecutive_failures += 1
                if rep.consecutive_failures >= self.unhealthy_after:
                    rep.healthy = False
            if rep.healthy:
                healthy += 1
        _prof.set_gauge("fleet.replicas_healthy", healthy, "fleet")

    def _poll_loop(self):
        while not self._stop_evt.wait(self.poll_interval_s):
            self._poll_once()

    # -- dispatch ---------------------------------------------------------
    def _pick(self):
        """The least-loaded admitting replica (score from the deep
        health snapshot + live outstanding count; round-robin among
        ties), or None when nothing is routable."""
        with self._lock:
            cands = [(i, r) for i, r in enumerate(self.replicas)
                     if r.healthy and not r.draining]
            if not cands:
                return None
            n = len(self.replicas)
            rr = self._rr
            self._rr = rr + 1
            best = min(cands,
                       key=lambda ir: (ir[1].load_score(),
                                       (ir[0] - rr) % n))[1]
            best.outstanding += 1
            return best

    def _release(self, rep):
        with self._lock:
            rep.outstanding = max(0, rep.outstanding - 1)

    def _forward(self, rep, body, traceparent=None):
        """One forward on this thread's keep-alive connection to `rep`;
        a stale kept-alive socket gets ONE fresh-connection retry, any
        other failure propagates to the caller's failover loop. The
        optional ``traceparent`` is the router's OWN span context — the
        replica's servescope span becomes its child."""
        conns = getattr(self._local, "conns", None)
        if conns is None:
            conns = self._local.conns = {}
        headers = {"Content-Type": "application/json"}
        if traceparent is not None:
            headers["traceparent"] = traceparent
        for attempt in (0, 1):
            conn = conns.get(rep.name)
            if conn is None:
                conn = http.client.HTTPConnection(
                    rep.host, rep.port, timeout=self.forward_timeout_s)
                conn.connect()
                # same delayed-ACK stall as the serving handler: the
                # forwarded reply is a small write behind a small write
                conn.sock.setsockopt(socket.IPPROTO_TCP,
                                     socket.TCP_NODELAY, 1)
                conns[rep.name] = conn
            try:
                conn.request("POST", "/predict", body=body,
                             headers=headers)
                resp = conn.getresponse()
                return resp.status, resp.read()
            except Exception:
                try:
                    conn.close()
                except Exception:  # noqa: BLE001
                    pass
                conns.pop(rep.name, None)
                if attempt:
                    raise
        raise RuntimeError("unreachable")

    def handle_predict(self, body, traceparent=None):
        """Route one /predict body; returns ``(status, reply_dict)``.
        Tries up to ``forward_retries + 1`` distinct replicas before
        giving up — a replica that fails mid-forward is failed over,
        not surfaced to the client.

        When fleetscope is armed the router is the ROOT hop: it accepts
        the client's ``traceparent`` (or mints a fresh trace — a
        malformed header is counted and re-minted, never guessed) and
        forwards its own child span to the replica, so one request is
        one trace across processes."""
        fs = _fs._FS
        rctx = None
        if fs is not None:
            # upstream view (the client's span, or a synthesized
            # client-edge root when the header is absent/malformed);
            # the router's own span is always its child
            rctx = fs.accept(traceparent).child()
        t_start = time.perf_counter()
        tried = set()
        for attempt in range(self.forward_retries + 1):
            rep = self._pick()
            if rep is None or rep.name in tried:
                if rep is not None:
                    self._release(rep)
                break
            tried.add(rep.name)
            t0 = time.perf_counter()
            try:
                status, raw = self._forward(
                    rep, body,
                    rctx.header() if rctx is not None else None)
            except Exception:  # noqa: BLE001 — transport failure: fail over
                _c("fleet.routed_errors").increment()
                rep.consecutive_failures += 1
                if rep.consecutive_failures >= self.unhealthy_after:
                    rep.healthy = False
                continue
            finally:
                self._release(rep)
            forward_ms = (time.perf_counter() - t0) * 1e3
            _c("fleet.routed").increment()
            _prof.observe("fleet.forward_ms", forward_ms, "fleet")
            with self._lock:
                self.dispatch_counts[rep.name] = \
                    self.dispatch_counts.get(rep.name, 0) + 1
            try:
                doc = json.loads(raw or b"{}")
                if isinstance(doc, dict):
                    doc["replica"] = rep.name
            except ValueError:
                doc = {"error": "BadReplicaResponse",
                       "message": "replica returned non-JSON",
                       "replica": rep.name}
                status = 502
            if rctx is not None:
                if isinstance(doc, dict):
                    doc.setdefault("trace_id", rctx.trace_id)
                self._trace_event(rctx, rep.name, status, forward_ms,
                                  (time.perf_counter() - t_start) * 1e3)
            return status, doc
        _c("fleet.no_replica_available").increment()
        if rctx is not None:
            # the trace still records the failed route: an unjoined
            # router-side record is a datum the join rate must count
            self._trace_event(rctx, None, 503, None,
                              (time.perf_counter() - t_start) * 1e3)
        return 503, {"error": "NoReplicaAvailable",
                     "message": "no healthy admitting replica"}

    @staticmethod
    def _trace_event(rctx, replica, status, forward_ms, e2e_ms):
        """The router side of the cross-process join: one
        ``fleetscope.request`` record per routed request, carrying the
        router span + the two router-clock walls the wire-gap math
        needs (forward wall vs replica-reported e2e is a difference of
        perf_counter durations — clock-skew free)."""
        args = {"trace_id": rctx.trace_id, "span_id": rctx.span_id,
                "parent_id": rctx.parent_id, "replica": replica,
                "status": status, "e2e_ms": round(e2e_ms, 3)}
        if forward_ms is not None:
            args["forward_ms"] = round(forward_ms, 3)
        if _flight._REC is not None:
            _flight.record("fleetscope", "fleetscope.request", dict(args))
        if _events._LOG is not None:
            _events.emit("fleetscope", "fleetscope.request", args=args)

    # -- aggregate surfaces ----------------------------------------------
    def health(self):
        """(code, body): 200 while at least one replica is admitting."""
        rows = [r.snapshot() for r in self.replicas]
        admitting = sum(1 for r in rows
                        if r["healthy"] and not r["draining"])
        status = "ok" if admitting else "degraded"
        return (200 if admitting else 503), {
            "status": status, "role": "router",
            "replicas": rows, "admitting": admitting}

    def stats(self) -> dict:
        """Router counters + per-replica rows + dispatch balance."""
        snap = {k.split("/", 1)[1]: v for k, v in _prof.counters().items()
                if k.startswith("fleet/")}
        with self._lock:
            counts = dict(self.dispatch_counts)
        rows = [r.snapshot() for r in self.replicas]
        for row in rows:
            row["dispatched"] = counts.get(row["name"], 0)
        vals = list(counts.values())
        mean = (sum(vals) / len(vals)) if vals else 0.0
        snap["dispatch_counts"] = counts
        snap["dispatch_imbalance"] = (max(vals) / mean
                                      if vals and mean > 0 else 0.0)
        snap["replicas"] = rows
        if self._started_at:
            snap["uptime_s"] = round(time.time() - self._started_at, 3)
        return snap

    # -- draining deploys -------------------------------------------------
    def drain(self, rep, timeout=30.0) -> bool:
        """Stop routing to `rep`, then wait until its outstanding
        forwards AND its batcher queue are empty. Returns False on
        timeout (the replica is left draining — readmit explicitly)."""
        with self._lock:
            rep.draining = True
        _c("fleet.drains").increment()
        _event("fleet.drain", {"replica": rep.name})
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            with self._lock:
                outstanding = rep.outstanding
            if outstanding == 0 and rep.live_queue_depth() == 0:
                return True
            time.sleep(0.01)
        return False

    def readmit(self, rep):
        """Probe, then route to `rep` again."""
        try:
            rep.probe(timeout=2.0)
        except Exception:  # noqa: BLE001 — the poller will retry
            pass
        with self._lock:
            rep.draining = False
        _c("fleet.readmits").increment()
        _event("fleet.readmit", {"replica": rep.name,
                                 "healthy": rep.healthy})

    def deploy(self, model_factory, compile_cache=None, timeout=60.0):
        """Rolling drain → swap → readmit across the fleet: at least
        one replica admits at every instant and no accepted request is
        dropped. For in-process replicas,
        ``model_factory(compile_cache=...)`` is called once per replica
        (same contract as `ReplicaSet`) and the model is hot-swapped
        via ``ModelServer.swap_model``; for spawned replicas, pass the
        new worker **spec dict** — the deploy is a rolling respawn
        (the fresh process warms from the shared cache before the old
        one is retired)."""
        for rep in self.replicas:
            self.drain(rep, timeout=timeout)
            if rep.server is not None:
                model = model_factory(compile_cache=compile_cache)
                rep.server.swap_model(model)
                desc = repr(model)
            else:
                if self._rset is None:
                    raise RuntimeError("deploying spawned replicas "
                                       "needs the owning ReplicaSet "
                                       "(construct Router with it)")
                spec = model_factory if isinstance(model_factory, dict) \
                    else None
                self._rset.respawn(rep, spec)
                desc = f"respawn pid={rep.proc.pid}"
            _c("fleet.swaps").increment()
            _event("fleet.swap", {"replica": rep.name, "model": desc})
            self.readmit(rep)

    # -- lifecycle --------------------------------------------------------
    def start(self):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        router = self

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # see serving/server.py: without TCP_NODELAY the reply's
            # header+body writes hit Nagle vs delayed-ACK (~40 ms/req)
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
                        code, doc = router.health()
                        self._reply(code, doc)
                    elif self.path.startswith("/stats"):
                        self._reply(200, router.stats())
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
                    body = self.rfile.read(length)
                    tp = (self.headers.get("traceparent")
                          if _fs._FS is not None else None)
                    code, doc = router.handle_predict(body, traceparent=tp)
                    self._reply(code, doc)
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
            # same SYN-backlog sizing rationale as ModelServer: the
            # router fronts EVERY replica's clients at once
            request_queue_size = 256

        # routing needs health data before the first request arrives
        self._poll_once()
        self._stop_evt.clear()
        self._poller = threading.Thread(target=self._poll_loop,
                                        name="mxtpu-fleet-health",
                                        daemon=True)
        self._poller.start()
        self._httpd = _Server((self.host, self.port), _Handler)
        self.port = self._httpd.server_address[1]
        t = threading.Thread(target=self._httpd.serve_forever,
                             name="mxtpu-fleet-router", daemon=True)
        t.start()
        self._started_at = time.time()
        _event("fleet.router_start",
               {"replicas": len(self.replicas),
                "address": f"{self.host}:{self.port}"})
        return self.host, self.port

    def stop(self):
        _event("fleet.router_stop",
               {"routed": int(_c("fleet.routed").value)})
        self._stop_evt.set()
        if self._poller is not None:
            self._poller.join(5.0)
            self._poller = None
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None

    @property
    def address(self):
        return f"http://{self.host}:{self.port}"
