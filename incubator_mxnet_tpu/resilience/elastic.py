"""Elastic rank membership over the rank-0 TCP wire — the
leave/join half of mxtpu.resilience (docs/resilience.md).

A fixed-world collective stack dies with its first preempted host: the
allgather blocks forever, the stall watchdog writes the obituary. This
module gives the run a MEMBERSHIP layer in front of its state exchange,
riding the same transport discipline as the dist_async parameter server
(kvstore/async_ps.py): rank 0 hosts a tiny TCP coordinator
(length-prefixed pickled frames — the existing wire's framing helpers
are imported, not reimplemented), the jax coordination KV (when a
cluster is formed) or an explicit address is used ONLY for rendezvous,
and every data-plane message is one request/response round trip.

The contract:

* **sync is the heartbeat** — members call :meth:`ElasticGroup.sync`
  once per step with their flat state/gradient vector; the coordinator
  holds each round open until every CURRENT member contributes or the
  round deadline passes.
* **leave = eviction at the deadline** — a member that missed the
  deadline (SIGKILLed, preempted, wedged) is evicted: the generation
  bumps, the round completes over the SURVIVORS, and every survivor
  sees ``membership_changed`` in its sync response — its cue to roll
  back to the last good checkpoint (so the survivors restart the step
  from identical state) and keep training at the smaller world size
  instead of dying.
* **join = admission at the checkpoint boundary** — a (re)joining rank
  polls :meth:`join`; it stays ``pending`` until the group reports its
  next completed checkpoint (:meth:`report_checkpoint`), then is
  admitted with the generation, the checkpoint path to restore from,
  and the step at which to start contributing. Mid-step admission is
  impossible by construction — a joiner can only enter with last-good
  state, which only exists at a checkpoint boundary.

The coordinator (rank 0) is the membership authority, exactly as the
ps-lite scheduler was; rank 0's own calls short-circuit in-process.
Telemetry: ``resilience.rank_departures`` / ``resilience.rank_joins``
counters on every member that observes the change, plus
``resilience.rank_departed`` / ``resilience.rank_joined`` events.

Fleetscope rides this wire for TRAINING runs (serving uses the
collector's HTTP pull instead): members push bounded telemetry
snapshots with :meth:`ElasticGroup.report_telemetry` — the coordinator
cannot initiate a connection to a member on this wire, so collection is
member-push — and each reply carries the coordinator's wall clock, from
which the member estimates its clock offset (NTP midpoint, ± rtt/2)
and includes it in its NEXT report. Rank 0 keeps per-rank bounded
rings; :meth:`pod_telemetry` returns the merged view.
"""
from __future__ import annotations

import pickle
import socket
import threading
import time

import numpy as np

from ..kvstore.async_ps import _recv_frame, _send_frame
from ..profiler.counters import counter as _counter
from .checkpoint import _breadcrumb, _emit

__all__ = ["ElasticGroup", "GroupClosed"]

_KV_KEY = "mxtpu_elastic/addr"


class GroupClosed(RuntimeError):
    """The coordinator is gone (rank 0 died or left) — process-level
    restart territory, not membership-level recovery."""


class ElasticGroup:
    """One rank's handle on the elastic membership group.

        g = ElasticGroup(rank=r, addr=addr)       # rank 0 hosts
        info = g.join()                           # admit (or wait)
        ...
        mean, info = g.sync(step, flat_vec)
        if info["membership_changed"]:
            ...roll back to last good, continue at new world size...
        g.report_checkpoint(step, path)           # admits pending joiners
        g.leave()

    addr: ``(host, port)`` of the coordinator. Rank 0 passes the port it
    wants (or 0 for ephemeral) via ``port=``; non-zero ranks pass
    ``addr=`` explicitly, or leave it None to read the coordination KV
    (a formed jax cluster) or ``MXTPU_ELASTIC_ADDR`` (``host:port``).
    sync_timeout_s: round deadline after which missing members are
    evicted (``MXTPU_ELASTIC_SYNC_TIMEOUT``, default 10).
    startup_grace_s: a member that has NEVER contributed (still
    compiling/restoring after join) cannot be evicted until this much
    time passed since its join (``MXTPU_ELASTIC_STARTUP_GRACE``,
    default 60) — first-round compile skew must not read as death."""

    def __init__(self, rank, addr=None, port=0, sync_timeout_s=None,
                 host="127.0.0.1", startup_grace_s=None):
        self.rank = int(rank)
        from ..settings import env_float
        self.sync_timeout_s = float(env_float(
            "MXTPU_ELASTIC_SYNC_TIMEOUT", 10.0,
            call_site=sync_timeout_s))
        self.startup_grace_s = float(env_float(
            "MXTPU_ELASTIC_STARTUP_GRACE", 60.0,
            call_site=startup_grace_s))
        self._gen_seen = 0
        self._c_departures = _counter("resilience.rank_departures",
                                      "resilience")
        self._c_joins = _counter("resilience.rank_joins", "resilience")
        self._closed = False
        # fleetscope clock alignment: offset of the COORDINATOR's wall
        # clock relative to ours, refreshed by every telemetry report
        self._telem_offset = None
        self._telem_bound = None
        if self.rank == 0:
            self._listener = socket.socket(socket.AF_INET,
                                           socket.SOCK_STREAM)
            self._listener.setsockopt(socket.SOL_SOCKET,
                                      socket.SO_REUSEADDR, 1)
            self._listener.bind((host, int(port)))
            self._listener.listen(64)
            self._listener.settimeout(0.2)
            self.addr = self._listener.getsockname()
            self._co = _Coordinator(self.sync_timeout_s,
                                    self.startup_grace_s)
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._serve, daemon=True,
                name="mxtpu-elastic-coordinator")
            self._thread.start()
            self._publish_addr()
        else:
            self.addr = self._resolve_addr(addr)
            self._co = None

    # -- rendezvous -------------------------------------------------------
    def _publish_addr(self):
        try:
            from jax._src import distributed as _jd
            c = _jd.global_state.client
            if c is not None:
                c.key_value_set_bytes(_KV_KEY, pickle.dumps(self.addr),
                                      allow_overwrite=True)
        except Exception:   # noqa: BLE001 — KV rendezvous is optional
            pass

    @staticmethod
    def _resolve_addr(addr):
        if addr is not None:
            return tuple(addr) if not isinstance(addr, str) else \
                (addr.rsplit(":", 1)[0], int(addr.rsplit(":", 1)[1]))
        from ..settings import env_str
        env = env_str("MXTPU_ELASTIC_ADDR")
        if env:
            host, port = env.rsplit(":", 1)
            return (host, int(port))
        try:
            from jax._src import distributed as _jd
            c = _jd.global_state.client
            if c is not None:
                return tuple(pickle.loads(
                    c.blocking_key_value_get_bytes(_KV_KEY, 60_000)))
        except Exception:   # noqa: BLE001
            pass
        raise ValueError("ElasticGroup needs addr= (or MXTPU_ELASTIC_ADDR,"
                         " or a formed jax cluster's coordination KV)")

    # -- member surface ---------------------------------------------------
    def join(self, poll_s=0.2, timeout_s=120.0):
        """Register with the group. Admission is immediate while the
        group has not started stepping; afterwards it waits for the next
        checkpoint boundary. Returns {generation, members, next_step,
        last_good} and records the join."""
        deadline = time.monotonic() + timeout_s
        while True:
            resp = self._call("join", self.rank)
            if resp["admitted"]:
                self._gen_seen = resp["generation"]
                info = {"rank": self.rank,
                        "generation": resp["generation"],
                        "members": resp["members"],
                        "next_step": resp["next_step"]}
                self._c_joins.increment()
                _breadcrumb("rank_joined", info)
                _emit("resilience", "resilience.rank_joined",
                      step=resp.get("next_step"), args=info)
                return resp
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"rank {self.rank}: join not admitted within "
                    f"{timeout_s}s (no checkpoint boundary reached?)")
            time.sleep(poll_s)

    def sync(self, step, vec):
        """Contribute this rank's flat float32 vector for `step` and
        block for the round mean over the CURRENT members. Returns
        ``(mean, info)``; ``info["membership_changed"]`` is True when the
        generation moved since this rank last looked — departures are in
        ``info["departed"]`` (roll back to ``info["last_good"]``),
        joiners in ``info["joined"]``."""
        vec = np.asarray(vec, np.float32)
        # the coordinator may legitimately hold a round open past the
        # eviction deadline while a just-admitted joiner is still inside
        # its startup grace (compiling/restoring) — the socket timeout
        # must outlast the longest such hold, or every healthy survivor
        # would misread the wait as a dead coordinator
        resp = self._call("sync", self.rank, self._gen_seen, int(step),
                          vec, timeout=(self.sync_timeout_s
                                        + self.startup_grace_s + 30.0))
        changed = resp["generation"] != self._gen_seen
        self._gen_seen = resp["generation"]
        info = {"generation": resp["generation"],
                "members": resp["members"],
                "membership_changed": changed,
                "departed": resp.get("departed", []),
                "left": resp.get("left", []),
                "joined": resp.get("joined", []),
                "last_good": resp.get("last_good")}
        if changed:
            if info["left"]:
                args = {"rank": self.rank, "left": info["left"],
                        "generation": info["generation"],
                        "members": info["members"]}
                _breadcrumb("rank_left", args)
                _emit("resilience", "resilience.rank_left",
                      step=int(step), args=args)
            if info["departed"]:
                self._c_departures.increment(len(info["departed"]))
                args = {"rank": self.rank, "departed": info["departed"],
                        "generation": info["generation"],
                        "members": info["members"]}
                _breadcrumb("rank_departed", args)
                _emit("resilience", "resilience.rank_departed",
                      step=int(step), args=args)
            if info["joined"]:
                args = {"rank": self.rank, "joined": info["joined"],
                        "generation": info["generation"],
                        "members": info["members"]}
                _breadcrumb("rank_joined", args)
                _emit("resilience", "resilience.rank_joined",
                      step=int(step), args=args)
        return resp["mean"], info

    def report_checkpoint(self, step, path):
        """Tell the coordinator a good checkpoint exists at `path` for
        `step` — the admission boundary for pending joiners."""
        return self._call("ckpt", self.rank, int(step), str(path))

    def members(self):
        return self._call("info")["members"]

    # -- fleetscope telemetry (member-push over the membership wire) ------
    def report_telemetry(self, counters=None, events_tail=None,
                         health=None):
        """Push one bounded telemetry snapshot to the coordinator and
        refresh this rank's clock-offset estimate from the reply's
        coordinator wall clock (NTP midpoint, error ≤ rtt/2). The
        offset rides along on the NEXT report so rank 0's merged view
        is clock-aligned without a second protocol. Never raises: a
        failed push is a counted ``fleetscope.telem_errors`` datum.
        Returns ``{"offset_s", "offset_bound_s"}`` or None."""
        from ..fleetscope.collector import estimate_offset
        payload = {"ts": time.time(), "mono": time.monotonic(),
                   "counters": counters, "events_tail": events_tail,
                   "health": health,
                   "offset_s": self._telem_offset,
                   "offset_bound_s": self._telem_bound}
        t_send = time.time()
        try:
            resp = self._call("telem", self.rank, payload)
        except Exception:   # noqa: BLE001 — telemetry never breaks a run
            _counter("fleetscope.telem_errors", "fleetscope").increment()
            return None
        t_recv = time.time()
        co_ts = resp.get("coordinator_ts")
        if isinstance(co_ts, (int, float)):
            self._telem_offset, self._telem_bound = estimate_offset(
                t_send, t_recv, float(co_ts))
        _counter("fleetscope.telem_reports", "fleetscope").increment()
        return {"offset_s": self._telem_offset,
                "offset_bound_s": self._telem_bound}

    def pod_telemetry(self):
        """The coordinator's per-rank telemetry rings: {rank: [reports,
        oldest first]} plus the per-rank clock offsets it last saw —
        the ``mxdiag.py pod`` input for training runs."""
        return self._call("telem_snap")

    def leave(self):
        """Graceful drain: this rank is removed without waiting out a
        round deadline, and survivors re-form WITHOUT rolling back (a
        drained rank completed its rounds — nothing was lost mid-step,
        unlike an eviction). Rank 0 leaving closes the whole group."""
        if self._closed:
            return
        self._closed = True
        if self.rank == 0:
            self._stop.set()
            self._thread.join(timeout=5)
            try:
                self._listener.close()
            except Exception:   # noqa: BLE001
                pass
        else:
            try:
                self._call("leave", self.rank)
            except Exception:   # noqa: BLE001 — leaving a dead group is
                pass            # already the goal

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.leave()
        return False

    # -- transport --------------------------------------------------------
    def _call(self, op, *args, timeout=30.0):
        if self.rank == 0:
            return self._co.handle(op, args)
        try:
            with socket.create_connection(self.addr,
                                          timeout=timeout) as s:
                _send_frame(s, (op,) + args)
                kind, payload = _recv_frame(s)
        except (OSError, ConnectionError) as e:
            raise GroupClosed(f"elastic coordinator unreachable: "
                              f"{type(e).__name__}: {e}") from e
        if kind == "err":
            raise RuntimeError(f"elastic coordinator: {payload}")
        return payload

    def _serve(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except socket.timeout:
                continue
            except Exception:
                if self._stop.is_set():
                    break
                time.sleep(0.05)
                continue
            threading.Thread(target=self._handle_conn, args=(conn,),
                             daemon=True).start()

    def _handle_conn(self, conn):
        try:
            with conn:
                msg = _recv_frame(conn)
                op, args = msg[0], tuple(msg[1:])
                try:
                    reply = ("ok", self._co.handle(op, args))
                except Exception as e:   # noqa: BLE001 — one bad request
                    reply = ("err", f"{type(e).__name__}: {e}")
                _send_frame(conn, reply)
        except Exception:
            pass                  # a dropped member must not kill rank 0


class _Coordinator:
    """Rank-0 membership + round state. Thread-safe; every op goes
    through :meth:`handle` (called from connection handler threads and
    rank 0's own in-process calls alike)."""

    def __init__(self, sync_timeout_s, startup_grace_s=60.0):
        self.sync_timeout_s = float(sync_timeout_s)
        self.startup_grace_s = float(startup_grace_s)
        self._joined_at = {}     # rank -> monotonic join time
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._gen = 1
        self._members = set()
        self._pending = set()
        self._rounds = {}        # step -> {rank: vec}
        self._active_from = {}   # rank -> first step it must sync at
        self._last_contrib = {}  # rank -> newest round it contributed to
        self._departed_log = []  # [(gen, [ranks])] — EVICTIONS only
        self._left_log = []      # [(gen, [ranks])] — graceful drains
        self._joined_log = []
        self._last_good = None   # (step, path)
        self._max_step = 0
        self._started = False
        # fleetscope: bounded per-rank telemetry rings (member-push)
        self._telem = {}         # rank -> deque of reports

    def handle(self, op, args):
        if op == "join":
            return self._join(int(args[0]))
        if op == "sync":
            rank, gen_seen, step, vec = args
            return self._sync(int(rank), int(gen_seen), int(step),
                              np.asarray(vec, np.float32))
        if op == "ckpt":
            rank, step, path = args
            return self._ckpt(int(step), str(path))
        if op == "leave":
            return self._leave(int(args[0]))
        if op == "info":
            with self._lock:
                return {"generation": self._gen,
                        "members": sorted(self._members),
                        "pending": sorted(self._pending),
                        "last_good": self._last_good,
                        "max_step": self._max_step}
        if op == "telem":
            rank, payload = args
            return self._telem_push(int(rank), payload)
        if op == "telem_snap":
            return self._telem_snapshot()
        raise ValueError(f"unknown elastic op {op!r}")

    def _telem_push(self, rank, payload):
        """Store one member telemetry report (bounded ring) and reply
        with the coordinator's wall clock — the member's offset
        estimate needs nothing more than this round trip."""
        rec = dict(payload) if isinstance(payload, dict) else {}
        rec["rank"] = rank
        rec["received_ts"] = time.time()
        with self._lock:
            import collections
            ring = self._telem.get(rank)
            if ring is None:
                ring = self._telem[rank] = collections.deque(maxlen=16)
            ring.append(rec)
        return {"coordinator_ts": time.time(), "generation": self._gen}

    def _telem_snapshot(self):
        with self._lock:
            reports = {r: list(ring) for r, ring in self._telem.items()}
        offsets = {}
        for r, ring in reports.items():
            if ring:
                off = ring[-1].get("offset_s")
                if isinstance(off, (int, float)):
                    offsets[r] = off
        return {"reports": reports, "offsets": offsets}

    def _admit(self, rank, active_from):
        """Shared admission bookkeeping. Dropping any stale
        _last_contrib entry is what re-arms the startup grace for a
        RE-joining rank (a relaunched SIGKILL victim): its pre-eviction
        contributions must not make its restore/compile silence read as
        death again."""
        self._members.add(rank)
        self._active_from[rank] = active_from
        self._joined_at[rank] = time.monotonic()
        self._last_contrib.pop(rank, None)

    def _join(self, rank):
        with self._cond:
            if rank in self._members:
                return self._admit_payload(rank)
            if not self._started:
                self._admit(rank, 1)
                return self._admit_payload(rank)
            if self._last_good is not None:
                # a checkpoint boundary has already passed: restorable
                # last-good state exists, so the joiner enters now
                # (effective from the step after the current round)
                self._admit(rank, self._max_step + 1)
                self._gen += 1
                self._joined_log.append((self._gen, [rank]))
                self._cond.notify_all()
                return self._admit_payload(rank)
            # mid-run with NO checkpoint yet: admission waits for the
            # next checkpoint boundary (the joiner needs state to
            # restore)
            self._pending.add(rank)
            return {"admitted": False, "generation": self._gen,
                    "members": sorted(self._members)}

    def _admit_payload(self, rank):
        lg = self._last_good
        return {"admitted": True, "generation": self._gen,
                "members": sorted(self._members),
                "next_step": self._max_step + 1,
                "last_good": ({"step": lg[0], "path": lg[1]}
                              if lg else None)}

    def _ckpt(self, step, path):
        with self._cond:
            if self._last_good is None or step >= self._last_good[0]:
                self._last_good = (step, path)
            admitted = []
            if self._pending:
                # the admission boundary: last-good state now exists for
                # joiners to restore from
                for r in sorted(self._pending):
                    self._admit(r, self._max_step + 1)
                    admitted.append(r)
                self._pending.clear()
                self._gen += 1
                self._joined_log.append((self._gen, admitted))
                self._cond.notify_all()
            return {"last_good": {"step": self._last_good[0],
                                  "path": self._last_good[1]},
                    "admitted": admitted, "generation": self._gen}

    def _leave(self, rank):
        with self._cond:
            if rank in self._members:
                # a graceful drain, NOT an eviction: the leaver finished
                # its rounds, so survivors re-form without rolling back
                self._members.discard(rank)
                self._gen += 1
                self._left_log.append((self._gen, [rank]))
                self._cond.notify_all()
            self._pending.discard(rank)
            return {"generation": self._gen,
                    "members": sorted(self._members)}

    def _sync(self, rank, gen_seen, step, vec):
        with self._cond:
            self._started = True
            self._max_step = max(self._max_step, step)
            if rank not in self._members:
                # an evicted rank syncing again (it was only slow, not
                # dead, and missed the round): it must re-join through
                # the checkpoint boundary like any other joiner
                raise RuntimeError(
                    f"rank {rank} is not a member (evicted or never "
                    f"joined) — call join() to re-enter at the next "
                    f"checkpoint boundary")
            rnd = self._rounds.setdefault(step, {})
            rnd[rank] = vec
            self._last_contrib[rank] = max(
                self._last_contrib.get(rank, 0), step)
            self._cond.notify_all()
            deadline = time.monotonic() + self.sync_timeout_s
            while True:
                # a joiner admitted at a checkpoint boundary is only
                # REQUIRED from the step it was told to start at — a
                # survivor mid-round must not wait on a contribution
                # the joiner was never asked for
                current = {r for r in self._members
                           if self._active_from.get(r, 1) <= step}
                missing = current - set(rnd)
                if not missing:
                    break
                # a member already syncing LATER rounds is alive and
                # will never come back to this one (a lagging re-joiner
                # replaying a stale round must neither wait for it nor
                # evict it) — complete over whoever is here
                ahead = {r for r in missing
                         if self._last_contrib.get(r, -1) > step}
                if missing == ahead:
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    now = time.monotonic()
                    # a member that has NEVER contributed is still in
                    # startup (compiling, restoring): inside its grace
                    # window its silence is expected, not death
                    graced = {r for r in missing - ahead
                              if r not in self._last_contrib
                              and now - self._joined_at.get(r, now)
                              < self.startup_grace_s}
                    dead = sorted(missing - ahead - graced)
                    if dead:
                        # eviction: the departed rank's contribution is
                        # never coming; the survivors' round completes
                        # without it
                        for r in dead:
                            self._members.discard(r)
                        self._gen += 1
                        self._departed_log.append((self._gen, dead))
                        # survivors will roll back to last-good and
                        # REPLAY rounds ≤ this one: stale buffered
                        # contributions must not mix into the replayed
                        # means, stale _last_contrib must not make the
                        # "ahead" rule complete a replayed round over a
                        # partial set, and the restore-from-last-good
                        # pause must not itself read as death — so the
                        # round state resets and every survivor gets a
                        # fresh startup-grace window
                        self._rounds.clear()
                        self._last_contrib.clear()
                        now_m = time.monotonic()
                        for r in self._members:
                            self._joined_at[r] = now_m
                        self._cond.notify_all()
                        break
                    if not graced:
                        break
                    deadline = now + 0.5   # re-check as grace expires
                self._cond.wait(min(max(remaining, 0.05), 0.2))
            contrib = [v for r, v in rnd.items() if r in self._members]
            mean = (np.mean(contrib, axis=0) if contrib
                    else np.asarray(vec, np.float32))
            resp = {"mean": mean, "generation": self._gen,
                    "members": sorted(self._members), "step": step}
            if self._gen != gen_seen:
                resp["departed"] = sorted(
                    r for g, rs in self._departed_log if g > gen_seen
                    for r in rs)
                resp["left"] = sorted(
                    r for g, rs in self._left_log if g > gen_seen
                    for r in rs)
                resp["joined"] = sorted(
                    r for g, rs in self._joined_log if g > gen_seen
                    for r in rs)
                lg = self._last_good
                resp["last_good"] = ({"step": lg[0], "path": lg[1]}
                                     if lg else None)
            # bounded round memory: everything older than a few steps
            # is settled
            for s in [s for s in self._rounds if s < step - 4]:
                self._rounds.pop(s, None)
            return resp
