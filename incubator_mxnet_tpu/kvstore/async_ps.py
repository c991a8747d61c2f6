"""Cross-process asynchronous parameter server for `dist_async`.

Parity: the reference's ps-lite server path (`src/kvstore/
kvstore_dist_server.h`) — each worker's push is applied as its OWN
server-side optimizer update in arrival order, with no cross-worker
aggregation barrier, and pulls return the server's CURRENT weights
(possibly missing other workers' in-flight pushes).

TPU-native rebuild, second iteration: rank 0 hosts the server state and
a plain TCP listener on loopback/pod-LAN; the jax coordination service
is used ONLY for the one-time address exchange (one `key_value_set` by
the server, one `blocking_key_value_get` per worker). All data-plane
traffic — pushes, pulls, applied-count acks, flushes — rides
length-prefixed pickled frames over sockets, exactly ps-lite's own
van/zmq layout.

Why not the coordination-service KV as the wire (the first iteration)?
Sustained traffic through this jaxlib's KV client (polled dir listings,
repeated blocking gets) segfaults the client after a few hundred RPCs —
a C++ bug we cannot patch from here, and one the low-volume rendezvous
usage never hits. A socket wire is also the honest rebuild: the
reference never routed gradients through its tracker either.

Per-worker FIFO is preserved by connection order + sequence numbers;
cross-worker interleaving is genuine arrival nondeterminism (TCP accept
order and thread scheduling decide it). Induced bounded staleness
(`set_async_staleness`) still applies through the store's `_AsyncQueue`,
aged by a server-side ticker so held-back entries release by time as
well as by traffic.
"""
from __future__ import annotations

import pickle
import socket
import struct
import threading
import time

import numpy as np

_NS = "mxtpu_aps"
_LIVE = []      # live transports; distributed.shutdown() stops them first


def stop_all(timeout=5.0):
    """Stop every live server thread (joined, not abandoned): called by
    mx.distributed.shutdown() before the coordination client dies.
    Snapshot first: stop() deregisters each transport from _LIVE."""
    live = list(_LIVE)
    for t in live:
        t.stop()
    for t in live:
        if t._thread is not None:
            t._thread.join(timeout)
    _LIVE.clear()


def _client():
    from jax._src import distributed
    c = distributed.global_state.client
    if c is None:
        raise RuntimeError(
            "dist_async across processes needs jax.distributed "
            "(mx.distributed.init()) — the coordination service is the "
            "rendezvous")
    return c


# -- framing ----------------------------------------------------------------

def _send_frame(sock, obj):
    blob = pickle.dumps(obj, protocol=4)
    sock.sendall(struct.pack(">Q", len(blob)) + blob)


def _recv_exact(sock, n):
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed mid-frame")
        buf += chunk
    return buf


def _recv_frame(sock):
    (n,) = struct.unpack(">Q", _recv_exact(sock, 8))
    return pickle.loads(_recv_exact(sock, n))


class AsyncPSTransport:
    """One per dist_async KVStore when process_count > 1."""

    def __init__(self, kv, poll_ms=2.0, flush_timeout=None):
        import jax
        self._kv = kv
        self._c = _client()
        self.rank = jax.process_index()
        self.nproc = jax.process_count()
        self._seq = 0                 # my push sequence (per-worker FIFO)
        self._pushed = 0
        self._poll_s = poll_ms / 1e3
        from ..settings import env_float
        self.flush_timeout = float(env_float(
            "MXTPU_APS_FLUSH_TIMEOUT", 120.0, call_site=flush_timeout))
        self._stop = threading.Event()
        self._applied = {}            # server: worker rank -> applied count
        self._last_seq = {}           # server: rank -> newest applied seq
        self._health = {}             # server: rank -> latest health record
        self._lock = threading.Lock()
        self._apply_lock = threading.Lock()  # serializes optimizer applies
        self._thread = None
        self._listener = None
        self._server_addr = None
        if self.rank == 0:
            self._listener = socket.socket(socket.AF_INET,
                                           socket.SOCK_STREAM)
            self._listener.setsockopt(socket.SOL_SOCKET,
                                      socket.SO_REUSEADDR, 1)
            from ..settings import env_str
            host = env_str("MXTPU_APS_HOST", "127.0.0.1")
            self._listener.bind((host, 0))
            self._listener.listen(64)
            self._listener.settimeout(0.2)   # lets the accept loop stop
            self._server_addr = self._listener.getsockname()
            # rendezvous: the ONLY coordination-KV write on the data path
            self._c.key_value_set_bytes(
                f"{_NS}/addr", pickle.dumps(self._server_addr),
                allow_overwrite=True)
            self._thread = threading.Thread(target=self._serve, daemon=True)
            self._thread.start()
        _LIVE.append(self)

    # -- worker side -------------------------------------------------------
    def _addr(self):
        if self._server_addr is None:
            blob = self._c.blocking_key_value_get_bytes(f"{_NS}/addr",
                                                        60_000)
            self._server_addr = tuple(pickle.loads(blob))
        return self._server_addr

    def _rpc(self, *msg, timeout=30.0):
        """One request/response round trip (connection per call: the
        volume is one RPC per push/pull/ack, trivial for loopback/LAN)."""
        with socket.create_connection(self._addr(), timeout=timeout) as s:
            _send_frame(s, msg)
            kind, payload = _recv_frame(s)
        if kind == "err":
            raise RuntimeError(f"dist_async server: {payload}")
        return payload

    def publish_init(self, key, value_np):
        """Rank 0 (the server) holds initial weights in its own store;
        workers block until the server reports the key initialized (the
        reference's init-on-server + worker pull-before-train)."""
        if self.rank == 0:
            return
        deadline = time.time() + 60.0
        while time.time() < deadline:
            if self._rpc("has", key):
                return
            time.sleep(self._poll_s)
        raise TimeoutError(f"dist_async: server never initialized {key!r}")

    def push(self, key, grad_np):
        self._seq += 1
        self._pushed += 1
        if self.rank == 0:
            self._ingest(self.rank, self._seq, key, np.asarray(grad_np))
        else:
            self._rpc("push", self.rank, self._seq, key,
                      np.asarray(grad_np))

    def pull(self, key):
        return self._rpc("pull", key)

    def flush(self, timeout=None):
        """Block until every push THIS worker issued has been applied
        server-side (the reference's per-worker Wait on the send queue).
        Push RPCs are synchronous, so by entry every push has been
        RECEIVED; the flush RPC force-drains staleness-delayed entries
        and the loop waits out any apply still in flight."""
        limit = self.flush_timeout if timeout is None else float(timeout)
        deadline = time.time() + limit
        self._rpc("flush")
        last_flush = time.time()
        while time.time() < deadline:
            if self._applied_count(self.rank) >= self._pushed:
                return
            time.sleep(max(self._poll_s, 0.01))
            if time.time() - last_flush >= 0.5:
                # re-force-drain only occasionally (covers pushes that
                # raced past the first flush); re-sending per poll would
                # hammer rank 0 with a connection + full queue drain
                # every couple of milliseconds
                self._rpc("flush")
                last_flush = time.time()
        raise TimeoutError(
            f"dist_async flush: rank {self.rank} pushed {self._pushed} "
            f"but the server did not acknowledge them in {limit:g}s")

    def _applied_count(self, rank):
        if self.rank == 0:
            with self._lock:
                return self._applied.get(rank, 0)
        return self._rpc("applied", rank)

    def wait_outstanding(self, max_outstanding, timeout=60.0):
        """Block until at most `max_outstanding` of MY pushes are still
        unapplied — the worker-side pacing ps-lite gets implicitly from
        pulling updated weights after each push. Cross-worker staleness
        stays unbounded; only a worker's lead over ITSELF is capped."""
        applied = 0   # a non-positive timeout must raise TimeoutError
        deadline = time.time() + timeout
        while time.time() < deadline:
            applied = self._applied_count(self.rank)
            if self._pushed - applied <= max_outstanding:
                return
            time.sleep(max(self._poll_s, 0.01))  # each poll = one RPC
        raise TimeoutError(
            f"rank {self.rank}: {self._pushed} pushed but server applied "
            f"only {applied} after {timeout}s")

    def health_exchange(self, record):
        """healthmon skew-timeline transport for dist_async (workers are
        NOT in lockstep, so the sync path's allgather would deadlock):
        post this worker's fixed-width timing record to the rank-0
        server, get back the merged {rank: record} table — best-effort
        and possibly stale for other ranks, the async contract."""
        record = [float(v) for v in record]
        if self.rank == 0:
            with self._lock:
                self._health[0] = record
                return {int(r): list(v) for r, v in self._health.items()}
        merged = self._rpc("health", self.rank, record)
        return {int(r): list(v) for r, v in merged.items()}

    def applied_counts(self):
        """Per-worker applied-update counts from the server."""
        if self.rank == 0:
            with self._lock:
                return {r: self._applied.get(r, 0)
                        for r in range(self.nproc)}
        counts = self._rpc("counts")
        return {r: counts.get(r, 0) for r in range(self.nproc)}

    def stop(self):
        """Signal the server thread to exit and deregister from _LIVE so a
        discarded dist_async store doesn't pin an accept-loop daemon (and
        its listener socket) for the life of the process."""
        self._stop.set()
        try:
            _LIVE.remove(self)
        except ValueError:
            pass

    # -- server side (rank 0) ---------------------------------------------
    def _apply(self, tagged_key, grad):
        """_AsyncQueue apply hook: one worker push = one optimizer step."""
        key, rank = tagged_key
        with self._apply_lock:
            self._kv._apply_one_update(key, grad)
        with self._lock:
            self._applied[rank] = self._applied.get(rank, 0) + 1

    def _ingest(self, rank, seq, key, grad):
        """Seq-deduped enqueue into the staleness queue (per-worker FIFO:
        TCP + the per-connection handler give per-worker ordering)."""
        from ..ndarray import NDArray
        with self._lock:
            if seq <= self._last_seq.get(rank, 0):
                return            # duplicate delivery; already applied
            self._last_seq[rank] = seq
        self._kv._async_queue.push((key, rank), NDArray(np.asarray(grad)))

    def _handle(self, conn):
        try:
            with conn:
                msg = _recv_frame(conn)
                op, args = msg[0], msg[1:]
                try:
                    if op == "push":
                        rank, seq, key, grad = args
                        self._ingest(int(rank), int(seq), key, grad)
                        reply = ("ok", True)
                    elif op == "pull":
                        (key,) = args
                        with self._apply_lock:
                            w = np.asarray(self._kv._store[key].asnumpy())
                        reply = ("ok", w)
                    elif op == "has":
                        (key,) = args
                        reply = ("ok", key in self._kv._store)
                    elif op == "applied":
                        (rank,) = args
                        with self._lock:
                            reply = ("ok", self._applied.get(rank, 0))
                    elif op == "counts":
                        with self._lock:
                            reply = ("ok", dict(self._applied))
                    elif op == "health":
                        rank, rec = args
                        with self._lock:
                            self._health[int(rank)] = [float(v)
                                                       for v in rec]
                            reply = ("ok", dict(self._health))
                    elif op == "flush":
                        self._kv._async_queue.flush()
                        reply = ("ok", True)
                    else:
                        reply = ("err", f"unknown op {op!r}")
                except Exception as e:  # noqa: BLE001 — one bad request
                    reply = ("err", f"{type(e).__name__}: {e}")
                _send_frame(conn, reply)
        except Exception:
            pass                  # a dropped client must not kill serving

    def _serve(self):
        """Accept loop + staleness ticker. Handler threads are short-lived
        (one request per connection); the ticker ages delayed entries so
        induced staleness releases by TIME as well as by traffic."""
        last_tick = time.time()
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
                threading.Thread(target=self._handle, args=(conn,),
                                 daemon=True).start()
            except socket.timeout:
                pass
            except Exception:
                if self._stop.is_set():
                    break
                # persistent accept failures (EMFILE, invalidated fd)
                # must not hot-spin a rank-0 core; pause and retry
                time.sleep(0.05)
            now = time.time()
            if now - last_tick >= max(self._poll_s, 0.01):
                last_tick = now
                q = self._kv._async_queue
                if q is not None and q.pending_count:
                    q._drain(force=False)
        try:
            self._listener.close()
        except Exception:
            pass
