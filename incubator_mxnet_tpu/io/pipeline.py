"""mxtpu.io.pipeline — the staged host ingest engine behind
:class:`~.prefetch.DevicePrefetcher`.

The PR 6 prefetcher was ONE worker thread that read, decoded, stacked
and ``jax.device_put`` each chunk *serially*, so decode wall and
transfer wall added instead of overlapping. This module splits that
body into the classic input-pipeline stages, each on its own thread(s),
with the batch ORDER pinned by sequence numbers so the resume cursor
and the training trajectory are bit-identical to the serial reader no
matter how decode completions interleave:

    reader ──► decode pool (io_workers) ──► ordered staging ring ──► transfer
    (source next, skip/cycle      (host decode/transform/stack,      (device_put
     cursor — the order            completes out of order)            in seq order,
     authority)                                                       depth slots)

* **reader** — the single thread that iterates the source. It owns the
  ``skip=`` data cursor and the cycle/epoch-fold logic (resilience
  resume semantics live HERE, before any parallelism), assigns each
  chunk a sequence number, and feeds a bounded work queue.
* **decode pool** — ``workers`` threads perform the host-side work:
  the optional ``transform`` hook, NDArray→raw conversion, the
  mixed-label check, and numpy stacking for chunk mode. Results land
  in the staging ring keyed by sequence number — completion order is
  irrelevant.
* **transfer** — one thread pops the ring strictly in sequence order
  (the wait is ``io.stage_ms``) and parks the batch in the
  ``depth``-bounded buffer the consumer pops. On thread-safe backends
  (TPU) it also resolves the late-bound sharding and issues
  ``jax.device_put`` itself under the process-wide
  :data:`TRANSFER_GATE` (the wall is ``io.put_ms``); on XLA:CPU the
  put is deferred to the consumer thread — see the safety model below.

Per-stage wall counters split devicescope's ``input_starved`` bucket
into disk-vs-decode-vs-transfer attribution (docs/io.md):

* ``io.read_ms``   counter — reader wall inside the SOURCE's next();
* ``io.decode_ms`` counter — decode-pool wall (sums across workers, so
  it can exceed wall-clock — it is host-work attribution, not a span);
* ``io.stage_ms``  counter — transfer wall waiting for the next
  in-order chunk (reordering/decode-lag wait);
* ``io.put_ms``    counter — convert + ``device_put`` wall;
* ``io.workers``   gauge   — resolved decode-pool width.

Backend-safety model (the PR 14 1-in-3 ``test_resilience`` flake):
this jaxlib's XLA:CPU client is not safe against host↔device copies
concurrent with a DONATING execution running on its internal threads —
the donated-buffer handoff happens *during* the async execution, and a
concurrent ``BufferFromHostBuffer`` corrupts the heap (the crash then
detonates anywhere: the copy itself, the next dispatch, orbax's
asyncio loop). Empirically it does not matter which *Python* thread
issues the copy: gating the dispatch enqueue, fencing on the last
dispatch handle, and even moving every put onto the dispatching thread
each still crashed 2-3 in 5-6 suite runs — because (PR 17's flake hunt)
the DOMINANT planter was not a transfer race at all: this jaxlib also
mis-deserializes persistent-compile-cache entries for donated
executables, probabilistically per READ (warm cache: 6/10 process
crashes on the resume tests; cache wiped per run: 1/12; reads
quarantined: 0/12 — see runtime/cache_guard.py). The fix therefore has
four parts — the cache-read quarantine removes the dominant planter,
and the transfer serialization below closes the concurrency windows
the PR 14 diagnosis named:

1. **deferred put** (this module): on the CPU backend the transfer
   stage parks host-staged batches in the buffer and the CONSUMER
   thread issues ``device_put`` inside ``next()`` — every XLA call the
   pipeline makes comes from the one thread that also dispatches.
   Decode-pool ∥ compute overlap (the CPU win) is preserved; only the
   put moves on-thread, and on CPU a put is a host-memory copy with
   negligible wall.
2. **synchronous donating dispatch**
   (:class:`~..parallel.trainer_step.FusedTrainStep`): on the CPU
   backend the dispatch blocks until the donating execution retires,
   so no client call can ever overlap the donation window. Only async
   dispatch depth is forfeited, on the backend where it buys nothing —
   compute still overlaps the decode pool (host threads). The block
   happens INSIDE the gate, so on CPU the donation window and the gate
   window coincide.
3. **gated checkpoint serialization**
   (:class:`~..resilience.checkpoint.CheckpointManager`): the async
   checkpoint worker holds the same gate for the whole orbax save on
   CPU. With part 2 the gate covers every XLA window, so a save can
   never overlap one.
4. **donated cache-read quarantine**
   (:mod:`~..runtime.cache_guard`): donating fused-step dispatches
   run under a forced persistent-cache MISS, so their executables
   always come from a fresh backend compile, never from the unsound
   deserialization path.

On TPU the client supports concurrent transfers and donation is
handled by the runtime, so the transfer thread issues the put itself
(put ∥ compute overlap kept) and dispatches stay async. Both backends
still serialize the put against the dispatch enqueue via the
process-wide :data:`TRANSFER_GATE` that FusedTrainStep shares.
"""
from __future__ import annotations

import queue as _queue
import threading
import time

import numpy as np

from .. import profiler as _prof

__all__ = ["Pipeline", "ShardedRecordReader", "TRANSFER_GATE",
           "transfer_gate"]

_SENTINEL = object()
_DONE = object()          # decode-pool poison pill

# default close() deadline for a reader parked inside the source's
# next(); DevicePrefetcher passes its own (monkeypatchable) constant
_CLOSE_DEADLINE_S = 5.0

# Process-wide host→device transfer gate. Held around every pipeline
# device_put and by FusedTrainStep around the donating dispatch
# enqueue, so a put enqueue never interleaves a dispatch enqueue on
# the client. One lock for the process: the ordering it protects is a
# client-level property, not a per-pipeline one.
TRANSFER_GATE = threading.Lock()

# lazily-probed "must the put run on the consumer thread?" cache.
# XLA:CPU yes — its client races off-thread host→device copies against
# the donated-buffer handoff of a RUNNING execution (see the module
# docstring); TPU no — concurrent transfers are supported there, and
# deferring would forfeit the put∥compute overlap.
_DEFER_BACKEND = []


def transfer_gate():
    """The process-wide transfer/dispatch serialization lock (use as
    ``with transfer_gate(): ...``)."""
    return TRANSFER_GATE


def _defer_put_needed():
    if not _DEFER_BACKEND:
        import jax
        _DEFER_BACKEND.append(jax.default_backend() == "cpu")
    return _DEFER_BACKEND[0]


class _HostStaged:
    """Buffer wrapper for a batch whose device_put is deferred to the
    consumer thread (CPU backend — see the module docstring). Holds
    only host/already-landed arrays, so close()-time draining frees
    nothing the client could still be writing."""
    __slots__ = ("payload",)

    def __init__(self, payload):
        self.payload = payload


def _split_batch(b):
    """Normalize one source item to (x, y): DataBatch, (x, y) pair, or a
    bare array (y=None)."""
    data = getattr(b, "data", None)
    if data is not None and not isinstance(b, (tuple, list, np.ndarray)):
        label = getattr(b, "label", None)
        return data[0], (label[0] if label else None)
    if isinstance(b, (tuple, list)) and len(b) == 2:
        return b[0], b[1]
    return b, None


def _raw(a):
    from ..ndarray import NDArray
    if isinstance(a, NDArray):
        return a._data
    return np.asarray(a)


def _stack_dev(arrs):
    import jax.numpy as jnp
    return jnp.stack([jnp.asarray(a) for a in arrs])


def _resolve_workers(workers):
    """Decode-pool width (call-site > MXTPU_IO_WORKERS > 2)."""
    from .. import settings as _settings
    v = int(_settings.resolve("io_workers", workers)[0])
    if v < 1:
        raise ValueError(f"io workers must be >= 1, got {v}")
    return v


class Pipeline:
    """Staged host ingest: reader → decode pool → ordered ring →
    transfer → ``depth`` device slots. See the module docstring for the
    stage model; :class:`~.prefetch.DevicePrefetcher` is the public
    face and documents the source/depth/chunk/sharding/cycle/skip
    contract (unchanged from PR 6).

    workers   : decode-pool width (the ``io_workers`` setting; None
                resolves through settings.py).
    transform : optional host-side hook ``(x, y) -> (x, y)`` applied to
                each batch INSIDE the decode pool — the place for
                per-batch decode/augment work, because the pool parallelizes
                it while order stays pinned by the ring.
    """

    def __init__(self, source, depth=2, chunk=None, sharding=None,
                 cycle=False, skip=0, workers=None, transform=None):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if chunk is not None and chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        if skip < 0:
            raise ValueError(f"skip must be >= 0, got {skip}")
        self._source = source
        self._depth = int(depth)
        self._chunk = int(chunk) if chunk else None
        self._sharding = sharding
        self._cycle = bool(cycle)
        self._skip = int(skip)
        self._workers = _resolve_workers(workers)
        self._transform = transform
        self._epoch_len = None   # learned at the first source wrap
        self._buf = _queue.Queue(maxsize=self._depth)
        self._stop = threading.Event()
        self._exhausted = False
        # counters exist from construction so smoke checks can assert on
        # them even for an all-hits run (wait_ms == 0 is a signal too)
        self._c_batches = _prof.counter("io.batches_prefetched", "io")
        self._c_wait = _prof.counter("io.wait_ms", "io")
        self._c_put = _prof.counter("io.put_ms", "io")
        self._c_read = _prof.counter("io.read_ms", "io")
        self._c_decode = _prof.counter("io.decode_ms", "io")
        self._c_stage = _prof.counter("io.stage_ms", "io")
        _prof.set_gauge("io.depth", self._depth, "io")
        _prof.set_gauge("io.buffer_fill", 0, "io")
        _prof.set_gauge("io.workers", self._workers, "io")
        # work queue bound: enough for every decoder plus readahead
        self._work = _queue.Queue(maxsize=self._workers + 2)
        # in-flight window: the reader may run at most this many chunks
        # ahead of the transfer stage (acquired per chunk read, released
        # per chunk popped from the ring). Without it the decode pool
        # churns arbitrarily far ahead of a slow consumer on a cycling
        # source — unbounded ring memory AND host CPU stolen from
        # compute (a pipelined run once ran SLOWER than serial through
        # exactly this)
        self._window = threading.Semaphore(
            self._workers + self._depth + 2)
        self._ring = {}          # seq -> ("ok", payload) | ("err", exc)
        self._ring_cv = threading.Condition()
        self._eof_seq = None     # chunk count, set once by the reader
        self._threads = [
            threading.Thread(target=self._read_loop, daemon=True,
                             name="mxtpu-io-read")]
        self._threads += [
            threading.Thread(target=self._decode_loop, daemon=True,
                             name=f"mxtpu-io-decode-{i}")
            for i in range(self._workers)]
        # the transfer thread keeps the historical name: it is the one
        # that lands batches on device, i.e. the old worker's role
        self._thread = threading.Thread(target=self._transfer_loop,
                                        daemon=True,
                                        name="mxtpu-device-prefetch")
        self._threads.append(self._thread)
        for t in self._threads:
            t.start()

    # -- reader stage -----------------------------------------------------
    def _iter_source(self):
        src = self._source
        while True:
            it = iter(src) if not hasattr(src, "next") else src
            n = 0
            while True:
                t0 = time.perf_counter()
                try:
                    b = next(it)
                except StopIteration:
                    self._c_read.increment(
                        (time.perf_counter() - t0) * 1e3)
                    break
                self._c_read.increment((time.perf_counter() - t0) * 1e3)
                n += 1
                yield b
            if n and self._epoch_len is None:
                self._epoch_len = n
            if not self._cycle:
                return
            if hasattr(src, "reset"):
                src.reset()
            elif iter(src) is src:
                return          # a bare iterator can't be rewound

    def _read_loop(self):
        """The order authority: iterates the source, applies the resume
        cursor, numbers chunks. Runs the EXACT skip/cycle semantics of
        the PR 6 serial worker — parallelism starts downstream of the
        cursor, so a resumed run sees the same batches in the same
        order at any worker count."""
        seq = 0
        try:
            pending = []
            n = self._chunk or 1
            to_skip = self._skip
            if to_skip:
                c_skip = _prof.counter("io.batches_skipped", "io")
            for b in self._iter_source():
                if self._stop.is_set():
                    break
                if to_skip > 0:
                    # cursor resume: already-consumed batches are
                    # dropped host-side, before any conversion/transfer.
                    # An ABSOLUTE cursor through a cycling source only
                    # matters modulo the epoch: once the first wrap
                    # teaches us the epoch length, whole epochs of the
                    # remaining skip fold away instead of being read and
                    # discarded — resume cost stays bounded by ~one
                    # epoch of host reads however long the run was
                    if self._cycle and self._epoch_len:
                        to_skip %= self._epoch_len
                        if to_skip == 0:
                            pass   # fell exactly on a boundary: train b
                        else:
                            to_skip -= 1
                            c_skip.increment()
                            continue
                    else:
                        to_skip -= 1
                        c_skip.increment()
                        continue
                pending.append(_split_batch(b))
                if len(pending) < n:
                    continue
                if not self._put_work((seq, pending)):
                    break
                seq += 1
                pending = []
            # a trailing partial chunk is dropped (static-shape programs
            # can't take a short chunk); per-batch mode has no remainder
            with self._ring_cv:
                if self._eof_seq is None:
                    self._eof_seq = seq
                self._ring_cv.notify_all()
        except Exception as e:  # noqa: BLE001 — surfaced at next(), in order
            with self._ring_cv:
                self._ring[seq] = ("err", e)
                self._eof_seq = seq + 1
                self._ring_cv.notify_all()
        for _ in range(self._workers):
            try:
                self._work.put_nowait(_DONE)
            except _queue.Full:
                break            # stopping: decoders exit on the flag

    def _put_work(self, item):
        while not self._stop.is_set():       # in-flight window first:
            if self._window.acquire(timeout=0.05):   # released by the
                break                        # transfer stage per chunk
        else:
            return False
        while not self._stop.is_set():
            try:
                self._work.put(item, timeout=0.05)
                return True
            except _queue.Full:
                continue
        return False

    # -- decode stage -----------------------------------------------------
    def _decode_loop(self):
        while not self._stop.is_set():
            try:
                item = self._work.get(timeout=0.05)
            except _queue.Empty:
                continue
            if item is _DONE:
                return
            seq, items = item
            t0 = time.perf_counter()
            try:
                entry = ("ok", self._decode(items))
            except Exception as e:  # noqa: BLE001 — surfaced at next()
                entry = ("err", e)
            self._c_decode.increment((time.perf_counter() - t0) * 1e3)
            with self._ring_cv:
                self._ring[seq] = entry
                self._ring_cv.notify_all()

    def _decode(self, items):
        """Host-side chunk decode: transform hook, raw conversion, the
        mixed-label check, numpy stacking. Returns (xs, ys) lists —
        singleton once stacked; device-array stacking is deferred to the
        transfer stage (it is device work)."""
        if self._transform is not None:
            items = [self._transform(x, y) for x, y in items]
        xs = [_raw(x) for x, _ in items]
        n_labeled = sum(1 for _, y in items if y is not None)
        if 0 < n_labeled < len(items):
            # fail HERE, not as a leading-axis mismatch deep inside the
            # compiled scan: a partially-labeled chunk is a source bug
            raise ValueError(
                f"mixed labeled/label-less batches in one prefetch chunk "
                f"({n_labeled}/{len(items)} labeled)")
        ys = [_raw(y) for _, y in items if y is not None]
        if self._chunk is not None:
            if all(isinstance(a, np.ndarray) for a in xs):
                xs = [np.stack(xs)]
            if ys and all(isinstance(a, np.ndarray) for a in ys):
                ys = [np.stack(ys)]
        return xs, ys

    # -- transfer stage ---------------------------------------------------
    def _transfer_loop(self):
        seq = 0
        try:
            while True:
                t0 = time.perf_counter()
                with self._ring_cv:
                    while True:
                        if self._stop.is_set():
                            return
                        if seq in self._ring:
                            kind, payload = self._ring.pop(seq)
                            self._window.release()   # reader may read on
                            break
                        if self._eof_seq is not None \
                                and seq >= self._eof_seq:
                            kind, payload = "eof", None
                            break
                        self._ring_cv.wait(0.05)
                self._c_stage.increment((time.perf_counter() - t0) * 1e3)
                if kind == "eof":
                    self._put(_SENTINEL)
                    return
                if kind == "err":
                    self._put(payload)
                    return
                if _defer_put_needed():
                    # CPU: no XLA call may leave this thread — park the
                    # host-staged batch; next() issues the put on the
                    # consumer thread (module docstring: safety model)
                    item = _HostStaged(payload)
                else:
                    item = self._to_device(payload)
                self._c_batches.increment(self._chunk or 1)
                if not self._put(item):
                    return
                seq += 1
        except Exception as e:  # noqa: BLE001 — surfaced at next()
            self._put(e)

    def _to_device(self, payload):
        import jax
        xs, ys = payload
        t0 = time.perf_counter()
        if self._chunk is not None:
            # device-array chunks could not np.stack in the decode pool
            if len(xs) > 1:
                xs = [_stack_dev(xs)]
            if len(ys) > 1:
                ys = [_stack_dev(ys)]
        sharding = self._sharding() if callable(self._sharding) \
            else self._sharding
        put = (lambda a: jax.device_put(a, sharding)) \
            if sharding is not None else jax.device_put
        with TRANSFER_GATE:
            out = (put(xs[0]), put(ys[0]) if ys else None)
        # materialize OUTSIDE the gate (holding it would stall dispatch
        # enqueues): device_put returns an async array, and a copy
        # still in flight when the batch reaches the buffer could race
        # a close()-time free. On the deferred path this runs on the
        # consumer thread, where a CPU put is a near-synchronous
        # host-memory copy — negligible wall, counted in io.put_ms.
        for a in out:
            if a is not None:
                jax.block_until_ready(a)
        self._c_put.increment((time.perf_counter() - t0) * 1e3)
        return out

    def _put(self, item):
        """Blocking put that stays responsive to close()."""
        while not self._stop.is_set():
            try:
                self._buf.put(item, timeout=0.05)
                return True
            except _queue.Full:
                continue
        return False

    # -- consumer ---------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        t0 = time.perf_counter()
        item = self._buf.get()
        self._c_wait.increment((time.perf_counter() - t0) * 1e3)
        _prof.set_gauge("io.buffer_fill", self._buf.qsize(), "io")
        if item is _SENTINEL:
            self._exhausted = True
            raise StopIteration
        if isinstance(item, Exception):
            self._exhausted = True
            raise item
        if isinstance(item, _HostStaged):
            # deferred put (CPU backend): the one XLA call the pipeline
            # makes off the worker threads happens HERE, on the same
            # thread that dispatches — single-threaded client usage
            item = self._to_device(item.payload)
        return item

    next = __next__

    # -- lifecycle --------------------------------------------------------
    def close(self, deadline_s=_CLOSE_DEADLINE_S):
        """Stop every stage and drop every buffered device batch. Safe
        to call at any point (mid-epoch early stop included) and
        idempotent; after close() the buffer holds no device references.

        A reader parked inside the SOURCE's ``next()`` (streaming/queue
        sources) cannot be interrupted; close() stops waiting for it
        after ``deadline_s`` — the threads are daemons, and once the
        stop flag is set ``_put`` refuses every item, so nothing can
        land in the buffer after close() returns either way."""
        self._stop.set()
        with self._ring_cv:
            self._ring_cv.notify_all()
        deadline = time.monotonic() + deadline_s
        # the transfer thread dies FIRST (all its waits are short-tick
        # timeouts, so it exits promptly once the flag is up): after
        # this join no off-thread device_put can be in flight, so the
        # drain below frees fully-landed arrays (or host-staged
        # batches, on the deferred-put backend) instead of racing an
        # async copy — the close()-time half of the PR 14 segfault
        while self._thread.is_alive() and time.monotonic() < deadline:
            self._thread.join(timeout=0.05)
        while True:
            try:
                with TRANSFER_GATE:
                    self._buf.get_nowait()
            except _queue.Empty:
                if not any(t.is_alive() for t in self._threads) \
                        or time.monotonic() > deadline:
                    break
                time.sleep(0.01)
        self._exhausted = True
        with self._ring_cv:
            self._ring.clear()
        _prof.set_gauge("io.buffer_fill", 0, "io")
        for t in self._threads:
            t.join(timeout=0.1)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class ShardedRecordReader:
    """Deterministic rank-sharded iteration over an indexed record file.

    Wraps :class:`~..recordio.MXIndexedRecordIO` and yields
    ``decode_fn(payload)`` for every key in THIS rank's shard
    (``recordio.shard_keys``: interleaved ``keys[rank::num_ranks]``, so
    fleet replicas and elastic re-joins read disjoint, deterministic
    shards with no coordination — the shard is a pure function of
    (keys, rank, num_ranks)).

    Rewindable (``reset()``), so it cycles under the prefetcher; counts
    ``io.records_read`` and exports the shard geometry as gauges. The
    file handle is opened lazily per iteration pass and owned by the
    single reader thread — this class is NOT thread-safe by design (the
    pipeline's parallelism lives in the decode pool, not the reader).
    """

    def __init__(self, idx_path, rec_path, rank=0, num_ranks=1,
                 decode_fn=None, key_type=int):
        from ..recordio import MXIndexedRecordIO, shard_keys
        self._idx_path = idx_path
        self._rec_path = rec_path
        self._key_type = key_type
        self.rank = int(rank)
        self.num_ranks = int(num_ranks)
        self._decode_fn = decode_fn
        self._rec = MXIndexedRecordIO(idx_path, rec_path, "r",
                                      key_type=key_type)
        if not self._rec.keys:
            raise ValueError(f"record file {rec_path!r} has no index "
                             f"({idx_path!r} missing or empty)")
        self.keys = shard_keys(self._rec.keys, self.rank, self.num_ranks)
        self._pos = 0
        self._c_records = _prof.counter("io.records_read", "io")
        _prof.set_gauge("io.shard_rank", self.rank, "io")
        _prof.set_gauge("io.shard_ranks", self.num_ranks, "io")
        _prof.set_gauge("io.shard_records", len(self.keys), "io")

    def __len__(self):
        return len(self.keys)

    def reset(self):
        self._pos = 0

    def __iter__(self):
        return self

    def __next__(self):
        if self._pos >= len(self.keys):
            raise StopIteration
        payload = self._rec.read_idx(self.keys[self._pos])
        self._pos += 1
        self._c_records.increment()
        return payload if self._decode_fn is None \
            else self._decode_fn(payload)

    next = __next__

    def close(self):
        self._rec.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
