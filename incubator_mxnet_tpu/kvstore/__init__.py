"""KVStore (parity: python/mxnet/kvstore.py + src/kvstore/).

The reference aggregates gradients through ps-lite servers or NCCL
(`dist_sync_device`, `src/kvstore/kvstore_dist.h`). TPU-native: aggregation
IS an XLA collective over the device mesh. Two surfaces:

* object API here (init/push/pull/pushpull, server-side optimizer) — keeps
  Trainer/Module code shape-compatible with the reference. Multi-device
  values aggregate through ONE jitted bucketed computation: per-device
  shards are flattened into a single fusion buffer per device (the
  reference's kvstore big-array batching), assembled into a global array
  sharded over a Mesh, and summed with replicated output sharding — XLA
  lowers that to an all-reduce that rides ICI on real hardware;
* the fused path (parallel/trainer_step) inlines a `psum` over the 'dp' mesh
  axis inside the compiled train step — the highest-performance route,
  the one the benchmark times.

`dist_async` semantics (parity: `src/kvstore/kvstore_dist_server.h`): each
worker's push applies as its OWN optimizer update in arrival order — no
cross-worker aggregation barrier, so the server performs num_workers
updates per round and a worker's pull may miss other workers' in-flight
pushes. Here each device slot of a pushed value acts as one virtual
worker. Because a single process has a deterministic arrival order, the
multi-host race is reproduced explicitly: `set_async_staleness(max_delay,
seed)` holds a random subset of pushes back up to `max_delay` rounds
before applying them in shuffled order — the bounded-staleness model of
async PS. `barrier()` drains every pending push (the reference's
Wait/Barrier on the server queue).

Gradient compression (parity: src/kvstore/gradient_compression.cc): `2bit`
quantizes each pushed value to {-threshold, 0, +threshold} with
error-feedback residuals kept per (key, device-slot); `fp16` casts to
half precision for the wire. Unsupported types raise (no silent no-ops).
"""
from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ndarray import NDArray
from .. import healthmon as _hm
from .. import perfscope as _ps
from .. import optimizer as _opt
from .. import profiler as _prof
from ..diagnostics import flight as _flight
from ..diagnostics.memory import logical_nbytes as _logical_nbytes


def _value_nbytes(value) -> int:
    """Logical bytes of an NDArray / (nested) list of NDArrays — the
    always-live `kvstore.*_bytes` counters the metrics exporter scrapes."""
    total = 0
    stack = [value]
    while stack:
        v = stack.pop()
        if isinstance(v, (list, tuple)):
            stack.extend(v)
        elif isinstance(v, NDArray):
            total += _logical_nbytes(v._data)
    return total


def _account(op: str, value) -> None:
    """Count one collective-surface call + its payload bytes, and drop a
    flight-recorder breadcrumb when the ring is live."""
    nb = _value_nbytes(value)
    _prof.counter("kvstore.%s_calls" % op).increment()
    _prof.counter("kvstore.%s_bytes" % op).increment(nb)
    if _flight._REC is not None:
        _flight.record("collective", "kvstore.%s" % op, {"bytes": nb})


def _timed(op: str, fn):
    """Run one collective-surface call, feeding its entry-to-exit wall
    time to the healthmon skew timeline (docs/observability.md) and the
    cumulative ``kvstore.collective_ms`` counter perfscope's step-time
    decomposition reads. The duration includes the cross-rank wait
    inside blocking collectives — exactly the quantity straggler
    attribution and the step budget decompose — and the hook costs two
    predicate checks when both layers are off."""
    hm = _hm._HM
    if hm is None and _ps._PS is None:
        return fn()
    t0 = time.perf_counter()
    try:
        return fn()
    finally:
        ms = (time.perf_counter() - t0) * 1e3
        if hm is not None:
            hm.record_collective(op, ms)
        _prof.counter("kvstore.collective_ms").increment(ms)

__all__ = ["KVStore", "create"]


# --------------------------------------------------------------------------
# Bucketed compiled aggregation
# --------------------------------------------------------------------------

@jax.jit
def _tree_sum(values_per_key):
    """Sum each key's list of same-device arrays in one compiled call.
    jit caches per pytree-structure/shape signature automatically."""
    out = []
    for vals in values_per_key:
        total = vals[0]
        for v in vals[1:]:
            total = total + v
        out.append(total)
    return out


class _BucketedAllReduce:
    """Aggregates many (key -> per-device shards) in one compiled XLA call.

    Strategy (mirrors the reference kvstore's fusion-buffer batching, but
    as a compiled collective instead of server RPCs):
      1. ravel each key's shard and concatenate per device slot into one
         flat fusion buffer (one cached-jit dispatch per device);
      2. assemble the n_dev buffers into a global (n_dev, total) array
         sharded over a 1-axis Mesh of those devices;
      3. jitted sum over the sharded axis with replicated out_shardings —
         XLA inserts the all-reduce — and split/reshape back per key,
         all inside the same compiled computation.

    Compiled callables are cached per (devices, dtype, shapes) signature.
    """

    def __init__(self):
        self._reduce_cache = {}
        self._flatten_cache = {}
        self._lock = threading.Lock()

    def _flatten_fn(self, shapes, dtype):
        key = (shapes, dtype)
        fn = self._flatten_cache.get(key)
        if fn is None:
            def flatten(vals):
                return jnp.concatenate([v.ravel().astype(dtype) for v in vals])
            fn = jax.jit(flatten)
            with self._lock:
                self._flatten_cache[key] = fn
        return fn

    @staticmethod
    def _collective_mesh(devs):
        """The 1-axis mesh the fused all-reduce rides. When the process-
        global sharding mesh (parallel.sharding.set_mesh) is itself a
        single axis over exactly these devices, return THE SAME Mesh
        object — kvstore collectives and the sharded executor share one
        mesh identity (one ICI ring layout, one XLA mesh context)
        instead of each path minting its own. Multi-axis registry meshes
        can't be identity-shared (the reduce needs one flat axis), so
        those fall through to a private mesh and are not counted."""
        from ..parallel import sharding as _sharding
        gm = _sharding.get_mesh()
        if gm is not None and len(gm.axis_names) == 1:
            gdevs = tuple(np.ravel(np.asarray(gm.devices, dtype=object)))
            if gdevs == tuple(devs):
                _prof.counter("kvstore.mesh_reuse").increment()
                return gm
        return Mesh(np.array(devs), ("kv",))

    def _reduce_fn(self, devs, shapes, dtype):
        key = (devs, shapes, dtype)
        hit = self._reduce_cache.get(key)
        if hit is None:
            mesh = self._collective_mesh(devs)
            sizes = [int(np.prod(s)) if s else 1 for s in shapes]
            offs = np.cumsum([0] + sizes)

            def reduce_split(stacked):
                flat = stacked.sum(axis=0)
                return tuple(
                    flat[offs[i]:offs[i + 1]].reshape(shapes[i])
                    for i in range(len(shapes)))

            fn = jax.jit(
                reduce_split,
                out_shardings=tuple(NamedSharding(mesh, P())
                                    for _ in shapes))
            with self._lock:
                self._reduce_cache[key] = (fn, mesh)
            return fn, mesh
        return hit

    def __call__(self, values_per_key):
        """values_per_key: list over keys of lists of jax.Array shards
        (equal length n_dev, consistent device order). Returns list of
        aggregated jax.Array, one per key."""
        n_dev = len(values_per_key[0])
        if n_dev == 1:
            return [v[0] for v in values_per_key]
        dev_slots = [tuple(sorted(v.devices(), key=lambda d: d.id))[0]
                     for v in values_per_key[0]]
        distinct = len(set(dev_slots)) == n_dev
        if not distinct:
            # shared-device shards (e.g. emulated workers on one chip): one
            # fused compiled tree-sum. Coalesce stragglers onto slot 0's
            # device first — jit refuses mixed committed devices.
            if len(set(dev_slots)) > 1:
                dev0 = dev_slots[0]
                values_per_key = [
                    [v if dev0 in v.devices() else jax.device_put(v, dev0)
                     for v in vals]
                    for vals in values_per_key]
            return _tree_sum(values_per_key)

        shapes = tuple(tuple(v[0].shape) for v in values_per_key)
        dtype = jnp.result_type(*[v[0].dtype for v in values_per_key])
        flatten = self._flatten_fn(shapes, dtype)
        bufs = []
        for slot in range(n_dev):
            bufs.append(flatten([v[slot] for v in values_per_key]))
        total = bufs[0].shape[0]
        devs = tuple(dev_slots)
        fn, mesh = self._reduce_fn(devs, shapes, dtype)
        # the mesh may be the reused registry mesh, whose one axis is
        # named dp/ep/… rather than "kv" — shard over whatever it has
        sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
        stacked = jax.make_array_from_single_device_arrays(
            (n_dev, total), sharding,
            [jax.device_put(b, d)[None] for b, d in zip(bufs, devs)])
        return list(fn(stacked))


# --------------------------------------------------------------------------
# Gradient compression (parity: src/kvstore/gradient_compression.cc)
# --------------------------------------------------------------------------

@jax.jit
def _compress_2bit(grad, residual, threshold):
    acc = grad + residual
    q = jnp.where(acc >= threshold, threshold,
                  jnp.where(acc <= -threshold, -threshold, 0.0)
                  ).astype(grad.dtype)
    return q, acc - q


class _AsyncQueue:
    """Arrival-order update queue with induced bounded staleness.

    Models the reference async server (`kvstore_dist_server.h`): pushes
    apply independently, possibly delayed and reordered relative to other
    workers. `max_delay=0` = deterministic arrival order (still per-worker
    updates, the async/sync semantic difference); `max_delay=k` holds a
    random subset of pushes up to k rounds and releases them shuffled,
    reproducing multi-host arrival races reproducibly (seeded).
    """

    def __init__(self, apply_fn, max_delay=0, seed=0):
        self._apply = apply_fn
        self._pending = []      # [age, key, grad]
        self._rng = np.random.RandomState(seed)
        self.max_delay = max_delay
        self.delayed_total = 0  # pushes that were held back at least once
        self.applied_total = 0
        # on rank 0 of a cross-process cluster BOTH the main thread
        # (barrier/flush) and the AsyncPSTransport server thread mutate
        # this queue; unlocked, a push between _drain's iteration and its
        # reassignment of _pending would be silently dropped
        self._qlock = threading.RLock()

    def push(self, key, grad):
        with self._qlock:
            self._pending.append([0, key, grad])
            self._drain(force=False)

    def _drain(self, force):
        with self._qlock:
            now, keep = [], []
            for item in self._pending:
                overdue = item[0] >= self.max_delay
                if force or overdue or self._rng.rand() < 0.5:
                    now.append(item)
                else:
                    if item[0] == 0:
                        self.delayed_total += 1  # distinct pushes held back
                    item[0] += 1
                    keep.append(item)
            self._rng.shuffle(now)
            for _, k, g in now:
                self._apply(k, g)
                self.applied_total += 1
            self._pending = keep

    def flush(self):
        self._drain(force=True)

    @property
    def pending_count(self):
        with self._qlock:
            return len(self._pending)


class KVStore:
    def __init__(self, kv_type="local"):
        self.type = kv_type
        self._store = {}
        self._optimizer = None
        self._states = {}
        self._is_dist = kv_type.startswith("dist")
        self._is_async = kv_type == "dist_async"
        self._compression = None
        self._residuals = {}
        self._allreduce = _BucketedAllReduce()
        self._async_queue = (_AsyncQueue(self._async_apply)
                             if self._is_async else None)
        self._async_ps = None     # cross-process transport, created lazily
        # dist_async flush deadline (seconds); None = transport default
        # (MXTPU_APS_FLUSH_TIMEOUT env or 120 s)
        self.async_flush_timeout = None

    def _ps(self):
        """Cross-process async transport (kvstore/async_ps.py), active
        when this is a dist_async store in a real multi-process cluster.
        Lazy: the store may be created before mx.distributed.init()."""
        if not self._is_async or jax.process_count() <= 1:
            return None
        if self._async_ps is None:
            from .async_ps import AsyncPSTransport
            self._async_ps = AsyncPSTransport(
                self, flush_timeout=self.async_flush_timeout)
        return self._async_ps

    def _async_apply(self, key, grad):
        """Apply target for the async queue: plain keys are this
        process's virtual-worker pushes; (key, rank) tuples were tagged
        by the cross-process server for per-worker accounting."""
        if isinstance(key, tuple):
            self._async_ps._apply(key, grad)
        else:
            self._apply_one_update(key, grad)

    # -- topology ---------------------------------------------------------
    @property
    def rank(self):
        return jax.process_index() if self._is_dist else 0

    @property
    def num_workers(self):
        return jax.process_count() if self._is_dist else 1

    # -- data plane -------------------------------------------------------
    def init(self, key, value):
        if isinstance(key, (list, tuple)):
            for k, v in zip(key, value):
                self.init(k, v)
            return
        self._store[key] = value.copy() if isinstance(value, NDArray) else NDArray(value)
        ps = self._ps()
        if ps is not None:
            # server publishes initial weights; workers block until seen
            ps.publish_init(key, self._store[key].asnumpy())

    def _compress(self, values):
        """Apply gradient compression per device slot with error-feedback
        residuals, before aggregation (the 'wire' stage of the reference)."""
        if self._compression is None:
            return values
        ctype = self._compression["type"]
        if ctype == "fp16":
            return [[v.astype(jnp.float16).astype(v.dtype) for v in vals]
                    for key_i, vals in values]
        threshold = float(self._compression.get("threshold", 0.5))
        out = []
        for key_i, vals in values:
            cvals = []
            for slot, v in enumerate(vals):
                rkey = (key_i, slot)
                r = self._residuals.get(rkey)
                if r is None or r.shape != v.shape:
                    r = jnp.zeros_like(v)
                q, r = _compress_2bit(v, r, jnp.asarray(threshold, v.dtype))
                self._residuals[rkey] = r
                cvals.append(q)
            out.append(cvals)
        return out

    def _batch_aggregate(self, keys, values):
        """Aggregate a batch of keys' multi-device values in one compiled
        bucketed collective. values: list (per key) of NDArray or list of
        NDArray. Returns list of aggregated NDArray."""
        norm = []
        for v in values:
            if isinstance(v, NDArray):
                norm.append([v._data])
            elif len(v) == 0:
                raise ValueError("empty value list in kvstore aggregation")
            else:
                norm.append([x._data for x in v])
        n_dev = len(norm[0])
        if any(len(v) != n_dev for v in norm):
            # ragged: aggregate each key independently
            return [self._batch_aggregate([k], [v])[0]
                    for k, v in zip(keys, values)]
        if self._compression is not None and n_dev > 1:
            norm = self._compress(list(zip(keys, norm)))
        aggs = self._allreduce(norm)
        if self._is_dist and jax.process_count() > 1:
            from jax.experimental import multihost_utils
            aggs = [jnp.sum(multihost_utils.process_allgather(a), axis=0)
                    for a in aggs]
        return [NDArray(a) for a in aggs]

    def _aggregate(self, values, key=None):
        return self._batch_aggregate([key], [values])[0]

    def push(self, key, value, priority=0):
        _account("push", value)
        if _prof._ACTIVE:
            with _prof.Scope("kvstore.push", "kvstore", sync=False):
                return _timed("push",
                              lambda: self._push_impl(key, value, priority))
        return _timed("push", lambda: self._push_impl(key, value, priority))

    def _push_impl(self, key, value, priority=0):
        if self._is_async:
            ps = self._ps()
            keys = key if isinstance(key, (list, tuple)) else [key]
            vals = value if isinstance(key, (list, tuple)) else [value]
            for k, v in zip(keys, vals):
                slots = list(v) if isinstance(v, (list, tuple)) else [v]
                slots = self._compress_slots(k, slots)
                for g in slots:  # each device slot = one virtual worker
                    if ps is not None:
                        # cross-process: ship to the rank-0 server, which
                        # applies it in genuine arrival order
                        ps.push(k, np.asarray(g))
                    else:
                        self._async_queue.push(k, g)
            return
        if isinstance(key, (list, tuple)):
            aggs = self._batch_aggregate(key, value)
            for k, a in zip(key, aggs):
                self._apply_push(k, a)
            return
        self._apply_push(key, self._aggregate(value, key))

    def set_async_staleness(self, max_delay, seed=0):
        """Configure the induced-staleness simulation for `dist_async`
        (see module docstring). max_delay=0 restores deterministic
        arrival order."""
        if not self._is_async:
            raise ValueError("set_async_staleness requires a dist_async "
                             "store, got %r" % self.type)
        self._async_queue.flush()  # don't drop in-flight delayed pushes
        self._async_queue = _AsyncQueue(self._async_apply,
                                        max_delay=max_delay, seed=seed)

    def _apply_one_update(self, key, grad):
        """One worker's push = one server-side update (async semantics)."""
        self._apply_push(key, grad if isinstance(grad, NDArray)
                         else NDArray(grad))

    def _compress_slots(self, key, slots):
        """Wire-stage compression for async per-worker pushes. Single-slot
        pushes skip compression, matching the sync path's n_dev > 1 guard
        (no wire between worker and server)."""
        raws = [s._data if isinstance(s, NDArray) else jnp.asarray(s)
                for s in slots]
        if self._compression is None or len(raws) <= 1:
            return raws
        return self._compress([(key, raws)])[0]

    def _apply_push(self, key, agg):
        if self._optimizer is not None:
            weight = self._store[key]
            if key not in self._states:
                self._states[key] = self._optimizer.create_state_multi_precision(
                    key, weight._data)
            self._states[key] = self._optimizer.update(key, weight, agg,
                                                       self._states[key])
        else:
            if key in self._store:
                self._store[key]._data = self._store[key]._data + agg._data
            else:
                self._store[key] = agg.copy()

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        _account("pull", out)
        if _prof._ACTIVE:
            with _prof.Scope("kvstore.pull", "kvstore", sync=False):
                return _timed("pull", lambda: self._pull_impl(
                    key, out, priority, ignore_sparse))
        return _timed("pull", lambda: self._pull_impl(key, out, priority,
                                                      ignore_sparse))

    def _pull_impl(self, key, out=None, priority=0, ignore_sparse=True):
        if isinstance(key, (list, tuple)):
            for k, o in zip(key, out):
                self._pull_impl(k, o, priority)
            return
        ps = self._ps()
        if ps is not None and ps.rank != 0:
            # CURRENT published server weights — in-flight pushes may be
            # missing, which is the async contract. (Rank 0 reads its own
            # store: the server thread updates it in place, and swapping
            # the entry here would race a concurrent update.)
            src = NDArray(ps.pull(key))
        else:
            src = self._store[key]
        outs = out if isinstance(out, (list, tuple)) else [out]
        for o in outs:
            src.copyto(o)

    def pushpull(self, key, value, out=None, priority=0):
        """Fused allreduce (parity: kv.pushpull in dist_sync_device).
        List-form calls aggregate ALL keys in one compiled bucketed
        collective — the efficient path Trainer uses. In dist_async the
        push applies per-worker server updates and the pull returns the
        CURRENT server weights (which may not yet include delayed
        workers' pushes — the async contract)."""
        _account("pushpull", value)
        if _prof._ACTIVE:
            with _prof.Scope("kvstore.pushpull", "kvstore", sync=False):
                return _timed("pushpull", lambda: self._pushpull_impl(
                    key, value, out, priority))
        return _timed("pushpull", lambda: self._pushpull_impl(
            key, value, out, priority))

    def _pushpull_impl(self, key, value, out=None, priority=0):
        if self._is_async and self._optimizer is not None:
            self._push_impl(key, value)
            if out is not None:
                self._pull_impl(key, out=out)
                return None
            ps = self._ps()
            if ps is not None and ps.rank != 0:
                if isinstance(key, (list, tuple)):
                    return [NDArray(ps.pull(k)) for k in key]
                return NDArray(ps.pull(key))
            if isinstance(key, (list, tuple)):
                return [self._store[k].copy() for k in key]
            return self._store[key].copy()
        if isinstance(key, (list, tuple)):
            aggs = self._batch_aggregate(key, value)
            if out is None:
                return aggs
            for a, o in zip(aggs, out):
                outs = o if isinstance(o, (list, tuple)) else [o]
                for oo in outs:
                    a.copyto(oo)
            return
        agg = self._aggregate(value, key)
        if out is None:
            return agg
        outs = out if isinstance(out, (list, tuple)) else [out]
        for o in outs:
            agg.copyto(o)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the requested rows (parity: reference row_sparse_pull,
        python/mxnet/kvstore.py). `row_ids` selects rows of the stored
        value; result rows appear at their row_id positions (other rows
        zero), matching the reference's RowSparseNDArray densified view."""
        if row_ids is None:
            self.pull(key, out, priority)
            return
        if isinstance(key, (list, tuple)):
            rids = row_ids if isinstance(row_ids, (list, tuple)) else [row_ids] * len(key)
            for k, o, r in zip(key, out, rids):
                self.row_sparse_pull(k, o, priority, r)
            return
        src = self._store[key]
        ids = row_ids._data if isinstance(row_ids, NDArray) else jnp.asarray(row_ids)
        ids_np = np.unique(np.asarray(ids).astype(np.int64).ravel())
        rows = jnp.take(src._data, jnp.asarray(ids_np), axis=0)
        if out is None:
            from ..ndarray import sparse as _sparse
            return _sparse.RowSparseNDArray(rows, ids_np, src.shape)
        dense = jnp.zeros_like(src._data).at[jnp.asarray(ids_np)].set(rows)
        outs = out if isinstance(out, (list, tuple)) else [out]
        for o in outs:
            NDArray(dense).copyto(o)

    # -- server-side optimizer --------------------------------------------
    def set_optimizer(self, optimizer):
        self._optimizer = (_opt.create(optimizer)
                           if isinstance(optimizer, str) else optimizer)

    def is_capable(self, capability):
        return capability in ("optimizer",)

    def set_gradient_compression(self, compression_params):
        ctype = (compression_params or {}).get("type")
        if ctype not in ("2bit", "fp16"):
            raise ValueError(
                f"unsupported gradient compression type {ctype!r}: "
                "supported are '2bit' (error-feedback sign quantization, "
                "parity: src/kvstore/gradient_compression.cc) and 'fp16'")
        self._compression = dict(compression_params)
        self._residuals = {}

    def save_optimizer_states(self, fname, dump_optimizer=False):
        import pickle
        blob = {k: jax.tree_util.tree_map(lambda a: np.asarray(a), v)
                for k, v in self._states.items()}
        with open(fname, "wb") as f:
            pickle.dump(blob, f)

    def load_optimizer_states(self, fname):
        import pickle
        with open(fname, "rb") as f:
            blob = pickle.load(f)
        self._states = {k: jax.tree_util.tree_map(jnp.asarray, v)
                        for k, v in blob.items()}

    def async_applied_counts(self):
        """dist_async: per-worker counts of server-applied updates.
        Cross-process these come from the rank-0 server's published
        accounting; single-process, all pushes are worker 0's."""
        if not self._is_async:
            raise ValueError("async_applied_counts requires dist_async")
        ps = self._ps()
        if ps is not None:
            return ps.applied_counts()
        return {0: self._async_queue.applied_total}

    def barrier(self):
        ps = self._ps() if self._is_async else None
        if ps is not None:
            # wait until MY pushes are all server-applied, then rendezvous
            # with the other workers (reference: Barrier on the server).
            # The deadline is read here, not at transport construction, so
            # adjusting kv.async_flush_timeout mid-run takes effect.
            ps.flush(timeout=self.async_flush_timeout)
            from .. import distributed
            distributed.barrier("mxtpu_kv_barrier")
        if self._async_queue is not None:
            self._async_queue.flush()  # drain in-flight async pushes
        from ..ndarray import waitall
        waitall()


def create(name="local") -> KVStore:
    if name not in ("local", "device", "dist_sync", "dist_sync_device",
                    "dist_async", "dist_device_sync"):
        raise ValueError(f"unknown kvstore type {name!r}")
    return KVStore(name)
