"""gluon.Trainer (parity: python/mxnet/gluon/trainer.py).

step() = allreduce_grads() (kvstore) + update() (optimizer), as in the
reference. Each parameter's update is one jitted XLA kernel; the fully-fused
single-computation train step (forward+backward+psum+update in one jit) lives
in parallel/ and is what the benchmark times.
"""
from __future__ import annotations

import os
import pickle

import jax
import numpy as np

from .. import healthmon as _hm
from .. import kvstore as kvs_mod
from .. import optimizer as opt_mod
from .. import profiler as _prof
from ..diagnostics import flight as _flight
from ..ndarray import NDArray
from .parameter import Parameter, ParameterDict

__all__ = ["Trainer"]


class _GradCommScheduler:
    """ByteScheduler-style priority scheduler for gradient aggregation
    (reference: ps-lite push/pull pipelining in src/kvstore/kvstore_dist.h
    and the BytePS/ByteScheduler papers the ymjiang fork exists for).

    Semantics rebuilt TPU-native:

    * **readiness** — parameters' grad hooks fire mid-backward the moment
      each gradient is finalized (reverse layer order), not at step();
    * **priority** — forward-order parameter index, ascending: the next
      iteration's forward is unblocked by the FRONT layers, so when
      several buckets are ready the front-most is issued first;
    * **overlap** — each issued aggregation is an XLA computation that
      dispatches asynchronously, so device collective work runs while the
      host continues the remaining backward walk (the reference overlaps
      NCCL/ps-lite transfers the same way);
    * **credit** — at most ``credit_bytes`` of aggregation may be in
      flight (completion polled via ``jax.Array.is_ready``); when credit
      is exhausted, ready buckets wait in a priority heap — so a
      front-layer gradient arriving later OVERTAKES queued lower-priority
      buckets, which is the ByteScheduler reordering;
    * **bucketing** — consecutive parameters are grouped into ~``
      bucket_bytes`` buckets (0 = one bucket per parameter); a bucket
      issues once every member's grad is ready.

    ``step()`` calls ``flush()`` which force-issues stragglers (params
    that never fired — e.g. unused this pass) and drains the heap, so the
    result is always bit-identical to the unscheduled batched path.
    """

    def __init__(self, kvstore, params, bucket_bytes=0,
                 credit_bytes=4 << 20):
        self._kv = kvstore
        self._params = params
        self._bucket_bytes = int(bucket_bytes)
        self._credit = int(credit_bytes)
        # SPMD safety: when aggregation is a cross-process collective
        # (process_allgather in _batch_aggregate), EVERY process must
        # issue buckets in the SAME order — credit-based overtaking
        # depends on local is_ready() timing and would mispair the
        # collectives. Multi-process clusters therefore issue strictly in
        # (deterministic) availability order; overlap is kept, only the
        # reordering is dropped. Single-process keeps full ByteScheduler
        # semantics.
        self._deterministic = jax.process_count() > 1
        self._buckets = []           # list[list[int]] consecutive indices
        self._bucket_of = {}         # param idx -> bucket idx
        self._rebucket()
        self._ready = set()          # param indices with finalized grads
        self._issued = set()         # bucket indices already issued
        self._heap = []              # [(priority, bucket_idx)]
        self._inflight = []          # [(nbytes, [jax.Array])]
        self.issued_log = []         # bucket priority order (tests/debug)

    def _rebucket(self):
        self._buckets, self._bucket_of = [], {}
        cur, cur_bytes = [], 0
        for i, p in enumerate(self._params):
            itemsize = np.dtype(p.dtype).itemsize if p.dtype else 4
            nbytes = (itemsize * int(np.prod(p.shape))
                      if p.shape_is_known else 0)
            cur.append(i)
            cur_bytes += nbytes
            if self._bucket_bytes <= 0 or cur_bytes >= self._bucket_bytes:
                self._buckets.append(cur)
                cur, cur_bytes = [], 0
        if cur:
            self._buckets.append(cur)
        for b, members in enumerate(self._buckets):
            for i in members:
                self._bucket_of[i] = b

    # -- readiness --------------------------------------------------------
    def notify(self, i):
        """Param i's grad finalized mid-backward: queue its bucket when
        complete, then drain as much as credit allows."""
        import heapq
        if self._kv.num_workers <= 1:
            return                    # nothing to aggregate: keep backward hot
        b = self._bucket_of[i]
        if i in self._ready or b in self._issued:
            # a SECOND finalization before step(): the bucket's aggregated
            # value is already (or about to be) replaced by the collective,
            # so re-aggregating would double-count the earlier contribution
            # across workers. Real overlapped schedulers (BytePS) share
            # this one-push-per-iteration contract.
            raise RuntimeError(
                "overlap_comm saw a second backward pass before the "
                "scheduler was flushed; gradient accumulation across "
                "multiple backwards is not compatible with mid-backward "
                "aggregation — after each backward call step(), or "
                "allreduce_grads() followed by update(), or construct "
                "the Trainer with overlap_comm=False")
        self._ready.add(i)
        if all(j in self._ready for j in self._buckets[b]):
            heapq.heappush(self._heap, (self._buckets[b][0], b))
            self._issued.add(b)
        self._drain(force=self._deterministic)

    # -- issue ------------------------------------------------------------
    def _inflight_bytes(self):
        self._inflight = [(n, arrs) for n, arrs in self._inflight
                          if not all(a.is_ready() for a in arrs)]
        return sum(n for n, _ in self._inflight)

    def _issue(self, b):
        members = self._buckets[b]
        grads = [self._params[i].grad() for i in members]
        keys = [f"grad{i}" for i in members]
        if _prof._ACTIVE:
            with _prof.Scope("overlap_comm.issue_bucket%d" % b, "trainer",
                             sync=False):
                self._kv.pushpull(keys, grads, out=grads)
        else:
            self._kv.pushpull(keys, grads, out=grads)
        self.issued_log.append(b)
        nbytes = sum(int(np.prod(g.shape)) * g._data.dtype.itemsize
                     for g in grads)
        self._inflight.append((nbytes, [g._data for g in grads]))

    def _drain(self, force):
        import heapq
        while self._heap:
            if not force and self._inflight_bytes() >= self._credit:
                return
            _, b = heapq.heappop(self._heap)
            self._issue(b)

    def flush(self):
        """step(): issue stragglers (whole-bucket, priority order) and
        drain the heap unconditionally; afterwards every param's .grad()
        holds the aggregated value, as the batched path would.

        issued_log is reset here (start of flush) so it never grows across
        steps: after step() it holds exactly this flush's issuance order;
        mid-backward issuance is readable between backward() and step()."""
        import heapq
        self.issued_log.clear()
        if self._kv.num_workers <= 1:
            return
        # EVERY bucket not yet issued goes now — including ones whose
        # hooks never fired (deferred-init params, partial buckets): the
        # batched path aggregates all params, and parity is the contract
        for b, members in enumerate(self._buckets):
            if b not in self._issued:
                heapq.heappush(self._heap, (members[0], b))
                self._issued.add(b)
        self._drain(force=True)
        self._ready.clear()
        self._issued.clear()
        self._inflight.clear()

    def reset(self):
        """Drop all per-pass state WITHOUT issuing anything. update()
        calls this when the user skipped allreduce_grads(): whatever was
        already issued mid-backward stays aggregated (that money is
        spent), but nothing further is launched — crucially the next
        backward starts from a clean slate instead of tripping notify()'s
        second-backward guard with a misleading error."""
        self._ready.clear()
        self._issued.clear()
        self._heap.clear()
        self._inflight.clear()
        self.issued_log.clear()


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None,
                 overlap_comm=False, comm_bucket_bytes=0,
                 comm_credit_bytes=4 << 20, fused_update=None,
                 loop_chunk=None, sharding=None, resilience=None):
        if isinstance(params, (dict, ParameterDict)):
            params = [params[k] for k in sorted(params.keys())] \
                if isinstance(params, dict) else list(params.values())
        self._params = [p for p in params if p.grad_req != "null"]
        self._all_params = list(params)
        param_dict = {i: p for i, p in enumerate(self._params)}
        optimizer_params = optimizer_params or {}
        if isinstance(optimizer, opt_mod.Optimizer):
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt_mod.create(optimizer, param_dict=param_dict,
                                             **optimizer_params)
        self._states = [None] * len(self._params)
        self._states_created = [False] * len(self._params)
        self._kvstore = None
        if kvstore is not None:
            self._kvstore = (kvstore if isinstance(kvstore, kvs_mod.KVStore)
                             else kvs_mod.create(kvstore))
        self._scale = 1.0
        # update_on_kvstore (parity: reference trainer's
        # _update_on_kvstore): the optimizer runs SERVER-side — step()
        # pushes gradients and pulls back updated weights; update() is
        # then unsupported. Auto (None) resolves True only for
        # `dist_async`, whose per-worker-update semantics only exist
        # server-side; everywhere else the local fused update is the
        # faster TPU-native path.
        if update_on_kvstore is None:
            update_on_kvstore = (self._kvstore is not None
                                 and self._kvstore.type == "dist_async")
        if update_on_kvstore and self._kvstore is None:
            raise ValueError("update_on_kvstore=True requires a kvstore")
        if update_on_kvstore and overlap_comm:
            raise ValueError(
                "overlap_comm schedules client-side aggregation; it is "
                "incompatible with server-side updates "
                "(update_on_kvstore)")
        self._update_on_kvstore = bool(update_on_kvstore)
        # fused multi-tensor apply: group params by (rule, dtype) and run
        # each group's updates as ONE jitted call (vs one call per param).
        # Default on; env MXTPU_FUSED_UPDATE=0 disables globally.
        if fused_update is None:
            from ..settings import env_flag
            fused_update = env_flag("MXTPU_FUSED_UPDATE", True)
        self._fused_update = bool(fused_update)
        # loop_chunk=N marks this trainer for WHOLE-LOOP execution: the
        # trainloop executor (mxtpu.trainloop.TrainLoop) compiles N
        # micro-steps (fwd+bwd+collective+update+lr schedule) into one
        # donated XLA program and reads this chunk size when constructed
        # from the Trainer. Env default: MXTPU_LOOP_CHUNK (settings);
        # an explicit loop_chunk= argument wins.
        # The eager step()/update() path ignores it (per-step by
        # construction).
        if loop_chunk is None:
            from .. import settings as _settings
            loop_chunk = _settings.resolve("loop_chunk")[0]
        self.loop_chunk = int(loop_chunk) if loop_chunk else None
        # sharding='dp'|'fsdp'|'auto' marks this trainer for MESH-NATIVE
        # execution (mxtpu.sharding, docs/sharding.md): TrainLoop /
        # FusedTrainStep constructed from this Trainer lower fwd+bwd+
        # optimizer into ONE jit whose in/out shardings carry the
        # resolved per-param NamedShardings — XLA inserts the
        # collectives, replacing kvstore pushpull on that path. The
        # eager step()/update() path ignores it (kvstore aggregation
        # stays). Env default: MXTPU_SHARDING. Needs a process-global
        # mesh (sharding.set_mesh) or an explicit mesh= at the executor.
        if sharding is None:
            from ..settings import env_str
            sharding = env_str("MXTPU_SHARDING", None)
        from ..parallel import sharding as _sharding_mod
        if sharding is not None and sharding not in _sharding_mod.MODES:
            raise ValueError(f"unknown sharding mode {sharding!r}; "
                             f"expected one of {_sharding_mod.MODES}")
        self.sharding = sharding
        # resilience=<checkpoint dir> marks this trainer for SUPERVISED
        # recovery (mxtpu.resilience, docs/resilience.md): TrainLoop.fit
        # constructed from this Trainer checkpoints asynchronously into
        # the directory, resumes from its manifest on restart, and rolls
        # back on NaN instead of dying. Env default: MXTPU_RESILIENCE_DIR.
        # The eager step()/update() path ignores it.
        if resilience is None:
            from ..settings import env_str
            resilience = env_str("MXTPU_RESILIENCE_DIR", None)
        self.resilience = resilience
        self._kv_params_init = False
        self._sched = None
        if overlap_comm:
            if self._kvstore is None:
                raise ValueError("overlap_comm=True requires a kvstore")
            self._sched = _GradCommScheduler(
                self._kvstore, self._params,
                bucket_bytes=comm_bucket_bytes,
                credit_bytes=comm_credit_bytes)
            self._ensure_grad_hooks()

    def _ensure_grad_hooks(self):
        """Attach readiness hooks to every initialized param; deferred-init
        params get theirs on a later call (their first backward simply
        falls back to flush-time aggregation — numerics are unchanged).
        Keyed on the parameter's CURRENT storage, not a one-shot latch:
        initialize(force_reinit=True) and cast() replace `p._data` (and
        with it the hook slot), so hooks are re-attached whenever the live
        storage has none — overlap survives re-init instead of silently
        degrading to flush-time aggregation."""
        sched = self._sched
        for i, p in enumerate(self._params):
            if p._data is not None and p._data._grad_hook is None:
                p.register_grad_hook(
                    lambda _p, _i=i: sched.notify(_i))

    # -- properties -------------------------------------------------------
    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # -- core -------------------------------------------------------------
    def _init_state(self, i, p):
        if not self._states_created[i]:
            self._states[i] = self._optimizer.create_state_multi_precision(
                i, p.data()._data)
            self._states_created[i] = True

    def allreduce_grads(self):
        """Aggregate gradients across devices/workers. Single-chip: no-op.
        The mesh path does this inside the compiled step via psum."""
        if self._sched is not None:
            # overlapped path: most buckets were issued mid-backward by
            # grad hooks; flush issues stragglers and resets the pass
            self._ensure_grad_hooks()
            self._sched.flush()
            return
        if self._kvstore is not None and self._kvstore.num_workers > 1:
            grads = [p.grad() for p in self._params]
            keys = [f"grad{i}" for i in range(len(grads))]
            # one batched call → one compiled bucketed collective
            self._kvstore.pushpull(keys, grads, out=grads)

    def step(self, batch_size, ignore_stale_grad=False):
        hm = _hm._HM
        if hm is not None:
            hm.step_begin()
        if _flight._REC is not None:
            _flight.record("trainer", "trainer.step",
                           {"batch_size": int(batch_size)})
        self._optimizer.rescale_grad = self._scale / batch_size
        if self._update_on_kvstore:
            if _prof._ACTIVE:
                with _prof.Scope("trainer.kvstore_step", "trainer",
                                 sync=False):
                    self._kvstore_step()
            else:
                self._kvstore_step()
            if hm is not None:
                # grad-norm sentinel BEFORE step_end: the kvstore step
                # left this worker's grads untouched, and step_end's
                # periodic exchange should see the freshest NaN verdict
                hm.maybe_check_grad_norm(self._params)
                hm.step_end(kv=self._kvstore, batch_size=batch_size)
            return
        phases = None
        if hm is not None:
            # healthmon step phases (cheap wall timing, on whether or not
            # a trace session is running — the event log is the consumer)
            import time as _time
            t0 = _time.perf_counter()
        if _prof._ACTIVE:
            # step phases as separate trace buckets: grad aggregation
            # (incl. overlap-comm stragglers) vs the optimizer update
            _prof.counter("trainer.steps").increment()
            with _prof.Scope("trainer.allreduce_grads", "trainer",
                             sync=False):
                self.allreduce_grads()
            if hm is not None:
                t1 = _time.perf_counter()
            with _prof.Scope("trainer.optimizer_update", "trainer",
                             sync=False):
                self._update()
        else:
            self.allreduce_grads()
            if hm is not None:
                t1 = _time.perf_counter()
            self._update()
        if hm is not None:
            t2 = _time.perf_counter()
            phases = {"allreduce_ms": (t1 - t0) * 1e3,
                      "update_ms": (t2 - t1) * 1e3}
            # grads survive _update (it only reads them), so the opt-in
            # global-norm sentinel runs on exactly what was applied
            hm.maybe_check_grad_norm(self._params)
            hm.step_end(kv=self._kvstore, batch_size=batch_size,
                        phases=phases)

    def _kvstore_step(self):
        """Server-side update round: push grads, pull updated weights
        (reference kvstore_dist flow). For dist_async the push applies as
        this worker's own arrival-order update on the rank-0 server; for
        sync stores it is aggregate-then-update."""
        kv = self._kvstore
        keys = [f"param{i}" for i in range(len(self._params))]
        if not self._kv_params_init:
            kv.set_optimizer(self._optimizer)
            kv.init(keys, [p.data() for p in self._params])
            self._kv_params_init = True
        kv.push(keys, [p.grad() for p in self._params])
        kv.pull(keys, out=[p.data() for p in self._params])

    def update(self, batch_size, ignore_stale_grad=False):
        if self._update_on_kvstore:
            raise ValueError(
                "update() is not supported when parameters are updated "
                "on the kvstore (update_on_kvstore=True); call step()")
        if self._sched is not None:
            # update() without allreduce_grads() must not leave the
            # overlap scheduler's _ready/_issued sets stale — the next
            # backward's first grad hook would raise the (misleading)
            # second-backward error. A correct allreduce_grads()+update()
            # sequence already flushed, so this reset is a no-op there;
            # re-flushing here instead would double-aggregate.
            self._sched.reset()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update()

    def _update(self):
        from .. import bulk as _bulk
        # grads/weights must be concrete before the optimizer reads them
        # (unconditional: cheap thread-local check, and a pending segment
        # can outlive its scope on this thread)
        _bulk.flush("step")
        skip = getattr(self, "_amp_skip", None)  # on-device found-inf bool
        opt = self._optimizer
        dispatches = 0
        if not (self._fused_update and opt.supports_fused()):
            for i, p in enumerate(self._params):
                self._init_state(i, p)
                self._states[i] = opt.update(i, p.data(), p.grad(),
                                             self._states[i], skip=skip)
                dispatches += 1
            _prof.set_gauge("optimizer.fused_groups", 0)
            _prof.set_gauge("trainer.dispatches_per_step", dispatches)
            _prof.counter("optimizer.dispatches").increment(dispatches)
            return
        from ..ndarray import sparse as _sparse
        groups = {}   # dtype str -> param indices (one rule per Trainer)
        for i, p in enumerate(self._params):
            self._init_state(i, p)
            g = p.grad()
            if isinstance(g, _sparse.RowSparseNDArray):
                # sparse rules keep the per-param (lazy-row) path
                self._states[i] = opt.update(i, p.data(), g,
                                             self._states[i], skip=skip)
                dispatches += 1
            else:
                groups.setdefault(str(p.data()._data.dtype), []).append(i)
        for idxs in groups.values():
            new_states = opt.fused_update(
                idxs,
                [self._params[i].data() for i in idxs],
                [self._params[i].grad() for i in idxs],
                [self._states[i] for i in idxs], skip=skip)
            for i, s in zip(idxs, new_states):
                self._states[i] = s
            dispatches += 1
        _prof.set_gauge("optimizer.fused_groups", len(groups))
        _prof.set_gauge("trainer.dispatches_per_step", dispatches)
        _prof.counter("optimizer.dispatches").increment(dispatches)

    # -- persistence ------------------------------------------------------
    def save_states(self, fname):
        blob = {
            "num_update": self._optimizer.num_update,
            "index_update_count": dict(self._optimizer._index_update_count),
            "states": [jax.tree_util.tree_map(lambda a: np.asarray(a), s)
                       for s in self._states],
        }
        with open(fname, "wb") as f:
            pickle.dump(blob, f, protocol=4)

    def load_states(self, fname):
        import jax.numpy as jnp
        with open(fname, "rb") as f:
            blob = pickle.load(f)
        self._optimizer.num_update = blob["num_update"]
        self._optimizer._index_update_count = dict(blob.get("index_update_count", {}))
        self._states = [jax.tree_util.tree_map(jnp.asarray, s)
                        for s in blob["states"]]
        self._states_created = [s is not None for s in self._states]
