"""FusedTrainStep: forward + backward + collective + optimizer in ONE XLA
computation.

This is the TPU replacement for the reference's hot loop (CachedOp fwd/bwd +
kvstore pushpull + per-weight optimizer kernels): everything fuses into a
single executable, gradients never round-trip to Python, and with a Mesh the
gradient all-reduce over the 'dp' axis is inserted by XLA and rides ICI —
the NCCL ring of `kvstore=dist_sync_device`, compiled away.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import autograd
from .. import perfscope as _ps
from .. import profiler as _prof
from ..gluon.parameter import _ParamTraceScope, _trace
from ..gluon.trainer import Trainer
from ..io.pipeline import TRANSFER_GATE as _TRANSFER_GATE
from ..io.pipeline import _defer_put_needed as _cpu_serial_client
from ..ndarray import NDArray
from ..ndarray import random as ndrandom
from ..ops import select as _select
from .. import optimizer as opt_mod
from . import fsdp as _fsdp
from . import sharding as _sharding

__all__ = ["FusedTrainStep"]


def _memscope_oom(exc, program, step):
    """Attribute an escaping allocator failure before it propagates:
    when memscope is armed and ``exc`` matches the RESOURCE_EXHAUSTED
    taxonomy, the OOM post-mortem (this program's static footprint,
    watermark tail, top-K ledger buffers, resolved knobs) lands on the
    alert surfaces; the caller re-raises the original error unchanged
    either way. One predicate when memscope is off; never raises."""
    try:
        from .. import memscope as _ms
        if _ms._MS is not None:
            _ms.record_oom(exc, program=program, step=step)
    except Exception:  # noqa: BLE001 — forensics never masks the error
        pass


def _memscope_analytic(step):
    """Hand memscope the FSDP analytic per-device byte budget so its
    reconciliation can check the sharding claim (e.g. the 3.3x
    param-memory reduction) against measured watermarks. Fires once per
    built step, only under fsdp, only when memscope is armed; never
    raises."""
    try:
        from .. import memscope as _ms
        if _ms._MS is not None and step.sharding == "fsdp":
            _ms.register_analytic(_fsdp.memory_report(step))
    except Exception:  # noqa: BLE001 — telemetry never breaks the step
        pass


@contextmanager
def _donated_cache_quarantine(step):
    """Suppress persistent-compile-cache READS while a donating fused
    step may compile on XLA:CPU.

    PR 4 found this jaxlib mis-deserializes cached donated fused-step
    executables; runtime/cache_guard re-entered the cache behind a
    once-per-process canary. PR 17's flake hunt showed the corruption
    is PROBABILISTIC PER READ — one certified read proves nothing
    about the next. So donated executables never read the cache at
    all: the dispatch call that may trigger their compile runs under
    cache_guard's read quarantine (a forced cache miss — full story in
    runtime/cache_guard.py). Scoped to donate+CPU; non-donated reads
    stay canary-guarded and keep the suite's warm-start win."""
    if not (step.donate and _cpu_serial_client()):
        yield
        return
    from ..runtime.cache_guard import donated_read_quarantine
    with donated_read_quarantine():
        yield


class FusedTrainStep:
    """Compile net+loss+optimizer into one train step.

    step = FusedTrainStep(net, loss_fn, trainer, mesh=mesh)   # or optimizer
    loss = step(x, y)    # NDArray scalar; params updated in place
    """

    def __init__(self, net, loss_fn, optimizer, mesh: Mesh | None = None,
                 data_axis: str | None = None, donate: bool = True,
                 remat: bool = False, remat_policy: str | None = None,
                 shard_optimizer_states: bool = False,
                 schedule_in_program: bool = False,
                 sharding: str | None = None):
        """remat=True rematerializes the forward during backward
        (jax.checkpoint with the dots-saveable policy) — the TPU-native
        form of the reference's memonger/mirror_stage memory trade:
        activations are recomputed instead of stored, buying batch size /
        sequence length for ~1/3 extra FLOPs, with matmul outputs still
        saved so the MXU work is not repeated. remat_policy picks the
        checkpoint policy: "dots" (default — matmul outputs saved),
        "nothing" (recompute everything: max memory savings, max extra
        FLOPs), "everything" (save all: remat becomes a no-op knob for
        A/B sweeps).

        shard_optimizer_states=True shards each optimizer-state tensor's
        leading axis over the data-parallel mesh axis (ZeRO-1: momentum/
        variance live once across the dp group instead of replicated,
        cutting optimizer memory by the dp degree). Pure layout change —
        GSPMD inserts the collectives; the math is bit-identical. Needs a
        mesh; states whose leading dim doesn't divide the axis stay
        replicated.

        schedule_in_program=True compiles the optimizer's lr schedule
        INTO the k-step program (lr_scheduler.as_jax closed form) so each
        micro-step computes its own lr from the on-device step counter —
        the host never touches the scheduler inside a chunk. Falls back
        to the host-sampled per-micro-step lr table when the scheduler
        has no closed form; either way run_k matches a sequential loop
        step-for-step (the k-granularity coarsening is gone).

        sharding='dp'|'fsdp'|'auto' picks the parallelism policy
        (mxtpu.sharding, docs/sharding.md): 'dp' replicates params and
        shards the batch over the data axis; 'fsdp' additionally shards
        unannotated params AND optimizer states over the data axis
        (all-gathered in-program by XLA — zero-style; same math, losses
        within ~1 ulp/step of the replicated run since the collective's
        reduction order is the compiler's); 'auto' first applies the
        default rule table to the net
        (Dense kernels / Embedding tables onto the model axis when the
        mesh has one). Defaults: the Trainer's `sharding=` flag when one
        is passed as `optimizer`, else $MXTPU_SHARDING, else 'dp'. With
        no mesh (explicit or process-global via sharding.set_mesh) the
        mode is a single-device no-op. Explicit Parameter annotations
        (Block.shard / logical axis rules) are honored in EVERY mode."""
        self.net = net
        self.loss_fn = loss_fn
        if isinstance(optimizer, Trainer):
            if sharding is None:
                sharding = getattr(optimizer, "sharding", None)
            self.optimizer = optimizer.optimizer
        elif isinstance(optimizer, str):
            self.optimizer = opt_mod.create(optimizer)
        else:
            self.optimizer = optimizer
        if sharding is None:
            from ..settings import env_str
            sharding = env_str("MXTPU_SHARDING", None)
        if sharding is not None and sharding not in _sharding.MODES:
            raise ValueError(f"unknown sharding mode {sharding!r}; "
                             f"expected one of {_sharding.MODES}")
        if mesh is None:
            mesh = _sharding.get_mesh()
        self.mesh = mesh
        self.sharding = (sharding or "dp") if mesh is not None else None
        if data_axis is None:
            data_axis = (_sharding.data_axis(mesh) or "dp") \
                if mesh is not None else "dp"
        self.data_axis = data_axis
        self.donate = donate
        self.remat = remat
        self.remat_policy = remat_policy
        self.schedule_in_program = schedule_in_program
        if self.sharding == "fsdp":
            # FSDP subsumes ZeRO-1: states follow their (dp-sharded)
            # weights; the zero1 flag additionally shards states of any
            # still-replicated weight
            shard_optimizer_states = True
        self.shard_optimizer_states = shard_optimizer_states and mesh is not None
        self._stats_published = False
        self._auto_specs = {}     # sharding='auto': ephemeral defaults
        self._jitted = None
        self._jitted_k = None
        self._stacked_sharding = None   # set by _build_k under a mesh
        self._lr_program = None   # traceable fn(t)->lr, set in _build_k
        self._lr_dummy = {}       # k -> cached zeros(k) placeholder table
        self._lr_const = {}       # k -> (lr, cached constant (k,) table)
        self._num_update = 0
        self.params = None      # resolved at first call (after deferred init)
        self._states = None
        self._scalar_cache = {}   # hyper name -> (float, device scalar)
        self._cost_analyzed = {}   # perfscope: name -> batch signature

    def _f32(self, name, v):
        """Device scalar for a hyperparameter, one slot per name: lr/wd/
        rescale rarely change, and re-uploading three host scalars every
        step is measurable host latency on the dispatch path. A
        per-step-varying scheduler just overwrites its slot (O(1) memory,
        never evicts the constant hyperparameters)."""
        v = float(v)
        hit = self._scalar_cache.get(name)
        if hit is None or hit[0] != v:
            hit = (v, jnp.float32(v))
            self._scalar_cache[name] = hit
        return hit[1]

    # -- setup ------------------------------------------------------------
    def _resolve(self, x, y):
        # persistent-compile-cache integrity canary (runtime/cache_guard):
        # this jaxlib has mis-deserialized donated fused-step executables
        # written by a previous process (PR 4); the canary validates the
        # cache READ path once per process and disables the cache on
        # corruption instead of letting the step train on garbage
        from ..runtime import cache_guard as _cg
        _cg.check()
        # 'auto' defaults (Dense kernels / Embedding tables onto the
        # model axis) are resolved EPHEMERALLY — the net's own
        # annotations are never mutated, so a later sharding='dp' build
        # of the same net stays replicated
        self._auto_specs = (_sharding.auto_specs(self.net)
                            if self.sharding == "auto" else {})
        # one eager pass completes deferred shapes
        try:
            all_params = list(self.net.collect_params().values())
            for p in all_params:
                p.data()
        except Exception:
            with autograd.pause(False):
                self.net(x)
            all_params = list(self.net.collect_params().values())
        self.params = all_params
        self.train_idx = [i for i, p in enumerate(all_params) if p.grad_req != "null"]
        self.aux_idx = [i for i, p in enumerate(all_params) if p.grad_req == "null"]
        self.lr_mults = [all_params[i].lr_mult for i in self.train_idx]
        self.wd_mults = [all_params[i].wd_mult for i in self.train_idx]
        self._states = [self.optimizer.create_state_multi_precision(
            i, all_params[i].data()._data) for i in self.train_idx]
        self._build(x, y)

    def _build(self, x, y):
        net, loss_fn, optimizer = self.net, self.loss_fn, self.optimizer
        params = self.params
        train_idx, aux_idx = self.train_idx, self.aux_idx
        lr_mults, wd_mults = self.lr_mults, self.wd_mults
        ids = [id(p) for p in params]
        aux_ids = [id(params[i]) for i in aux_idx]

        # Named `train_step`, not `step_fn` as before the step's operations
        # had owners: the compile cache's key leaves metadata out, so under
        # the old name a cache that holds the same program without names
        # (written by an older checkout) would serve it, and a profile of
        # this step would show no owner at all.
        def train_step(train_raws, aux_raws, states, key, lr, wd, t, rescale,
                       xb, yb):
            def loss_of(train_raws_):
                sub = {}
                for j, i in enumerate(train_idx):
                    sub[ids[i]] = train_raws_[j]
                for j, i in enumerate(aux_idx):
                    sub[ids[i]] = aux_raws[j]
                with _ParamTraceScope(sub), autograd._Scope(False, True), \
                        ndrandom._TraceKeyScope(key), \
                        _select.partitioned(self.mesh):
                    # owners of the step's operations in a device trace
                    # (docs/profiler.md): the net by its name (its
                    # children add their own in Block.__call__), then
                    # `loss`; jax itself marks forward and backward
                    with jax.named_scope(net.name):
                        out = net.forward(NDArray(xb))
                    with jax.named_scope("loss"):
                        loss = loss_fn(out, NDArray(yb))
                        loss_raw = jnp.mean(loss._data)
                    aux_new = [ _trace.aux_updates.get(aid, aux_raws[j])
                                for j, aid in enumerate(aux_ids)]
                return loss_raw, aux_new

            if self.remat:
                policies = {
                    None: jax.checkpoint_policies
                              .dots_with_no_batch_dims_saveable,
                    "dots": jax.checkpoint_policies
                               .dots_with_no_batch_dims_saveable,
                    "nothing": None,       # recompute everything
                    "everything": jax.checkpoint_policies.everything_saveable,
                }
                try:
                    policy = policies[self.remat_policy]
                except KeyError:
                    raise ValueError(
                        f"unknown remat_policy {self.remat_policy!r}; "
                        f"expected one of {sorted(k for k in policies if k)}"
                    ) from None
                loss_of = jax.checkpoint(loss_of, policy=policy)
            (loss, aux_new), grads = jax.value_and_grad(
                loss_of, has_aux=True)(train_raws)
            new_train, new_states = [], []
            with jax.named_scope("optimizer"):
                for j in range(len(train_raws)):
                    nw, ns = optimizer.update_step(
                        train_raws[j], grads[j], states[j],
                        lr * lr_mults[j], wd * wd_mults[j], t,
                        rescale=rescale,
                        clip=optimizer.clip_gradient)
                    new_train.append(nw)
                    new_states.append(ns)
            return loss, new_train, aux_new, new_states

        self._step_fn = train_step
        kwargs = {}
        self._sharding_info = None
        if self.mesh is not None:
            # batch over the data axis (replicated on a pure-mp mesh)
            batch_spec = (P(self.data_axis)
                          if self.data_axis in self.mesh.shape else P())
            batch_sharding = NamedSharding(self.mesh, batch_spec)
            repl = NamedSharding(self.mesh, P())

            # annotation resolution moved to mxtpu.sharding: logical axis
            # names map through the active rule table, and a dim that
            # doesn't divide the mesh axis (e.g. unpadded vocab under mp)
            # falls back to replicated — a layout hint, never a
            # correctness constraint. Under FSDP, unannotated trainable
            # params shard their leading dim over the data axis instead
            # of replicating (all-gathered in-program by XLA).
            if self.sharding == "fsdp":
                def pspec(p):
                    return _fsdp.fsdp_sharding(p, self.mesh, self.data_axis)
            else:
                def pspec(p):
                    return _sharding.resolve_param(
                        p, self.mesh,
                        default_spec=self._auto_specs.get(id(p)))

            train_sh = [pspec(params[i]) for i in self.train_idx]
            # aux state (BatchNorm running stats) never FSDP-shards —
            # explicit annotations only
            aux_sh = [_sharding.resolve_param(params[i], self.mesh)
                      for i in self.aux_idx]
            # optimizer state inherits its weight's sharding — or, under
            # ZeRO-1, shards its leading axis over the dp group
            def state_spec(j, leaf):
                # only ZeRO-shard states of otherwise-replicated weights:
                # tp/sp-sharded weights already split their state, and
                # stacking dp on top would reshard every step. The
                # leading-dim-over-dp policy is fsdp_spec — ONE place
                # for the divisibility/fallback rule.
                if (self.shard_optimizer_states
                        and train_sh[j].spec == P()):
                    spec = _fsdp.fsdp_spec(np.shape(leaf), self.mesh,
                                           self.data_axis)
                    if spec is not None:
                        return NamedSharding(self.mesh, spec)
                return train_sh[j]

            state_sh = [jax.tree_util.tree_map(
                lambda leaf, j=j: state_spec(j, leaf), self._states[j])
                for j in range(len(self._states))]
            kwargs["in_shardings"] = (train_sh, aux_sh, state_sh, repl, repl,
                                      repl, repl, repl,
                                      batch_sharding, batch_sharding)
            kwargs["out_shardings"] = (repl, train_sh, aux_sh, state_sh)
            self._sharding_info = (train_sh, aux_sh, state_sh, repl,
                                   batch_sharding)
        if self.donate:
            kwargs["donate_argnums"] = (0, 1, 2)
        self._jitted = jax.jit(train_step, **kwargs)

    def _build_k(self):
        """Wrap the same step_fn in a lax.scan over a leading micro-step
        axis: k fwd+bwd+collective+update iterations inside ONE XLA
        program. On a host-limited launch path this amortizes per-step
        latency by k — the chip runs
        micro-steps back-to-back instead of idling between dispatches.

        lr is PER MICRO-STEP: either computed in-program from the step
        counter t (schedule_in_program + a closed-form scheduler) or
        scanned from a host-sampled (k,) table — both match a sequential
        loop step-for-step; the old chunk-granularity lr is gone."""
        step_fn = self._step_fn
        self._lr_program = None
        if self.schedule_in_program:
            sched = getattr(self.optimizer, "lr_scheduler", None)
            if sched is not None:
                self._lr_program = sched.as_jax()
        lr_program = self._lr_program

        def scan_fn(train_raws, aux_raws, states, key, lrs, wd, t0, rescale,
                    xs, ys):
            def one(carry, xy):
                tr, ax, st, k, t = carry
                k, sub = jax.random.split(k)
                xb, yb, lr_t = xy
                if lr_program is not None:
                    lr_t = lr_program(t)        # in-program schedule
                loss, ntr, nax, nst = step_fn(
                    tr, ax, st, sub, lr_t, wd, t, rescale, xb, yb)
                return (ntr, nax, nst, k, t + 1), loss

            (tr, ax, st, _, _), losses = jax.lax.scan(
                one, (train_raws, aux_raws, states, key, t0), (xs, ys, lrs))
            return losses, tr, ax, st

        kwargs = {}
        self._stacked_sharding = None
        if self._sharding_info is not None:
            train_sh, aux_sh, state_sh, repl, batch_sh = self._sharding_info
            stacked = NamedSharding(
                self.mesh, P(None, *batch_sh.spec))  # k axis unsharded
            self._stacked_sharding = stacked   # single source for run_k
            kwargs["in_shardings"] = (train_sh, aux_sh, state_sh, repl, repl,
                                      repl, repl, repl, stacked, stacked)
            kwargs["out_shardings"] = (repl, train_sh, aux_sh, state_sh)
        if self.donate:
            kwargs["donate_argnums"] = (0, 1, 2)
        self._jitted_k = jax.jit(scan_fn, **kwargs)

    def _chunk_lrs(self, k):
        """The (k,) per-micro-step lr values for the NEXT k updates.

        Host-table mode samples the scheduler at each t exactly as a
        sequential loop would (stateful schedulers advance identically —
        t is monotone). In-program mode returns a cached zero placeholder
        (threaded through the scan signature, dead-code-eliminated by
        XLA) and leaves the scheduler object untouched."""
        if self._lr_program is not None:
            tab = self._lr_dummy.get(k)
            if tab is None:
                tab = jnp.zeros((k,), jnp.float32)
                self._lr_dummy[k] = tab
            return tab
        if getattr(self.optimizer, "lr_scheduler", None) is None:
            # constant lr: one cached device table per (k, lr) — no
            # per-chunk host upload (the _f32 scalar-cache discipline)
            lr = float(self.optimizer.learning_rate)
            hit = self._lr_const.get(k)
            if hit is None or hit[0] != lr:
                hit = (lr, jnp.full((k,), lr, jnp.float32))
                self._lr_const[k] = hit
            return hit[1]
        vals = np.empty((k,), np.float32)
        for i in range(k):
            self.optimizer.num_update = self._num_update + 1 + i
            vals[i] = self.optimizer.learning_rate
        return jnp.asarray(vals)

    def ensure_built(self, x, y):
        """Resolve parameters and compile from a shape probe WITHOUT
        consuming an optimizer update. The restore path needs a BUILT
        step (params resolved, states allocated); the old recipe — run
        one junk update and let restore overwrite it — advanced
        num_update and burned an RNG split, which a resumed stochastic
        net would notice. Idempotent; returns self."""
        if not isinstance(x, NDArray):
            x = NDArray(x)
        if not isinstance(y, NDArray):
            y = NDArray(y)
        if self._jitted is None:
            self._resolve(x, y)
        return self

    def lower(self, x, y):
        """The single-step program lowered for this batch signature, for
        inspection: ``.compile()`` gives ``as_text()`` (which kernels and
        collectives the compiler kept) and ``memory_analysis()`` (bytes
        per device). Reads shapes only — no update is consumed and no
        buffer is donated."""
        self.ensure_built(x, y)
        f32, i32 = jnp.float32(0), jnp.int32(0)
        args = ([self.params[i].data()._data for i in self.train_idx],
                [self.params[i].data()._data for i in self.aux_idx],
                self._states, jax.random.PRNGKey(0), f32, f32, i32, f32,
                x._data if isinstance(x, NDArray) else x,
                y._data if isinstance(y, NDArray) else y)
        specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        with _select.quiet():  # inspection must not count as a selection
            return self._jitted.lower(*specs)

    def states(self):
        """The optimizer's state of every trained parameter after the last
        step, for inspection, in the order of `train_idx` (positions in
        `collect_params()`): the tuples that
        `Optimizer.create_state_multi_precision` made, so with master
        weights `(float32 master, *state)`. These are the live buffers,
        which the next step donates: copy what has to outlive it."""
        return list(self._states or ())

    # -- execution --------------------------------------------------------
    # The host's side of a step, as spans on the device trace's clock
    # (docs/profiler.md, "Names in a device trace"): `mxtpu.step` is the
    # whole call, a step annotation numbered by the update; its children
    # `.args` (scalars, key, gathering the buffers), `.enqueue` (the jitted
    # call inside the gate) and `.rebind` (the new buffers written back).
    def __call__(self, x, y):
        if not isinstance(x, NDArray):
            x = NDArray(x)
        if not isinstance(y, NDArray):
            y = NDArray(y)
        if self._jitted is None:
            self._resolve(x, y)
        self._num_update += 1
        self.optimizer.num_update = self._num_update
        with _prof.Scope("mxtpu.step", "trainer", sync=False,
                         step_num=self._num_update):
            with _prof.Scope("mxtpu.step.args", "trainer", sync=False):
                lr = self._f32("lr", self.optimizer.learning_rate)
                wd = self._f32("wd", self.optimizer.wd)
                t = jnp.int32(self._num_update)
                key = ndrandom._key()
                xb, yb = x._data, y._data
                if self._sharding_info is not None:
                    batch_sharding = self._sharding_info[4]  # from _build
                    with _TRANSFER_GATE:
                        xb = jax.device_put(xb, batch_sharding)
                        yb = jax.device_put(yb, batch_sharding)
                train_raws = [self.params[i].data()._data
                              for i in self.train_idx]
                aux_raws = [self.params[i].data()._data
                            for i in self.aux_idx]
                rescale = self._f32("rescale", self.optimizer.rescale_grad)
                sig = (tuple(xb.shape), str(xb.dtype), tuple(yb.shape),
                       str(yb.dtype))
                if _ps._PS is not None and \
                        self._cost_analyzed.get("fused_step") != sig:
                    # roofline capture BEFORE dispatch: analyze_jit only
                    # reads shapes/dtypes, so it is safe against the
                    # donated buffers. Keyed on the batch signature: a
                    # shape-driven recompile gets re-analyzed so the table
                    # describes the program being timed. mesh/mode flow
                    # through to commscope, which (when armed) walks the
                    # compiled HLO for the program's collective inventory —
                    # the thing the step budget's `collective` component is
                    # estimated from under GSPMD (docs/commscope.md)
                    self._cost_analyzed["fused_step"] = sig
                    _ps.analyze_jit(
                        self._jitted,
                        (train_raws, aux_raws, self._states, key, lr, wd, t,
                         rescale, xb, yb),
                        name="fused_step", dtype=xb.dtype, kind="train_step",
                        mesh=self.mesh, mode=self.sharding)
            # the donating dispatch ENQUEUE is serialized against any
            # in-flight prefetcher device_put (io.pipeline.TRANSFER_GATE) —
            # the enqueue-ordering half of the PR 14 flake fix; the other
            # half is the pipeline's consumer-thread put on XLA:CPU. The
            # guarded region is the async enqueue, not the step execution.
            try:
                with _prof.Scope("mxtpu.step.enqueue", "trainer",
                                 sync=False), \
                        _TRANSFER_GATE, _donated_cache_quarantine(self):
                    loss, new_train, new_aux, new_states = self._jitted(
                        train_raws, aux_raws, self._states, key, lr, wd, t,
                        rescale, xb, yb)
                    if _cpu_serial_client():
                        # XLA:CPU (io/pipeline.py safety model): retire the
                        # donating execution before ANY other client call —
                        # this client races the donated-buffer handoff of a
                        # still-running execution against concurrent client
                        # work regardless of which Python thread issues it.
                        # INSIDE the gate: the donation window and the gate
                        # window coincide, so gate holders (async checkpoint
                        # saves, prefetcher puts) are mutually excluded from
                        # it. Compute∥decode overlap is unaffected (the
                        # decode pool is host-side); only async dispatch
                        # depth is forfeited, on the backend where it buys
                        # nothing.
                        jax.block_until_ready(
                            (loss, new_train, new_aux, new_states))
            except Exception as e:  # noqa: BLE001 — re-raised unchanged
                _memscope_oom(e, "fused_step", self._num_update)
                raise
            with _prof.Scope("mxtpu.step.rebind", "trainer", sync=False):
                self._rebind(new_train, new_aux, new_states)
        # fully-fused path: forward+backward+collective+update is ONE call
        # of the jitted step per step; what the device sees is the
        # benchmark's programs_per_step.train
        _prof.set_gauge("trainer.dispatches_per_step", 1)
        return NDArray(loss)

    def _rebind(self, new_train, new_aux, new_states):
        """Write a dispatch's new buffers back into the parameters."""
        for j, i in enumerate(self.train_idx):
            self.params[i]._data._data = new_train[j]
        for j, i in enumerate(self.aux_idx):
            self.params[i]._data._data = new_aux[j]
        self._states = new_states
        if not self._stats_published and self.mesh is not None:
            # one-time layout telemetry: the params now carry the
            # shardings the compiled program actually produced
            self._stats_published = True
            _sharding.publish_param_stats(self.params, self._states,
                                          self.mesh, self.sharding)
            _memscope_analytic(self)

    def run_k(self, xs, ys):
        """Run k optimizer micro-steps as ONE compiled XLA program (a
        lax.scan over the leading axis) — k× fewer host dispatches, so a
        slow launch path no longer bounds step time. xs/ys: stacked (k, batch, ...) arrays, or lists of k
        per-step batches. lr is per micro-step (host-sampled table, or
        computed in-program under schedule_in_program), so schedulers
        advance step-for-step exactly like a sequential loop. Returns the
        k per-step losses as an NDArray of shape (k,).

        Reference contrast: the reference's engine pipelines k steps by
        async dependency tracking; here the compiler gets all k steps in
        one program, which also lets XLA overlap grad collectives of step
        t with compute of step t+1."""
        def to_stacked(seq):
            if isinstance(seq, (list, tuple)):
                # stay on device: no host round-trip for NDArray batches
                return jnp.stack([b._data if isinstance(b, NDArray)
                                  else jnp.asarray(b) for b in seq])
            return seq._data if isinstance(seq, NDArray) else jnp.asarray(seq)

        xs, ys = to_stacked(xs), to_stacked(ys)
        k = int(xs.shape[0])
        if self._jitted is None:
            self._resolve(NDArray(xs[0]), NDArray(ys[0]))
        if self._jitted_k is None:
            self._build_k()
        # the same four spans as __call__, around k micro-steps
        with _prof.Scope("mxtpu.step", "trainer", sync=False,
                         step_num=self._num_update + 1):
            with _prof.Scope("mxtpu.step.args", "trainer", sync=False):
                lrs = self._chunk_lrs(k)
                wd = self._f32("wd", self.optimizer.wd)
                t0 = jnp.int32(self._num_update + 1)
                key = ndrandom._key()
                if self._stacked_sharding is not None:
                    with _TRANSFER_GATE:
                        xs = jax.device_put(xs, self._stacked_sharding)
                        ys = jax.device_put(ys, self._stacked_sharding)
                train_raws = [self.params[i].data()._data
                              for i in self.train_idx]
                aux_raws = [self.params[i].data()._data
                            for i in self.aux_idx]
                rescale = self._f32("rescale", self.optimizer.rescale_grad)
                sig = (tuple(xs.shape), str(xs.dtype), tuple(ys.shape),
                       str(ys.dtype))
                if _ps._PS is not None and \
                        self._cost_analyzed.get(f"fused_step_k{k}") != sig:
                    self._cost_analyzed[f"fused_step_k{k}"] = sig
                    _ps.analyze_jit(
                        self._jitted_k,
                        (train_raws, aux_raws, self._states, key, lrs, wd,
                         t0, rescale, xs, ys),
                        name=f"fused_step_k{k}", dtype=xs.dtype,
                        kind="train_step", extra={"k": k}, mesh=self.mesh,
                        mode=self.sharding)
            # donation-vs-transfer serialization, same contract as __call__
            try:
                with _prof.Scope("mxtpu.step.enqueue", "trainer",
                                 sync=False), \
                        _TRANSFER_GATE, _donated_cache_quarantine(self):
                    losses, new_train, new_aux, new_states = self._jitted_k(
                        train_raws, aux_raws, self._states, key, lrs, wd, t0,
                        rescale, xs, ys)
                    if _cpu_serial_client():
                        # XLA:CPU donating dispatch retires inside the gate
                        # — see the matching __call__ block and
                        # io/pipeline.py
                        jax.block_until_ready((losses, new_train, new_aux,
                                               new_states))
            except Exception as e:  # noqa: BLE001 — re-raised unchanged
                _memscope_oom(e, f"fused_step_k{k}", self._num_update)
                raise
            self._num_update += k
            self.optimizer.num_update = self._num_update
            with _prof.Scope("mxtpu.step.rebind", "trainer", sync=False):
                self._rebind(new_train, new_aux, new_states)
        # one dispatch drives k micro-steps
        _prof.set_gauge("trainer.dispatches_per_step", round(1.0 / k, 4))
        return NDArray(losses)
