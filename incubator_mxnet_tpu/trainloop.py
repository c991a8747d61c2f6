"""mxtpu.trainloop — the whole-loop train executor.

The reference's hot loop is host-driven: Python sits between every step
(CachedOp fwd/bwd → kvstore → per-weight optimizer kernels). PR 2–5
fused the *step*; this module fuses the *loop*: N micro-steps — forward,
backward, gradient collective, optimizer update, AND the lr schedule —
compile into ONE donated, remat-policy-tuned XLA program, losses
accumulate on device, and a double-buffered prefetcher
(io.DevicePrefetcher) lands the next chunk's batches on the chip while
the current chunk runs. The host's only per-chunk work is a queue pop
and one dispatch; between chunk boundaries it never touches the device.

What this adds over ``FusedTrainStep.run_k``:

* **scheduler granularity** — lr is per MICRO-STEP, not per chunk:
  closed-form schedulers (optimizer/lr_scheduler.as_jax) compute lr
  IN-PROGRAM from the on-device step counter ``t``; custom schedulers
  fall back to a host-sampled (k,) lr table. Either way a k-chunked run
  matches a sequential loop step-for-step. (wd has no scheduler in this
  framework — it is sampled once at chunk start, like every other
  constant hyperparameter.)
* **input starvation is visible** — the prefetcher exports ``io.*``
  counters (batches_prefetched / wait_ms / put_ms / depth / buffer_fill)
  through the shared registry, so "TPU starved by input" shows up in
  /metrics and flight dumps next to step times.
* **first-class selection** — ``Trainer(..., loop_chunk=N)`` or
  ``MXTPU_LOOP_CHUNK=N`` marks a training setup for whole-loop
  execution; ``TrainLoop(net, loss, trainer)`` picks the chunk size up.
* **Pallas hot paths** — the traced step routes through the kernel-
  selection layer (ops/select.py), so flash-attention / fused layernorm
  / fused BN+relu kernels land inside the loop program when shapes
  qualify.
* **mesh-native parallelism** — ``Trainer(..., sharding='dp'|'fsdp'|
  'auto')`` (or an explicit ``mesh=``) lowers the whole chunk with the
  resolved per-param NamedShardings (mxtpu.sharding), so XLA inserts
  the dp gradient all-reduce / FSDP all-gathers INSIDE the one compiled
  program; see docs/sharding.md.

Telemetry (domain ``trainloop``): ``trainloop.chunks`` /
``trainloop.steps`` counters, ``trainloop.k`` / ``trainloop.chunk_ms`` /
``trainloop.in_program_lr`` gauges — plus the existing
``trainer.dispatches_per_step`` gauge, which reads 1/k under the
executor. The chunk program's compile
capture (perfscope roofline + commscope collective inventory) rides
FusedTrainStep's ``fused_step_k<k>`` hook — a scan-body inventory is
static, i.e. PER MICRO-STEP, which is exactly the granularity the step
budget's estimated ``collective`` component needs (docs/commscope.md).

See docs/trainloop.md for lifecycle, remat-policy knobs, prefetch-depth
tuning and the Pallas selection table.
"""
from __future__ import annotations

import time

import numpy as np

from . import devicescope as _devicescope
from . import memscope as _memscope
from . import profiler as _prof
from . import settings as _settings
from .io.prefetch import DevicePrefetcher
from .parallel.trainer_step import FusedTrainStep

__all__ = ["TrainLoop", "resolve_chunk"]


def resolve_chunk(explicit=None, optimizer=None, default=4):
    """Chunk-size resolution: explicit argument > Trainer.loop_chunk >
    MXTPU_LOOP_CHUNK (settings.resolve) > default. The default
    stays 4 here: constructing a TrainLoop IS choosing whole-loop
    execution, so an unconfigured chunk of 0 would be self-
    contradictory."""
    if explicit:
        return int(explicit)
    lc = getattr(optimizer, "loop_chunk", None)
    if lc:
        return int(lc)
    return int(_settings.resolve("loop_chunk")[0] or default)


class TrainLoop:
    """Whole-loop executor: ``run_chunk`` dispatches k train steps as one
    XLA program; ``fit`` drives a data source through the device
    prefetcher for a whole run.

        loop = TrainLoop(net, loss_fn, trainer)          # or optimizer
        losses = loop.fit(train_iter, steps=500)         # np (500,)

        # or hand-fed chunks:
        losses = loop.run_chunk(xs, ys)                  # (k,) NDArray

    Parameters mirror FusedTrainStep (mesh/data_axis/donate/remat/
    remat_policy); ``chunk`` defaults through
    Trainer.loop_chunk → MXTPU_LOOP_CHUNK → 4, ``prefetch_depth`` sizes
    the device-side input buffer (2 = double buffering), ``io_workers``
    sizes the ingest decode pool (docs/io.md).

    Donation safety: every chunk donates the parameter/optimizer-state
    buffers into the program and rebinds the live Parameters to the
    outputs — reading ``net.collect_params()`` between chunks is always
    valid; stale references to raw pre-chunk ``jax.Array``s are not (the
    same contract as FusedTrainStep)."""

    def __init__(self, net, loss_fn, optimizer, chunk=None, mesh=None,
                 data_axis=None, donate=True, remat=False, remat_policy=None,
                 prefetch_depth=None, schedule_in_program=True,
                 sharding=None, io_workers=None, io_transform=None):
        self.chunk = resolve_chunk(explicit=chunk, optimizer=optimizer)
        if self.chunk < 1:
            raise ValueError(f"loop chunk must be >= 1, got {self.chunk}")
        # buffer depth: explicit arg > MXTPU_PREFETCH_DEPTH > 2
        # (classic double buffering). An explicit 0 is rejected HERE
        # (not deferred to the first _prefetcher build) so the error
        # names the constructor argument, same verdict as the env parse
        self.prefetch_depth = int(
            prefetch_depth if prefetch_depth is not None
            else _settings.resolve("prefetch_depth")[0])
        if self.prefetch_depth < 1:
            raise ValueError(f"prefetch_depth must be >= 1, "
                             f"got {self.prefetch_depth}")
        # decode-pool width: explicit arg > MXTPU_IO_WORKERS > 2;
        # io_transform is a per-item decode hook (docs/io.md) run on
        # the pool threads, off the training thread's critical path
        self.io_workers = int(
            io_workers if io_workers is not None
            else _settings.resolve("io_workers")[0])
        if self.io_workers < 1:
            raise ValueError(f"io_workers must be >= 1, "
                             f"got {self.io_workers}")
        self.io_transform = io_transform
        # sharding mode and mesh resolve exactly like FusedTrainStep's:
        # explicit arg > Trainer.sharding > MXTPU_SHARDING; explicit
        # mesh > process-global sharding.set_mesh (docs/sharding.md)
        self.step = FusedTrainStep(
            net, loss_fn, optimizer, mesh=mesh, data_axis=data_axis,
            donate=donate, remat=remat, remat_policy=remat_policy,
            schedule_in_program=schedule_in_program, sharding=sharding)
        self._c_chunks = _prof.counter("trainloop.chunks", "trainloop")
        self._c_steps = _prof.counter("trainloop.steps", "trainloop")
        # cumulative host wall spent INSIDE run_chunk dispatches — the
        # whole-loop host_gap signal perfscope's step-time decomposition
        # reads (per-step share = dispatch_ms delta / steps)
        self._c_dispatch = _prof.counter("trainloop.dispatch_ms",
                                         "trainloop")
        # Trainer(..., resilience=dir) marks the setup for supervised
        # recovery the same way loop_chunk marks it for whole-loop
        # execution; fit() picks it up unless overridden per call
        self._resilience_default = getattr(optimizer, "resilience", None)
        _prof.set_gauge("trainloop.k", self.chunk, "trainloop")

    # -- properties -------------------------------------------------------
    @property
    def net(self):
        return self.step.net

    @property
    def optimizer(self):
        return self.step.optimizer

    @property
    def num_update(self):
        return self.step._num_update

    @property
    def in_program_lr(self) -> bool:
        """True once the compiled loop computes lr on device from the
        step counter (closed-form scheduler); False = host lr table."""
        return self.step._lr_program is not None

    # -- execution --------------------------------------------------------
    def run_chunk(self, xs, ys):
        """Run one chunk: xs/ys stacked (k, batch, ...) arrays (or lists
        of k batches). Returns the k per-step losses as an NDArray —
        still on device; fetch at run end, not per chunk."""
        t0 = time.perf_counter()
        losses = self.step.run_k(xs, ys)
        k = int(losses.shape[0])
        self._c_chunks.increment()
        self._c_steps.increment(k)
        # dispatch wall time: through an async dispatch path this is the
        # HOST cost per chunk (the device runs behind), which is exactly
        # the quantity the executor exists to shrink
        chunk_ms = (time.perf_counter() - t0) * 1e3
        self._c_dispatch.increment(chunk_ms)
        _prof.set_gauge("trainloop.chunk_ms", round(chunk_ms, 3),
                        "trainloop")
        _prof.set_gauge("trainloop.in_program_lr",
                        int(self.in_program_lr), "trainloop")
        # devicescope capture windows bound themselves in STEPS, and the
        # executor is the only one who knows a dispatch was k of them —
        # mark the active window so `with devicescope.capture(): fit()`
        # needs no user-side plumbing (one predicate when no window).
        # The sync thunk fetches this chunk's last loss, a true barrier
        # (steps chain through donated params), so a window closing at
        # this mark never closes with its own steps still in flight —
        # it only runs if this mark IS the window boundary. No
        # dispatch_ms here: the trainloop.dispatch_ms counter above
        # already carries this chunk's wall, and the window reads that
        # counter's delta — passing it again would double-count the
        # dispatch share in the gap taxonomy
        win = _devicescope.active_window()
        if win is not None:
            win.step(k, sync=lambda: float(losses[k - 1]),
                     workload="train")
        # memscope watermark ride-along at the same chunk boundary: one
        # allocator sample per dispatch, one predicate when off
        if _memscope._MS is not None:
            _memscope.sample(step=self.num_update, workload="train")
        return losses

    def fit(self, data, steps=None, epochs=None, cycle=None,
            skip_batches=0, resilience=None):
        """Drive the executor from a data source.

        data   : DataIter / iterable of DataBatch or (x, y) pairs.
        steps  : total optimizer steps to run (rounded DOWN to whole
                 chunks). With ``steps``, DataIter sources are cycled
                 (reset + refeed) across epoch ends.
        epochs : alternatively, full passes over the source (chunk
                 remainders at each epoch tail are dropped — static
                 shapes can't take short chunks).
        skip_batches : discard the first N source batches before
                 training (the data-cursor resume path — a restarted
                 run must not replay consumed batches).
        resilience : arm mxtpu.resilience for this run — a
                 ``resilience.Supervisor``, or a checkpoint-directory
                 string (a default Supervisor is built on it); also
                 picked up from ``Trainer(..., resilience=dir)`` /
                 ``MXTPU_RESILIENCE_DIR`` (pass ``False`` to override
                 that default off for one call). The run then
                 checkpoints every N steps asynchronously, resumes from
                 the manifest when the directory already holds
                 checkpoints (restart-from-last-good), and rolls back +
                 retries on a NaN loss instead of training on garbage
                 (docs/resilience.md). Steps-driven only: an EXPLICIT
                 resilience= on an epochs-driven call raises; the
                 ambient Trainer/env default instead degrades that call
                 to an unsupervised fit with a warning, so exporting
                 MXTPU_RESILIENCE_DIR can never crash epoch-driven
                 scripts that predate it.

        Returns the per-step losses as a numpy array — fetched ONCE at
        the end (per CHUNK under resilience: the NaN check needs the
        scalars); the loop itself never blocks on device values."""
        from_default = False
        if resilience is None:
            resilience = self._resilience_default
            from_default = resilience is not None
        elif resilience is False:
            resilience = None
        if resilience is not None:
            unsupervisable = (steps is None or epochs is not None
                              or skip_batches)
            if unsupervisable and from_default:
                import warnings
                warnings.warn(
                    "resilience armed by Trainer/MXTPU_RESILIENCE_DIR "
                    "but this fit() is epochs-driven or passes "
                    "skip_batches — supervision needs steps= only; "
                    "running UNSUPERVISED (no checkpoints, no recovery) "
                    "for this call", stacklevel=2)
            else:
                from .resilience import Supervisor
                sup = (resilience if isinstance(resilience, Supervisor)
                       else Supervisor(str(resilience)))
                if steps is None or epochs is not None:
                    raise ValueError(
                        "resilient fit is steps-driven: pass steps= only "
                        "(epoch accounting does not survive a mid-epoch "
                        "restart)")
                if skip_batches:
                    raise ValueError(
                        "skip_batches is incompatible with resilience=: "
                        "the resume cursor from the checkpoint manifest "
                        "owns batch skipping, and a second offset would "
                        "silently double- or under-train the data")
                return sup.drive(self, data, steps=steps,
                                 cycle=True if cycle is None else cycle)
        if (steps is None) == (epochs is None):
            raise ValueError("pass exactly one of steps= or epochs=")
        histories = []
        if steps is not None:
            n_chunks = steps // self.chunk
            if n_chunks < 1:
                raise ValueError(
                    f"steps={steps} is less than one chunk of "
                    f"{self.chunk}; lower loop_chunk or raise steps")
            cycle = True if cycle is None else cycle
            with self._prefetcher(data, cycle=cycle,
                                  skip=skip_batches) as pf:
                for i in range(n_chunks):
                    try:
                        xs, ys = next(pf)
                    except StopIteration:
                        # never let a bare StopIteration escape (it would
                        # be swallowed by any enclosing iterator frame)
                        raise ValueError(
                            f"data source exhausted after "
                            f"{i * self.chunk} of {steps} steps and "
                            f"cannot be rewound (pass a DataIter or a "
                            f"re-iterable, or lower steps=)") from None
                    self._check_labeled(ys)
                    histories.append(self.run_chunk(xs, ys))
        else:
            for e in range(epochs):
                # MXNet epoch convention: DataIter sources rewind at each
                # epoch start (without this, epoch 2+ would iterate an
                # exhausted iterator and silently contribute nothing)
                if hasattr(data, "reset"):
                    data.reset()
                n_before = len(histories)
                with self._prefetcher(data, cycle=False,
                                      skip=skip_batches if e == 0
                                      else 0) as pf:
                    for xs, ys in pf:
                        self._check_labeled(ys)
                        histories.append(self.run_chunk(xs, ys))
                if len(histories) == n_before:
                    # an empty epoch is always a caller bug (one-shot
                    # iterator that can't rewind, or fewer batches than
                    # one chunk) — never silently under-train
                    raise ValueError(
                        f"epoch {e + 1} produced no chunks: the source "
                        f"is exhausted/non-rewindable or yields fewer "
                        f"than chunk={self.chunk} batches (pass a "
                        f"DataIter or a re-iterable)")
        if not histories:
            return np.zeros((0,), np.float32)
        return np.concatenate([h.asnumpy() for h in histories])

    @staticmethod
    def _check_labeled(ys):
        if ys is None:
            raise ValueError(
                "TrainLoop.fit needs labeled batches ((x, y) pairs or "
                "DataBatch with labels); got a label-less batch — for "
                "self-supervised inputs yield (x, x)")

    def _prefetcher(self, data, cycle, skip=0):
        # the stacked-batch sharding only exists after the first build;
        # hand the prefetcher a late-bound getter instead of a value
        return DevicePrefetcher(
            data, depth=self.prefetch_depth, chunk=self.chunk,
            sharding=lambda: self.step._stacked_sharding, cycle=cycle,
            skip=skip, workers=self.io_workers,
            transform=self.io_transform)
