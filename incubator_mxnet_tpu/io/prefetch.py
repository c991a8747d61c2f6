"""On-device prefetch (the chip-feeding half of the whole-loop
executor; reference analogue: the PrefetchingIter + the ThreadedEngine
IO lane, upgraded to land batches ON DEVICE).

`PrefetchingIter` overlaps host-side decode with compute but still hands
the training loop HOST arrays — the `device_put` (and under a mesh, the
shard placement) happens synchronously inside the step, on the critical
path. :class:`DevicePrefetcher` moves that transfer off the path and,
since PR 17, overlaps the host stages against each other too: it is the
public face of the staged :class:`~.pipeline.Pipeline` (reader → decode
pool → ordered staging ring → transfer; see io/pipeline.py and
docs/io.md for the stage model). Batches are converted +
`jax.device_put` with the step's batch sharding and parked, up to
``depth`` deep, in a bounded device-resident buffer. The consumer's
``next()`` is then a queue pop of arrays already on the chip.

Telemetry (shared counters registry — visible in /metrics and flight dumps
like every other family):

* ``io/io.batches_prefetched``  counter — batches landed on device;
* ``io/io.wait_ms``             counter — cumulative ms the CONSUMER
  blocked on the buffer ("TPU starved by input" when this grows);
* ``io/io.read_ms``             counter — reader wall inside the
  source's next() (disk share of the starvation split);
* ``io/io.decode_ms``           counter — decode-pool wall, summed
  across workers (host-decode share);
* ``io/io.stage_ms``            counter — transfer-stage wall waiting
  for the next in-order chunk (reorder/decode-lag share);
* ``io/io.put_ms``              counter — cumulative ms spent
  converting + transferring (host→device share);
* ``io/io.depth``               gauge — configured buffer depth;
* ``io/io.buffer_fill``         gauge — buffered batches at last pop;
* ``io/io.workers``             gauge — resolved decode-pool width.

Lifecycle: iterate to exhaustion, or ``close()`` early — close() always
drains the buffer and joins the stage threads, so device references are
dropped and nothing leaks when training stops mid-epoch. Context
manager does the same.
"""
from __future__ import annotations

from .pipeline import (Pipeline, _SENTINEL, _raw,  # noqa: F401 — legacy
                       _split_batch, _stack_dev)   # import surface

__all__ = ["DevicePrefetcher"]

# how long close() waits for a reader parked inside the source's next()
# before abandoning it (daemon threads; nothing can enter the buffer
# after the stop flag is set). Module-level so tests/operators can tune
# the tradeoff — read at call time in close().
_CLOSE_DEADLINE_S = 5.0


class DevicePrefetcher(Pipeline):
    """Iterate device-resident batches ahead of the consumer.

    source    : DataIter / iterable / iterator yielding DataBatch or
                (x, y) pairs (NDArray or numpy).
    depth     : device-side buffer depth (2 = classic double buffering).
                TrainLoop resolves it through settings.py
                (argument > MXTPU_PREFETCH_DEPTH > 2).
    chunk     : group k consecutive batches and stack them on a new
                leading axis — the shape the whole-loop executor's
                run_k/run_chunk consumes. None = per-batch.
    sharding  : a jax Sharding, or a zero-arg callable resolving to one
                (or None) at transfer time — lets the caller hand over
                the fused step's batch sharding once it exists.
    cycle     : on source exhaustion, reset() DataIter sources (or
                re-iter iterables) and keep feeding — for step-driven
                (rather than epoch-driven) loops.
    skip      : discard the first N source batches before prefetching —
                the data-cursor resume path (mxtpu.resilience): a
                restarted run skips the batches its checkpoint manifest
                records as consumed instead of replaying them. Skipped
                batches never touch the device; counted as
                ``io.batches_skipped``. The cursor is applied by the
                single reader stage BEFORE the decode pool, so resume
                order is identical at any worker count.
    workers   : decode-pool width (the ``io_workers`` setting; None
                resolves to MXTPU_IO_WORKERS, else 2).
    transform : optional host hook ``(x, y) -> (x, y)`` run inside the
                decode pool (per-batch decode/augment work).
    """

    def close(self):
        super().close(deadline_s=_CLOSE_DEADLINE_S)
