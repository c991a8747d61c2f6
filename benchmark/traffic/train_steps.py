"""Traffic kind `train_steps`: one fused train step after another on one
device-resident batch, for the length of the window.

Timed as a user's loop runs, with ONE step kept in flight: dispatch step
i, then wait on the loss of step i-1 and take the time. The host's
dispatch of the next step hides behind the device's current one whenever
it can; a barrier after every step would serialise them.

Parameters (the traffic file): `batch`, and whatever else the
configuration's batch maker reads (`seq`).
"""
import math
import time

import jax

from incubator_mxnet_tpu.parallel import FusedTrainStep
from lib import intervals

WARM_STEPS = 2      # the first compiles; the second meets donated buffers
TRACE_SECONDS = 3   # the traced part of a `--trace 1` run, mid-window


def _drive(step, x, y, seconds, out):
    """Steps for `seconds`, one in flight; the last is drained. Appends
    completion times, dispatch seconds and losses to `out`."""
    begin = time.perf_counter()
    in_flight = None
    while True:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            loss = step(x, y)
        out["dispatch_s"].append(time.perf_counter() - t0)
        out["losses"].append(loss)
        if in_flight is not None:
            with jax.profiler.TraceAnnotation("bench.wait_loss"):
                in_flight.wait_to_read()
            out["done"].append(time.perf_counter())
            if out["done"][-1] - begin >= seconds:
                break
        in_flight = loss
    with jax.profiler.TraceAnnotation("bench.wait_loss"):
        loss.wait_to_read()
    out["done"].append(time.perf_counter())
    return begin


def run(bench, config):
    doc, traffic = bench.config, bench.traffic
    net = config.net(doc, bench.seed)
    bench.mark("net")
    step = FusedTrainStep(net, config.loss(doc), config.optimizer(doc))
    x, y = config.batch(doc, traffic, bench.seed)
    bench.mark("batch")
    for i in range(WARM_STEPS):
        step(x, y).wait_to_read()
        bench.mark(f"warm_step_{i + 1}")

    rec = {"done": [], "dispatch_s": [], "losses": []}
    traced = None
    bench.open_window()
    if not bench.trace:
        begin = _drive(step, x, y, bench.seconds, rec)
    else:
        # a few traced seconds in the middle; each part drains its last
        # step, so the traced part holds whole steps and nothing else
        traced_s = min(TRACE_SECONDS, bench.seconds / 2)
        part = (bench.seconds - traced_s) / 2
        begin = _drive(step, x, y, part, rec)
        before = len(rec["done"])
        with bench.tracing():
            _drive(step, x, y, traced_s, rec)
        traced = len(rec["done"]) - before
        _drive(step, x, y, part, rec)
    bench.close_window()
    window = rec["done"][-1] - begin
    steps = len(rec["done"])

    losses = [float(l.asscalar()) for l in rec["losses"]]
    not_finite = sum(not math.isfinite(v) for v in losses)
    fell = (steps >= 20 and
            sum(losses[-10:]) / 10 < sum(losses[:10]) / 10)
    bench.note(steps=steps, window_s=window, loss_first=losses[:3],
               loss_last=losses[-3:], not_finite=not_finite, loss_fell=fell)

    # every interval between consecutive step completions, in seconds
    per_step = [b - a for a, b in zip(rec["done"], rec["done"][1:])]
    bench.note(step_ms={f"p{q}": intervals.percentile(per_step, q) * 1e3
                        for q in (0, 50, 95, 99, 100)})
    # what the compiled step holds, by the compiler's own account (after
    # the window: the cache serves the program, and set-up pays nothing)
    held = step.lower(x, y).compile().memory_analysis()
    out = {
        "attempted": steps,
        "failed": not_finite,
        "correct": not not_finite and fell,
        "end_to_end": {
            "samples_per_s": traffic["batch"] * steps / window,
        },
        "timers": {"dispatch_s": rec["dispatch_s"]},
        "program_bytes": {
            "argument": held.argument_size_in_bytes,
            "output": held.output_size_in_bytes,
            "alias": held.alias_size_in_bytes,
            "temp": held.temp_size_in_bytes,
        },
    }
    if not bench.trace:
        # a traced run drains three times: its intervals are not a tail
        out["end_to_end"]["step_ms_p95"] = \
            intervals.percentile(per_step, 95) * 1e3
    if traced is not None:
        out["traced_flops"] = (traced * traffic["batch"]
                               * config.flops_per_sample(doc, traffic))
    return out
