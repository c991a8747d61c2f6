"""Traffic kind `train_steps_ref`: `train_steps`'s loop and timers, with the
configuration's plain reference deciding `correct`.

The steps are driven and timed exactly as `train_steps` does it (its
`_drive`: one step in flight, the same window, the same end-to-end
numbers). Besides, this kind KEEPS what the two warm-up steps produced,
which the timed program computed at the timed sizes: the loss of step 1 on
the seed's weights, after it a host copy of every trained parameter's
float32 master weight and of Adam's first-moment state (the only place a
fused step shows its gradient: mean = (1 - beta1) g after update 1), and
the loss of step 2. After the window is closed (so that neither `setup_s`
nor the window carries it) the reference (benchmark/configs/
<config>.reference.py) computes, from the same weights and batch, in
float32 at `highest` precision on the same device:

  loss1 and its gradients, one optimizer update of its own, loss2 after
  it, and the logits of `positions` positions spread over the sequence,
  which are held against the program's hybridized forward.

`correct` further requires each of these under the limit the traffic file
gives, with its reason:

  loss1, loss2   |program's - reference's|
  logits         largest error over the reference's largest entry
  gradient       the worst parameter's |g - g_ref| / |g_ref| (Euclidean
                 norms over the parameter): the step's whole backward
  update         the worst parameter's |dw - dw_ref| / |dw_ref|, where dw
                 is the change of the master weight in step 1 and dw_ref
                 what the reference's optimizer makes of the PROGRAM's
                 gradient: the rate, the rule, and that no parameter is
                 left out. (Adam's first update is the rate times the
                 gradient's sign; against the reference's own gradient it
                 would read the signs bfloat16 flipped, which `gradient`
                 holds already.)

The configuration keeps float32 master weights and computes from their
copy in its `dtype`: so does the comparison. loss2 is the reference's loss
on its updated weights rounded to `dtype`, the values the program's second
step computes from.

The numbers go out beside their limits on the earlier line `reference`,
and `moe` gives what the program counted itself: live rows a layer and the
largest expert's load over the mean (gluon.nn.SparseExperts.load).
`longest_interval` says where a run far off the pace lost its time (`clock_s`:
when it ended on `time.perf_counter()`'s clock, which a process sampling
the host beside the run shares).

The reference runs in blocks of `rows` query rows, a layer at a time, so
that its temporaries stay below the timed program's: run.py holds the
reserved region to the timed program's own (`reserved_is_filled`). Its
executables are kept out of the persistent compile cache, which is for the
timed program's.

`control` puts the reference itself, computed with lower-precision
operands, and the reference with one parameter's update left out, through
the same comparison: what the limits are set against
(tools/reference_control.py).

Parameters (the traffic file): `batch`, `seq`, and `reference`: `rows`,
`positions`, `limits` {loss1, loss2, logits, gradient, update}.
"""
import contextlib
import gc
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from incubator_mxnet_tpu import autograd
from incubator_mxnet_tpu.parallel import FusedTrainStep
from lib import intervals, peaks
from traffic import train_steps

HERE = os.path.dirname(os.path.abspath(__file__))


def _reference(cell):
    path = os.path.join(HERE, os.pardir, "configs",
                        cell["config"] + ".reference.py")
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@contextlib.contextmanager
def _out_of_the_compile_cache():
    """Nothing compiled inside is written to the persistent compile cache:
    where the cache is capped, the reference's executables would evict the
    timed program's, and no second run would find them."""
    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    jax.config.update(name, float("inf"))
    try:
        yield
    finally:
        jax.config.update(name, before)


def _seed_state(bench, config, x):
    """(the seed's weights as float32 arrays in `collect_params()` order,
    the positions of the trained ones, the hybridized forward's logits at
    `positions`, the positions): the same seed gives the same initial
    values as the net the steps have been updating since."""
    spec = bench.traffic["reference"]
    seq = x.shape[1]
    positions = np.linspace(0, seq - 1, min(spec["positions"], seq)).astype(
        np.int32)
    net = config.net(bench.config, bench.seed)
    net.hybridize()
    with autograd.pause():
        logits = np.asarray(net(x).jax()[:, positions].astype(jnp.float32))
    every = list(net.collect_params().values())
    params = [jnp.asarray(p.data().jax(), jnp.float32) for p in every]
    trained = [i for i, p in enumerate(every) if p.grad_req != "null"]
    return params, trained, logits, positions


def _first_pass(ref, doc, spec, params, tokens, targets, positions,
                operands=None):
    """(logits at `positions`, loss1, its gradients) by the reference."""
    rows = spec["rows"]
    logits = np.asarray(jax.jit(lambda p: ref.logits(
        doc, p, tokens, positions=positions, rows=rows,
        operands=operands))(params))
    loss1, grads = jax.jit(lambda p: ref.loss_and_grads(
        doc, p, tokens, targets, rows=rows, operands=operands))(params)
    return logits, float(loss1), grads


def _second_loss(ref, doc, spec, params, grads, tokens, targets,
                 operands=None):
    """The reference's loss after its own update of `params` (donated),
    computed from the updated weights rounded to the configuration's dtype.
    (`reduce_precision`, not a pair of casts: XLA may keep the excess
    precision of float32 -> bfloat16 -> float32, and did on the chip.)"""
    held_as = jnp.finfo(doc["dtype"])
    params = jax.jit(lambda p, g: [
        jax.lax.reduce_precision(w, held_as.nexp, held_as.nmant)
        for w in ref.adam_step(doc, p, g)], donate_argnums=(0,))(params, grads)
    return float(jax.jit(lambda p: ref.loss(
        doc, p, tokens, targets, rows=spec["rows"], operands=operands))(
            params))


def _leaf_errors(ref, doc, params, trained, want_grads, got):
    """{gradient, update}: for each trained parameter, in order, the
    relative error of its gradient against the reference's, and of its
    master weight's change in step 1 against what the reference's optimizer
    makes of that same gradient. `got(i)` gives parameter i's (gradient,
    weight after step 1)."""
    @jax.jit
    def leaf(w0, g_want, g_got, w_got):
        norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))  # noqa: E731
        moved = ref.adam_step(doc, [w0], [g_got])[0] - w0
        return (norm(g_got - g_want) / norm(g_want),
                norm((w_got - w0) - moved) / norm(moved))

    out = {"gradient": [], "update": []}
    for i in trained:
        gradient, update = leaf(params[i], want_grads[i], *got(i))
        out["gradient"].append(float(gradient))
        out["update"].append(float(update))
    return out


def _verdict(measured, want, limits):
    """{name: [error, limit]} of one measured side against the
    reference's."""
    return {
        "loss1": [abs(measured["loss1"] - want["loss1"]), limits["loss1"]],
        "loss2": [abs(measured["loss2"] - want["loss2"]), limits["loss2"]],
        "logits": [float(np.max(np.abs(measured["logits"] - want["logits"]))
                         / np.max(np.abs(want["logits"]))), limits["logits"]],
        "gradient": [max(measured["gradient"]), limits["gradient"]],
        "update": [max(measured["update"]), limits["update"]],
    }


def inside(verdict):
    return all(math.isfinite(error) and error < limit
               for error, limit in verdict.values())


def compare(bench, config, x, y, warm_losses, first_update):
    """The reference's side, and the verdict on the program's.
    `first_update`: for each trained parameter, host copies of its master
    weight and of Adam's mean after warm step 1."""
    doc, spec = bench.config, bench.traffic["reference"]
    ref = _reference(bench.cell)
    tokens, targets = x.jax(), y.jax()
    with _out_of_the_compile_cache():
        params, trained, got_logits, positions = _seed_state(bench, config, x)
        logits, loss1, grads = _first_pass(ref, doc, spec, params, tokens,
                                           targets, positions)
        by_position = dict(zip(trained, first_update))
        leaves = _leaf_errors(
            ref, doc, params, trained, grads,
            lambda i: (ref.gradient_of_mean(doc, jnp.asarray(
                by_position[i][1])), jnp.asarray(by_position[i][0])))
        loss2 = _second_loss(ref, doc, spec, params, grads, tokens, targets)
        del params, grads
        gc.collect()
    measured = dict(leaves, loss1=warm_losses[0], loss2=warm_losses[1],
                    logits=got_logits)
    verdict = _verdict(measured, {"loss1": loss1, "loss2": loss2,
                                  "logits": logits}, spec["limits"])
    bench.note(reference=dict(
        verdict, program=warm_losses, reference=[loss1, loss2],
        loss_fell_by=[warm_losses[0] - warm_losses[1], loss1 - loss2],
        logits_max=float(np.max(np.abs(logits))), positions=len(positions),
        rows=spec["rows"], trained=trained, by_parameter=leaves))
    return verdict


def control(bench, config, operands, unmoved=0):
    """Two sides that have to come out NOT inside the limits, through the
    comparison `compare` makes: the reference computed with `operands`
    (a dtype below the configuration's) in every product, its own update
    included; and the float32 reference with the update of trained
    parameter number `unmoved` left out. No step is built or timed."""
    doc, spec = bench.config, bench.traffic["reference"]
    ref = _reference(bench.cell)
    x, y = config.batch(doc, bench.traffic, bench.seed)
    tokens, targets = x.jax(), y.jax()
    params, trained, _, positions = _seed_state(bench, config, x)
    logits, loss1, grads = _first_pass(ref, doc, spec, params, tokens,
                                       targets, positions)
    low_logits, low_loss1, low_grads = _first_pass(
        ref, doc, spec, params, tokens, targets, positions, operands)
    after = jax.jit(lambda p, g: ref.adam_step(doc, p, g))

    def low(i):
        return low_grads[i], after([params[i]], [low_grads[i]])[0]

    def planted(i):
        moved = after([params[i]], [grads[i]])[0]
        return grads[i], params[i] if i == trained[unmoved] else moved

    low_leaves = _leaf_errors(ref, doc, params, trained, grads, low)
    planted_leaves = _leaf_errors(ref, doc, params, trained, grads, planted)
    low_loss2 = _second_loss(ref, doc, spec, [jnp.copy(p) for p in params],
                             low_grads, tokens, targets, operands)
    del low_grads
    loss2 = _second_loss(ref, doc, spec, params, grads, tokens, targets)
    want = {"loss1": loss1, "loss2": loss2, "logits": logits}
    out = {
        operands: _verdict(dict(low_leaves, loss1=low_loss1, loss2=low_loss2,
                                logits=low_logits), want, spec["limits"]),
        "unmoved": _verdict(dict(planted_leaves, **want), want,
                            spec["limits"]),
    }
    bench.note(control={name: dict(verdict, inside=inside(verdict))
                        for name, verdict in out.items()},
               by_parameter={operands: low_leaves, "unmoved": planted_leaves},
               reference=[loss1, loss2], trained=trained)
    return out


def run(bench, config):
    doc, traffic = bench.config, bench.traffic
    net = config.net(doc, bench.seed)
    bench.mark("net")
    step = FusedTrainStep(net, config.loss(doc), config.optimizer(doc))
    x, y = config.batch(doc, traffic, bench.seed)
    bench.mark("batch")
    warm = []
    for i in range(train_steps.WARM_STEPS):
        warm.append(step(x, y))
        warm[-1].wait_to_read()
        bench.mark(f"warm_step_{i + 1}")
        if i == 0:
            # what step 1 made of every trained parameter: (float32 master
            # weight, Adam's mean), copied before step 2 donates them
            first_update = [(np.array(state[0]), np.array(state[1]))
                            for state in step.states()]
            bench.mark("first_update_read")

    rec = {"done": [], "dispatch_s": [], "losses": []}
    traced = None
    bench.open_window()
    if not bench.trace:
        begin = train_steps._drive(step, x, y, bench.seconds, rec)
    else:
        # a few traced seconds in the middle, as train_steps has them
        traced_s = min(train_steps.TRACE_SECONDS, bench.seconds / 2)
        part = (bench.seconds - traced_s) / 2
        begin = train_steps._drive(step, x, y, part, rec)
        before = len(rec["done"])
        with bench.tracing():
            train_steps._drive(step, x, y, traced_s, rec)
        traced = len(rec["done"]) - before
        train_steps._drive(step, x, y, part, rec)
    bench.close_window()
    window = rec["done"][-1] - begin
    steps = len(rec["done"])

    warm = [float(l.asscalar()) for l in warm]
    losses = [float(l.asscalar()) for l in rec["losses"]]
    not_finite = sum(not math.isfinite(v) for v in warm + losses)
    fell = (steps >= 20 and
            sum(losses[-10:]) / 10 < sum(losses[:10]) / 10)
    bench.note(steps=steps, window_s=window, loss_warm=warm,
               loss_first=losses[:3], loss_last=losses[-3:],
               not_finite=not_finite, loss_fell=fell)
    per_step = [b - a for a, b in zip(rec["done"], rec["done"][1:])]
    bench.note(step_ms={f"p{q}": intervals.percentile(per_step, q) * 1e3
                        for q in (0, 50, 95, 99, 100)})
    # where a run far off the pace lost its time: the longest interval, and
    # the dispatch made inside it (step k + 2 goes out before step k + 1 is
    # waited for)
    longest = max(range(len(per_step)), key=per_step.__getitem__)
    bench.note(longest_interval={
        "ms": per_step[longest] * 1e3,
        "at_s": rec["done"][longest + 1] - begin,
        "clock_s": rec["done"][longest + 1],
        "dispatch_ms": rec["dispatch_s"][min(longest + 2, steps - 1)] * 1e3})

    # what the program counted itself, from its last step
    moe = net.read_load()
    live_rows = [layer["live_rows"] for layer in moe]
    bench.note(moe={"live_rows": live_rows,
                    "load_max_over_mean": [layer["load_max_over_mean"]
                                           for layer in moe],
                    "rows_held": traffic["batch"] * traffic["seq"]
                    * doc["num_experts_per_tok"]})

    held = step.lower(x, y).compile().memory_analysis()
    verdict = compare(bench, config, x, y, warm, first_update)
    out = {
        "attempted": steps,
        "failed": not_finite,
        "correct": not not_finite and fell and inside(verdict),
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
        out["end_to_end"]["step_ms_p95"] = \
            intervals.percentile(per_step, 95) * 1e3
    if traced is not None:
        out["traced_flops"] = (traced * traffic["batch"]
                               * config.flops_per_sample(doc, traffic,
                                                         live_rows))
        out["ideal_s_per_step"] = ideal_seconds(bench, config, live_rows)
    return out


def ideal_seconds(bench, config, live_rows):
    """The least time a step's kernels could take on this chip, from the
    configuration's own counts (forward and the two products of backward:
    3 x forward): the held experts' grouped products, bound by the larger
    of their operations and their bytes, and attention over the visible
    pairs. None for a device whose peaks are not published."""
    doc, traffic = bench.config, bench.traffic
    try:
        flops = peaks.peak(bench.device_kind, "bf16_flops")
        bandwidth = peaks.peak(bench.device_kind, "hbm_bytes_per_s")
    except KeyError:
        return None
    kinds = doc["layer_types"][:doc["num_hidden_layers"]]
    return {
        "moe_experts": sum(
            max(3 * config.expert_flops(doc, rows) / flops,
                3 * config.expert_bytes(doc, rows) / bandwidth)
            for rows in live_rows),
        "attention": sum(
            3 * traffic["batch"] * config.attention_flops(
                doc, traffic["seq"], kind) / flops for kind in kinds),
    }
