"""The program's account of its own set-up (profiler/build.py): spans at
the set-up boundaries, jax's compile phases by program, one partition of
the wall time, and an account that closes itself at the first fused-step
dispatch that compiled nothing."""
import jax
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd, profiler
from incubator_mxnet_tpu.parallel import FusedTrainStep
from incubator_mxnet_tpu.profiler import build

SECONDS = ("init_s", "shapes_s", "states_s", "build_s", "step_trace_s",
           "step_lower_s", "step_load_s", "other_programs_s")


@pytest.fixture(autouse=True)
def _fresh_account():
    profiler.stop()
    profiler.reset()
    build.restart()
    yield
    build.close()
    profiler.stop()
    profiler.reset()


def _setup():
    return {k.split("/", 1)[1]: v for k, v in profiler.counters().items()
            if k.startswith("setup/")}


def _compile():
    return {k.split("/", 1)[1]: v for k, v in profiler.counters().items()
            if k.startswith("compile/")}


class _Clock:
    """time.time() for the account's spans, moved by hand."""

    def __init__(self):
        self.now = 0.0

    def time(self):
        return self.now


def _net():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8), gluon.nn.Dense(4))     # deferred shapes
    net.initialize()
    step = FusedTrainStep(net, gluon.loss.L2Loss(),
                          mx.optimizer.create("sgd", learning_rate=0.01))
    x = nd.array(np.ones((4, 6), np.float32))
    y = nd.array(np.ones((4, 4), np.float32))
    return net, step, x, y


# -- the partition ----------------------------------------------------------

def test_spans_nest_and_self_times_leave_out_children_and_compiles(
        monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(build, "time", clock)
    with build.span("mxtpu.setup.init", "init_s"):
        build._on_time_span(build.TRACE, 1.5, 2.5, fun_name="_uniform")
        clock.now = 3.0
        with build.span("mxtpu.setup.shapes", "shapes_s"):
            # a jit traced inside the step's trace reports first
            build._on_time_span(build.TRACE, 3.2, 3.4, fun_name="add")
            build._on_time_span(build.TRACE, 3.1, 3.8,
                                fun_name="train_step")
            clock.now = 4.0
        clock.now = 5.0
    got = _setup()
    assert got["other_programs_s"] == pytest.approx(1.0)
    assert got["step_trace_s"] == pytest.approx(0.7)       # 3.2-3.4 once
    assert got["shapes_s"] == pytest.approx(1.0 - 0.7)
    assert got["init_s"] == pytest.approx(5.0 - 1.0 - 1.0)
    assert sum(got[k] for k in SECONDS) == pytest.approx(5.0)


def test_an_outer_compile_interval_takes_in_a_span_inside_it(monkeypatch):
    """An initialiser run while a program is traced: the trace's interval
    counts, the span and what it held count nothing."""
    clock = _Clock()
    monkeypatch.setattr(build, "time", clock)
    clock.now = 1.0
    with build.span("mxtpu.setup.init", "init_s"):
        build._on_time_span(build.LOAD, 1.2, 1.4, fun_name="jit(_normal)")
        clock.now = 2.0
    build._on_time_span(build.TRACE, 0.5, 3.0, fun_name="train_step")
    got = _setup()
    assert got["init_s"] == pytest.approx(0.0)
    assert got["other_programs_s"] == pytest.approx(0.0)
    assert got["other_programs"] == 1      # a program was still loaded
    assert got["step_trace_s"] == pytest.approx(2.5)


def test_overlapping_intervals_count_their_union(monkeypatch):
    monkeypatch.setattr(build, "time", _Clock())
    build._on_time_span(build.LOWER, 0.0, 2.0, fun_name="jit(a)")
    build._on_time_span(build.LOAD, 1.0, 3.0, fun_name="jit(a)")
    assert _setup()["other_programs_s"] == pytest.approx(3.0)


def test_a_jit_traced_inside_another_is_counted_once():
    seen = []

    def listen(event, start, end, fun_name="", **_):
        if event == build.TRACE:
            seen.append(fun_name)

    @jax.jit
    def helper(v):
        return v * 2.0

    def train_step(v):
        return helper(v) + 1.0

    jax.monitoring.register_event_time_span_listener(listen)
    try:
        jax.jit(train_step)(np.ones((3,), np.float32)).block_until_ready()
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)
    assert "helper" in seen and "train_step" in seen
    got = _setup()
    # the helper's trace lies inside train_step's and is never lowered on
    # its own: nothing of it is another program's
    assert got["other_programs_s"] == 0 and got["other_programs"] == 0
    assert got["step_trace_s"] > 0 and got["step_load_s"] > 0
    assert got["step_lower_s"] > 0


def test_spans_of_one_gauge_inside_each_other_are_one_span():
    profiler.start()
    net, step, x, y = _net()
    net.collect_params().initialize(force_reinit=True)
    profiler.stop()
    inits = [e for e in profiler._records if e["name"] == "mxtpu.setup.init"]
    # Block.initialize holds ParameterDict.initialize; the biases are made
    # there (twice), the deferred weights in the shape pass
    assert len(inits) == 2
    assert all(e["cat"] == "setup" for e in inits)
    assert _setup()["init_leaves"] == 4
    assert _setup()["init_bytes"] == 2 * (8 + 4) * 4
    step(x, y).wait_to_read()
    assert _setup()["init_leaves"] == 6
    assert _setup()["init_bytes"] == (2 * (8 + 4) + 6 * 8 + 8 * 4) * 4


# -- the fused step ---------------------------------------------------------

def test_the_first_call_opens_the_set_up_spans_in_order():
    net, step, x, y = _net()
    profiler.start()
    step(x, y).wait_to_read()
    profiler.stop()
    names = [e["name"] for e in sorted(profiler._records,
                                       key=lambda e: e["ts"])
             if e["name"].startswith("mxtpu.setup.")]
    # the two deferred weights are made inside the shape pass
    assert names == ["mxtpu.setup.shapes", "mxtpu.setup.init",
                     "mxtpu.setup.init", "mxtpu.setup.states",
                     "mxtpu.setup.build"]
    got = _setup()
    assert got["closed"] == 0
    # (the eager programs may all be in jax's memory from an earlier test)
    for name in ("shapes_s", "states_s", "build_s", "step_trace_s",
                 "step_lower_s", "step_load_s"):
        assert got[name] > 0, name


def test_the_account_closes_at_the_first_dispatch_that_compiled_nothing():
    net, step, x, y = _net()
    step(x, y).wait_to_read()
    assert _setup()["closed"] == 0
    step(x, y).wait_to_read()
    closed = _setup()
    assert closed["closed"] == 1 and build.closed
    step(x, y).wait_to_read()
    assert _setup() == closed


def test_a_steady_call_opens_no_set_up_span():
    net, step, x, y = _net()
    step(x, y).wait_to_read()
    step(x, y).wait_to_read()           # closes the account
    profiler.start()
    step(x, y).wait_to_read()
    step.run_k([x], [y]).wait_to_read()
    step.run_k([x], [y]).wait_to_read()
    profiler.stop()
    assert not [e for e in profiler._records
                if e["name"].startswith("mxtpu.setup.")]


def test_after_it_closes_no_set_up_gauge_moves():
    """What the benchmark builds after its window: the step's program for
    its memory analysis, a second net for the reference comparison."""
    net, step, x, y = _net()
    step(x, y).wait_to_read()
    step(x, y).wait_to_read()
    closed = _setup()
    assert closed["closed"] == 1
    step.lower(x, y).compile()
    other, step2, x2, y2 = _net()
    other.initialize(force_reinit=True)
    step2(x2, y2).wait_to_read()
    step2(x2, y2).wait_to_read()
    assert _setup() == closed


def test_the_lifetime_counters_keep_counting_after_it_closes():
    net, step, x, y = _net()
    step(x, y).wait_to_read()
    step(x, y).wait_to_read()
    before = _compile()
    assert before["requests"] >= _setup()["other_programs"] + 1
    assert before["seconds"] > 0
    jax.jit(lambda v: v - 3.0)(np.ones((5,), np.float32)).block_until_ready()
    after = _compile()
    assert after["requests"] == before["requests"] + 1
    assert after["seconds"] > before["seconds"]


def test_the_account_is_a_partition_of_set_up():
    """Everything the account counts lies inside the wall time of set-up,
    and the spans and programs leave little of it unnamed."""
    import time
    t0 = time.time()
    net, step, x, y = _net()
    step(x, y).wait_to_read()
    step(x, y).wait_to_read()
    wall = time.time() - t0
    got = _setup()
    counted = sum(got[k] for k in SECONDS)
    assert counted <= wall
    assert counted >= 0.5 * wall


def test_the_hybridized_shape_pass_is_the_same_span():
    net = gluon.nn.Dense(3)
    net.initialize()
    net.hybridize()
    profiler.start()
    net(nd.array(np.ones((2, 5), np.float32))).wait_to_read()
    profiler.stop()
    names = {e["name"] for e in profiler._records}
    assert "mxtpu.setup.shapes" in names
    assert "jit.compile:" + net.name in names
    assert _setup()["shapes_s"] > 0


def test_a_restart_forgets_a_span_opened_before_it(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(build, "time", clock)
    with build.span("mxtpu.setup.init", "init_s"):
        clock.now = 1.0
        build.restart()
    assert _setup()["init_s"] == 0
    assert build._stack() == []


def test_closing_publishes_every_gauge_again():
    """A registry cleared during set-up still holds the whole account once
    it closes, where the benchmark's readers look for it."""
    build._on_time_span(build.LOAD, 0.0, 0.25, fun_name="jit(f)")
    profiler.reset_counters()
    assert not [k for k in profiler.counters() if k.startswith("setup/")]
    build.close()
    got = _setup()
    assert set(got) == set(build.GAUGES)
    assert got["closed"] == 1 and got["other_programs"] == 1
    assert got["other_programs_s"] == pytest.approx(0.25)


def test_programs_loaded_from_many_threads_are_all_counted():
    import sys
    import threading
    threads, each = 8, 5
    before = build.requests
    fns = [[jax.jit(lambda v, k=t * each + i: v * k) for i in range(each)]
           for t in range(threads)]

    def work(mine):
        for fn in mine:
            fn(np.ones((2,), np.float32)).block_until_ready()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=work, args=(mine,))
                   for mine in fns]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert build.requests - before == threads * each
    assert _setup()["other_programs"] == threads * each
