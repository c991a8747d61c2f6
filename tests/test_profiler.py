"""mxtpu.profiler subsystem tests (ISSUE 1): Chrome-trace validity,
exact aggregate counts, scope nesting, zero-overhead disabled mode,
multi-layer coverage of a real gluon train loop, engine.bulk scopes,
Monitor-through-counters, and the trace_check schema validator."""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, engine, gluon, nd, profiler


@pytest.fixture(autouse=True)
def _clean_profiler():
    profiler.stop()
    profiler.reset()
    profiler.reset_counters()
    yield
    profiler.stop()
    profiler.reset()
    profiler.reset_counters()
    profiler.set_config(filename="profile.json", profile_imperative=True,
                        profile_all=False)


def _load_trace_check():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_check.py")
    spec = importlib.util.spec_from_file_location("trace_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -------------------------------------------------------------------------
# Chrome trace validity
# -------------------------------------------------------------------------

def test_start_stop_dump_valid_chrome_trace(tmp_path):
    path = str(tmp_path / "trace.json")
    profiler.set_config(filename=path)
    profiler.start()
    a = nd.ones((4, 4))
    ((a * 2) + 1).sum().wait_to_read()
    profiler.stop()
    written = profiler.dump()
    assert written == path
    with open(path) as f:
        doc = json.loads(f.read())
    events = doc["traceEvents"]
    assert isinstance(events, list) and len(events) >= 3
    x_events = [e for e in events if e.get("ph") == "X"]
    assert x_events, "no complete events recorded"
    for e in x_events:
        assert isinstance(e["name"], str) and e["name"]
        assert e["ph"] == "X"
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    # the validator agrees
    assert _load_trace_check().check_trace(path) == []


def test_api_parity_surface():
    """mx.profiler parity: every reference entry point exists and the
    legacy utils.profiler path is the SAME module (one state)."""
    for name in ("set_config", "set_state", "start", "stop", "pause",
                 "resume", "dump", "dumps", "Scope", "record_function"):
        assert callable(getattr(profiler, name)), name
    from incubator_mxnet_tpu.utils import profiler as legacy
    assert legacy is profiler
    assert mx.profiler is profiler
    # unknown reference kwargs are accepted and ignored
    profiler.set_config(profile_process="worker", nonsense=1)


# -------------------------------------------------------------------------
# Aggregate stats
# -------------------------------------------------------------------------

def test_aggregate_counts_known_sequence_exactly():
    a = nd.ones((3, 3))
    b = nd.ones((3, 3))
    profiler.start()
    for _ in range(3):
        (a + b).wait_to_read()      # 3x add
    for _ in range(2):
        (a * b).wait_to_read()      # 2x mul
    (a + b).sum().wait_to_read()    # 1x add, 1x sum
    profiler.stop()
    stats = profiler.aggregate_stats()
    assert stats["add"]["count"] == 4
    assert stats["mul"]["count"] == 2
    assert stats["sum"]["count"] == 1
    for ent in stats.values():
        assert ent["min_us"] <= ent["avg_us"] <= ent["max_us"]
        assert ent["total_us"] == pytest.approx(
            ent["avg_us"] * ent["count"])
    table = profiler.dumps()
    assert "Calls" in table and "add" in table and "Min(us)" in table
    profiler.reset()
    assert profiler.dumps().count("\n") == 0


# -------------------------------------------------------------------------
# Scope nesting
# -------------------------------------------------------------------------

def test_nested_scopes_nest(tmp_path):
    path = str(tmp_path / "nested.json")
    profiler.set_config(filename=path)
    profiler.start()
    with profiler.Scope("outer"):
        nd.ones((2, 2)).wait_to_read()
        with profiler.record_function("inner"):
            (nd.ones((2, 2)) * 3).wait_to_read()
    profiler.stop()
    doc = json.load(open(profiler.dump()))
    by_name = {e["name"]: e for e in doc["traceEvents"]
               if e.get("ph") == "X"}
    outer, inner = by_name["outer"], by_name["inner"]
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"]["depth"] == 0
    assert inner["args"]["depth"] == 1


# -------------------------------------------------------------------------
# Disabled mode: bit-identical results, <5% overhead
# -------------------------------------------------------------------------

def test_disabled_mode_bit_identical():
    x = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))

    def work(v):
        return (((v * 1.5) + 2.0).sum() * 0.25).asnumpy()

    ref = work(x)
    profiler.start()              # enable...
    profiler.stop()               # ...and disable again
    out = work(x)
    assert ref.tobytes() == out.tobytes()


def test_disabled_mode_overhead_under_5_percent():
    """1k-op microloop: the disabled-profiler build (hooks compiled in,
    predicate False) must be within 5% of the same loop before the
    profiler was ever touched. min-of-N damps scheduler noise."""
    a = nd.ones((4,))

    def loop():
        v = a
        for _ in range(1000):
            v = v + 1.0
        v.wait_to_read()

    def best(n=3):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            loop()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    loop()                        # warm caches
    baseline = best()
    profiler.start()
    profiler.stop()               # leave hooks armed-then-disarmed
    disabled = best()
    if disabled > baseline * 1.05 + 0.010:
        # re-measure-once (the test_overhead_bounded deflake, PR 5): on
        # this 1-core box a single scheduler burp during the sub-100ms
        # microloop dwarfs the effect under test when the full suite
        # runs alongside — a REAL predicate regression reproduces on
        # the immediate re-measure, noise doesn't. Only `disabled` is
        # re-measured: the pristine PRE-ARM baseline is the very thing
        # the comparison exists to preserve (re-measuring both sides
        # in the armed-then-disarmed state would erase the difference
        # under test)
        disabled = best()
    # 5% relative, with a 10ms absolute floor against timer jitter
    assert disabled <= baseline * 1.05 + 0.010, (
        f"disabled-profiler overhead too high: {disabled:.4f}s vs "
        f"baseline {baseline:.4f}s")


def test_off_path_is_single_predicate():
    """The documented zero-overhead contract: profiling off means the
    ndarray funnel hook is literally None and the layer predicate False."""
    from incubator_mxnet_tpu import ndarray as nd_mod
    profiler.stop()
    assert nd_mod._op_hook is None
    assert profiler._ACTIVE is False
    profiler.start()
    assert nd_mod._op_hook is not None
    assert profiler._ACTIVE is True
    profiler.pause()
    assert nd_mod._op_hook is None and profiler._ACTIVE is False
    profiler.resume()
    assert nd_mod._op_hook is not None and profiler._ACTIVE is True
    profiler.stop()
    assert nd_mod._op_hook is None


# -------------------------------------------------------------------------
# Acceptance: 2 gluon train steps cover >= 4 distinct layers
# -------------------------------------------------------------------------

def test_train_loop_covers_four_layers(tmp_path):
    path = str(tmp_path / "train.json")
    net = gluon.nn.Dense(4)
    net.initialize()
    net.hybridize()
    loss_fn = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="device")
    kv = trainer._kvstore
    x = nd.ones((2, 3))
    y = nd.zeros((2, 4))

    profiler.set_config(profile_all=True, filename=path)
    profiler.start()
    for _ in range(2):
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(2)
        kv.pushpull("loss_sync", loss, out=loss)   # metric allreduce
    profiler.stop()
    doc = json.load(open(profiler.dump()))

    cats = {e.get("cat") for e in doc["traceEvents"] if e.get("cat")}
    # >= 4 distinct layers: ndarray op, trainer phase, kvstore collective,
    # jit compile-cache event (+ autograd tape for good measure)
    assert {"operator", "trainer", "kvstore", "jit", "autograd"} <= cats
    names = {e["name"] for e in doc["traceEvents"]}
    assert "trainer.allreduce_grads" in names
    assert "trainer.optimizer_update" in names
    assert "kvstore.pushpull" in names
    assert any(n.startswith("jit.compile:") for n in names)
    # compile-cache counters: step 1 missed, step 2 hit
    ctr = profiler.counters()
    assert ctr["gluon/jit.cache_miss"] == 1
    assert ctr["gluon/jit.cache_hit"] == 1
    assert ctr["mxtpu/trainer.steps"] == 2
    assert _load_trace_check().check_trace(path) == []


# -------------------------------------------------------------------------
# engine.bulk scope (satellite)
# -------------------------------------------------------------------------

def test_engine_bulk_records_scope_when_profiling():
    profiler.start()
    with engine.bulk(8) as b:
        assert b.size == 8
        nd.ones((2,)).wait_to_read()
    profiler.stop()
    stats = profiler.aggregate_stats()
    assert stats["bulk(8)"]["count"] == 1


def test_engine_bulk_noop_when_off():
    with engine.bulk(4) as b:
        assert b.size == 4
        assert b._scope is None
    assert profiler.aggregate_stats() == {}
    # exceptions propagate (exit returns False)
    with pytest.raises(ValueError):
        with engine.bulk():
            raise ValueError("boom")


def test_engine_push_wait_all_scopes():
    profiler.start()
    hit = []
    engine.push(lambda: hit.append(1))
    engine.wait_all()
    profiler.stop()
    stats = profiler.aggregate_stats()
    assert hit == [1]
    assert stats["engine.push"]["count"] == 1
    assert stats["engine.wait_all"]["count"] == 1


# -------------------------------------------------------------------------
# The fused step's spans (docs/profiler.md, "Names in a device trace")
# -------------------------------------------------------------------------

STEP_SPANS = ["mxtpu.step.args", "mxtpu.step.enqueue", "mxtpu.step.rebind"]


def _fused_step():
    from incubator_mxnet_tpu.parallel import FusedTrainStep
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize()
    step = FusedTrainStep(net, gluon.loss.L2Loss(),
                          mx.optimizer.create("sgd", learning_rate=0.01))
    x = nd.array(np.ones((4, 8), np.float32))
    y = nd.array(np.ones((4, 4), np.float32))
    step(x, y).wait_to_read()          # builds the step
    return step, x, y


@pytest.mark.parametrize("call", ["__call__", "run_k"])
def test_fused_step_records_its_four_spans_when_profiling(call):
    step, x, y = _fused_step()
    profiler.reset()
    if call == "run_k":
        step.run_k([x, x], [y, y])      # builds the k-step program
        profiler.reset()
    profiler.start()
    if call == "run_k":
        step.run_k([x, x], [y, y]).wait_to_read()
    else:
        step(x, y).wait_to_read()
    profiler.stop()
    spans = {e["name"]: e for e in profiler._records
             if e["name"].startswith("mxtpu.")}
    assert sorted(spans) == sorted(["mxtpu.step"] + STEP_SPANS)
    whole = spans["mxtpu.step"]
    assert whole["args"]["depth"] == 0
    cursor = whole["ts"]
    for name in STEP_SPANS:             # in this order, inside the step
        child = spans[name]
        assert child["args"]["depth"] == 1
        assert child["ts"] >= cursor
        cursor = child["ts"] + child["dur"]
    assert cursor <= whole["ts"] + whole["dur"] + 1e-3


def test_fused_step_records_nothing_when_off():
    step, x, y = _fused_step()
    profiler.reset()
    step(x, y).wait_to_read()
    step.run_k([x], [y]).wait_to_read()
    assert profiler._records == []
    assert profiler.aggregate_stats() == {}


def test_scope_enters_the_bridge_annotation_with_the_profiler_off(
        monkeypatch):
    """On a TPU a Scope is a TraceAnnotation whether or not the mx
    profiler runs (a step annotation where it has a step number), so a
    jax trace session holds the program's spans; nothing is recorded."""
    import jax
    from incubator_mxnet_tpu.profiler import tpu
    made = []

    class Fake:
        def __init__(self, name, **kw):
            made.append((type(self).__name__, name, kw))

        def __enter__(self):
            made.append("enter")

        def __exit__(self, *exc):
            made.append("exit")

    class FakeStep(Fake):
        pass

    monkeypatch.setattr(tpu, "_is_tpu", True)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Fake)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", FakeStep)
    assert profiler._ACTIVE is False
    with profiler.Scope("mxtpu.step", sync=False, step_num=7):
        with profiler.Scope("mxtpu.step.args", sync=False):
            pass
    assert made == [("FakeStep", "mxtpu.step", {"step_num": 7}), "enter",
                    ("Fake", "mxtpu.step.args", {}), "enter", "exit",
                    "exit"]
    assert profiler._records == []


# -------------------------------------------------------------------------
# Counters registry
# -------------------------------------------------------------------------

def test_counters_registry_and_trace_counter_events(tmp_path):
    c = profiler.counter("requests", domain="serving")
    c.increment()
    c.increment(2)
    c.decrement()
    assert profiler.counters()["serving/requests"] == 2
    profiler.set_gauge("step_ms", 12.5, domain="bench")
    assert profiler.counters()["bench/step_ms"] == 12.5
    # same name returns the same counter (registry, not a new object)
    assert profiler.counter("requests", domain="serving") is c
    path = str(tmp_path / "ctr.json")
    profiler.dump(filename=path)
    doc = json.load(open(path))
    c_events = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
    assert {"serving/requests", "bench/step_ms"} <= {e["name"]
                                                     for e in c_events}
    assert _load_trace_check().check_trace(path) == []


# -------------------------------------------------------------------------
# Monitor through the counters registry (satellite)
# -------------------------------------------------------------------------

class _FakeExec:
    """Executor double with dicts but NO outputs attribute."""

    def __init__(self):
        self.arg_dict = {"w": nd.ones((2, 2))}
        self.aux_dict = {}
        self.grad_dict = {"w": nd.full((2, 2), 3.0)}


def test_monitor_tolerates_executor_without_outputs():
    mon = mx.Monitor(1, pattern=".*")
    mon.install(_FakeExec())
    mon.tic()
    rows = mon.toc()                      # must not raise
    tags = {r[1] for r in rows}
    assert tags == {"w", "w_grad"}


def test_monitor_non_numeric_stat_func_still_works():
    """Custom stat funcs may return strings (formatted for toc_print);
    those stay rows-only and must not crash gauge publishing."""
    mon = mx.Monitor(1, stat_func=lambda x: f"{x.mean():.2f}")
    mon.install(_FakeExec())
    mon.tic()
    rows = mon.toc()                      # must not raise
    assert {r[1] for r in rows} == {"w", "w_grad"}
    assert "monitor/w" not in profiler.counters()


def test_monitor_stats_flow_through_counters():
    mon = mx.Monitor(1, stat_func=lambda x: float(np.abs(x).mean()))
    mon.install(_FakeExec())
    mon.tic()
    mon.toc()
    ctr = profiler.counters()
    assert ctr["monitor/w"] == 1.0
    assert ctr["monitor/w_grad"] == 3.0


# -------------------------------------------------------------------------
# trace_check validator (satellite: CI/tooling)
# -------------------------------------------------------------------------

def test_trace_check_accepts_valid_and_rejects_malformed(tmp_path):
    tc = _load_trace_check()
    good = tmp_path / "good.json"
    good.write_text(json.dumps({"traceEvents": [
        {"name": "op", "ph": "X", "ts": 0, "dur": 5, "pid": 0, "tid": 0},
        {"name": "process_name", "ph": "M", "pid": 0,
         "args": {"name": "mxtpu"}},
    ]}))
    assert tc.check_trace(str(good)) == []

    bad_json = tmp_path / "bad.json"
    bad_json.write_text("{not json")
    assert tc.check_trace(str(bad_json))

    missing_ph = tmp_path / "noph.json"
    missing_ph.write_text(json.dumps([{"name": "op", "ts": 0}]))
    assert any("ph" in e for e in tc.check_trace(str(missing_ph)))

    bad_dur = tmp_path / "dur.json"
    bad_dur.write_text(json.dumps(
        [{"name": "op", "ph": "X", "ts": 1, "dur": "oops"}]))
    assert any("dur" in e for e in tc.check_trace(str(bad_dur)))

    not_list = tmp_path / "scalar.json"
    not_list.write_text("42")
    assert tc.check_trace(str(not_list))

    # CLI contract: nonzero exit on malformed input
    assert tc.main([str(bad_dur)]) == 1
    assert tc.main([str(good)]) == 0


# -------------------------------------------------------------------------
# Smoke (tier-1 fast path): one start/op/stop/dump round-trip
# -------------------------------------------------------------------------

def test_profiler_smoke(tmp_path):
    path = str(tmp_path / "smoke.json")
    profiler.set_config(filename=path)
    profiler.start()
    (nd.ones((2,)) + 1).wait_to_read()
    profiler.stop()
    profiler.dump()
    assert json.load(open(path))["traceEvents"]
