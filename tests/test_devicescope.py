"""mxtpu.devicescope: trace ingestion against a checked-in real XLA:CPU
artifact (lane parsing, busy-fraction math, top-K program join, gap
classification edge cases — parser never raises), the windowed capture
lifecycle, StepBudget provenance upgrade/fallback pinned both ways, the
drift warning, the healthmon post-mortem attach, and the tooling
satellites (trace_check DEVICESCOPE_FAMILIES + check_devicescope_extra,
perf_regress busy-fraction gate incl. the 0→nonzero window transition,
mxdiag perf/device rendering)."""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import devicescope as ds
from incubator_mxnet_tpu import gluon, nd
from incubator_mxnet_tpu import perfscope as ps
from incubator_mxnet_tpu import profiler as prof
from incubator_mxnet_tpu.devicescope import ingest
from incubator_mxnet_tpu.profiler import tpu as prof_tpu

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "devicescope_trace_cpu.json.gz")


def _load_tool(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _devicescope_teardown():
    # provenance isolation: an earlier test's published sharding layout
    # legitimately flips StepBudget's collective_source to
    # "unavailable" (the PR 9 semantics) — these tests pin the
    # UNSHARDED contracts, so start from a clean registry both ways
    from incubator_mxnet_tpu.parallel import sharding as shmod
    shmod.clear_mesh()
    shmod._LAST.clear()
    yield
    ds.disable()          # stops any still-active window
    ds.reset()
    ps.disable()
    ps.reset_programs()
    shmod.clear_mesh()
    shmod._LAST.clear()
    assert not prof_tpu.tracing(), \
        "a test leaked an active jax profiler trace"


def _counters(prefix="devicescope/"):
    return {k: v for k, v in prof.counters().items()
            if k.startswith(prefix)}


# ---------------------------------------------------------------------------
# ingestion: the checked-in real XLA:CPU artifact
# ---------------------------------------------------------------------------

class TestFixtureIngestion:
    """The fixture is a REAL `jax.profiler.trace` artifact: 3 steps of a
    dp4 (4 fake CPU devices) matmul+tanh+all-reduce train-ish step named
    jit_step_fn, captured on XLA:CPU (see tests/fixtures/)."""

    def test_load_trace_events(self):
        events, path = ingest.load_trace_events(FIXTURE)
        assert path == FIXTURE
        assert len(events) > 100

    def test_lane_parsing(self):
        events, _ = ingest.load_trace_events(FIXTURE)
        ops, lanes = ingest.device_events(events)
        assert len(ops) > 50
        # every op is normalized and carries its module join key
        assert all(o["module"] == "jit_step_fn" for o in ops)
        assert all(o["dur"] >= 0 for o in ops)
        # lane metadata resolved from the M events
        assert len(lanes) >= 2
        assert any("tf_" in m["thread"] or "python" in m["thread"]
                   for m in lanes.values())
        kinds = {o["op"] for o in ops}
        assert "all-reduce" in kinds
        assert "dot" in kinds
        # trailing ".N" instance ids are stripped into op families
        assert not any(o["op"].split(".")[-1].isdigit() for o in ops)

    def test_summarize_busy_fraction_and_collectives(self):
        events, _ = ingest.load_trace_events(FIXTURE)
        s = ingest.summarize(events, wall_ms=50.0, steps=3)
        assert s["device_events"] > 50
        assert 0.0 < s["busy_fraction"] <= 1.0
        assert s["busy_ms"] > 0
        # busy is a UNION: concurrent lanes can't exceed the wall
        assert s["busy_ms"] <= 50.0 + 1e-6 or s["busy_fraction"] == 1.0
        per = s["per_step"]
        assert per["device_busy_ms"] == pytest.approx(s["busy_ms"] / 3)
        kinds = {r["kind"] for r in s["collectives"]["by_kind"]}
        assert kinds == {"all-reduce"}
        assert s["collectives"]["union_ms"] > 0
        # union of collective intervals <= their plain sum (4 fake
        # devices run the same all-reduce concurrently)
        assert s["collectives"]["union_ms"] <= s["collectives"]["sum_ms"]
        assert per["collective_ms"] > 0

    def test_top_k_join_to_program_table(self):
        events, _ = ingest.load_trace_events(FIXTURE)
        s = ingest.summarize(
            events, wall_ms=50.0, steps=3,
            program_map={"jit_step_fn": "fused_step"},
            programs=[{"name": "fused_step", "verdict": "hbm_bound"}])
        assert s["top_ops"], "top-K must be nonempty on a real artifact"
        assert all(t["program"] == "fused_step" for t in s["top_ops"])
        assert all(t["verdict"] == "hbm_bound" for t in s["top_ops"])
        # ranked by total device time, descending
        totals = [t["total_ms"] for t in s["top_ops"]]
        assert totals == sorted(totals, reverse=True)
        assert all(t["count"] >= 1 for t in s["top_ops"])

    def test_unjoined_module_keeps_null_program(self):
        events, _ = ingest.load_trace_events(FIXTURE)
        s = ingest.summarize(events, wall_ms=50.0, steps=3,
                             program_map={"some_other_module": "x"})
        assert all(t["program"] is None for t in s["top_ops"])
        assert all(t["verdict"] is None for t in s["top_ops"])

    def test_collective_axis_join_via_commscope_inventory(self):
        events, _ = ingest.load_trace_events(FIXTURE)
        comms = [{"name": "fused_step",
                  "collectives": [{"kind": "all-reduce", "axis": "dp"}]}]
        s = ingest.summarize(events, wall_ms=50.0, steps=3,
                             program_map={"jit_step_fn": "fused_step"},
                             comms_programs=comms)
        row = s["collectives"]["by_kind"][0]
        assert row["kind"] == "all-reduce"
        assert row["axis"] == "dp"

    def test_axis_by_kind_api(self):
        # the join rule's one home: commscope.axis_by_kind (record or
        # captured-name form; unknown -> {}, ambiguity -> None)
        from incubator_mxnet_tpu import commscope as cs
        rec = {"name": "p", "collectives": [
            {"kind": "all-reduce", "axis": "dp"},
            {"kind": "all-gather", "axis": "dp"},
            {"kind": "all-to-all", "axis": "dp"},
            {"kind": "all-to-all", "axis": "mp"}]}
        m = cs.axis_by_kind(rec)
        assert m == {"all-reduce": "dp", "all-gather": "dp",
                     "all-to-all": None}
        assert cs.axis_by_kind("never-captured-program") == {}
        assert cs.axis_by_kind(None) == {}

    def test_ambiguous_axis_is_none(self):
        events, _ = ingest.load_trace_events(FIXTURE)
        comms = [{"name": "fused_step",
                  "collectives": [{"kind": "all-reduce", "axis": "dp"},
                                  {"kind": "all-reduce", "axis": "mp"}]}]
        s = ingest.summarize(events, wall_ms=50.0, steps=3,
                             program_map={"jit_step_fn": "fused_step"},
                             comms_programs=comms)
        assert s["collectives"]["by_kind"][0]["axis"] is None


# ---------------------------------------------------------------------------
# ingestion: synthetic edge cases (the parser never raises)
# ---------------------------------------------------------------------------

def _x(ts, dur, name, pid=1, tid=1, module="jit_m", hlo=True):
    ev = {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
          "name": name}
    if hlo:
        ev["args"] = {"hlo_op": name, "hlo_module": module}
    return ev


class TestIngestEdgeCases:
    def test_empty_trace(self):
        s = ingest.summarize([], wall_ms=10.0, steps=2)
        assert s["busy_fraction"] == 0.0
        assert s["top_ops"] == []
        assert s["device_events"] == 0
        assert s["per_step"]["device_busy_ms"] == 0.0

    def test_single_event(self):
        s = ingest.summarize([_x(0.0, 4000.0, "dot.1")],
                             wall_ms=10.0, steps=1)
        assert s["busy_fraction"] == pytest.approx(0.4)
        assert s["top_ops"][0]["op"] == "dot"
        assert s["gaps"]["count"] == 0

    def test_overlapping_lanes_union_not_sum(self):
        # two lanes 100% busy over the same 5 ms: union is 5 ms, not 10
        evs = [_x(0.0, 5000.0, "dot.1", tid=1),
               _x(0.0, 5000.0, "dot.2", tid=2)]
        s = ingest.summarize(evs, wall_ms=5.0, steps=1)
        assert s["busy_ms"] == pytest.approx(5.0)
        assert s["busy_fraction"] == pytest.approx(1.0)

    def test_missing_metadata_never_raises(self):
        # no M events at all; events missing args/ts/dur/name; garbage
        evs = [{"ph": "X", "pid": 1, "tid": 1, "name": "dot",
                "args": {"hlo_op": "dot"}},            # no ts/dur
               {"ph": "X", "ts": "NaNish", "dur": 1.0,
                "args": {"hlo_op": "x"}},              # non-numeric ts
               {"ph": "X", "ts": 1.0, "dur": -5.0,
                "args": {"hlo_op": "y"}},              # negative dur
               {"ph": "M", "name": "thread_name"},     # argless meta
               {"ph": "X", "ts": 0.0, "dur": 1000.0, "name": "ok.1",
                "args": {"hlo_op": "ok.1"}},
               "not even a dict" if False else {"ph": "B"},
               {"args": {"hlo_op": "no-ph"}}]
        s = ingest.summarize(evs, wall_ms=2.0, steps=1)
        assert s["device_events"] == 1
        assert s["top_ops"][0]["op"] == "ok"

    def test_garbage_wall_and_steps(self):
        evs = [_x(0.0, 1000.0, "dot")]
        s = ingest.summarize(evs, wall_ms=None, steps=0)
        # no wall: device span is the fallback denominator
        assert s["busy_fraction"] == pytest.approx(1.0)
        s2 = ingest.summarize(evs, wall_ms="junk", steps=None)
        assert s2["device_events"] == 1

    def test_unreadable_artifact(self, tmp_path):
        evs, f = ingest.load_trace_events(str(tmp_path / "missing"))
        assert evs == [] and f is None
        p = tmp_path / "torn.trace.json"
        p.write_text('{"traceEvents": [ {"truncated": ')
        evs, f = ingest.load_trace_events(str(p))
        assert evs == [] and f == str(p)

    def test_gap_classification(self):
        # three 1 ms ops with 2 ms gaps between: 2 gaps, 4 ms total
        evs = [_x(0.0, 1000.0, "a"), _x(3000.0, 1000.0, "b"),
               _x(6000.0, 1000.0, "c")]
        s = ingest.summarize(evs, wall_ms=10.0, steps=1,
                             counters_delta={"io_wait_ms": 2.0,
                                             "dispatch_ms": 3.0})
        g = s["gaps"]
        assert g["count"] == 2
        assert g["total_ms"] == pytest.approx(4.0)
        assert g["max_ms"] == pytest.approx(2.0)
        assert g["histogram_ms"]["10.0"] == 2
        # idle = 10 - 3 busy = 7; io covers 2, dispatch 3, residual 2
        tax = g["taxonomy"]
        assert tax["input_starved_ms"] == pytest.approx(2.0)
        assert tax["dispatch_serialized_ms"] == pytest.approx(3.0)
        assert tax["host_gap_ms"] == pytest.approx(2.0)
        assert sum(tax.values()) == pytest.approx(s["idle_ms"])

    def test_union_intervals_handcomputed(self):
        merged, total = ingest.union_intervals(
            [(5, 7), (0, 2), (1, 3), (10, 10)])
        assert merged == [(0, 3), (5, 7)]
        assert total == pytest.approx(5.0)

    def test_collective_kind_of(self):
        assert ingest.collective_kind_of("all-reduce.5") == "all-reduce"
        assert ingest.collective_kind_of("all-gather-start.2") \
            == "all-gather"
        assert ingest.collective_kind_of("all-to-all") == "all-to-all"
        assert ingest.collective_kind_of("reduce-scatter.1") \
            == "reduce-scatter"
        assert ingest.collective_kind_of("collective-permute-start") \
            == "collective-permute"
        assert ingest.collective_kind_of("dot.3") is None
        assert ingest.collective_kind_of("reduce.8") is None


# ---------------------------------------------------------------------------
# windowed capture lifecycle
# ---------------------------------------------------------------------------

def _run_jit_steps(n=3):
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    x = jnp.ones((64, 64), jnp.float32)
    float(f(x))                       # compile outside the window
    return f, x


class TestCaptureWindow:
    def test_capture_stops_at_requested_steps(self, tmp_path):
        f, x = _run_jit_steps()
        win = ds.capture(steps=2, logdir=str(tmp_path / "w"))
        win.start()
        assert win.active
        assert ds.active_window() is win
        for _ in range(5):
            float(f(x))
            win.step(1)
        # stopped itself at step 2; later marks were no-ops
        assert not win.active
        assert win.steps_done == 2
        assert ds.active_window() is None
        assert ds.last_window() is win
        s = win.summary()
        assert s["window"]["steps"] == 2
        assert s["window"]["complete"] is True
        assert 0.0 < s["busy_fraction"] <= 1.0
        assert s["top_ops"]
        assert _counters()["devicescope/devicescope.windows"] >= 1

    def test_context_manager_early_stop(self, tmp_path):
        f, x = _run_jit_steps()
        with ds.capture(steps=100, logdir=str(tmp_path / "w")) as win:
            float(f(x))
            win.step(1)
        assert not win.active
        s = win.summary()
        assert s["window"]["steps"] == 1
        assert s["window"]["complete"] is False    # early stop, honest
        assert s["busy_fraction"] is not None

    def test_concurrent_window_declines(self, tmp_path):
        f, x = _run_jit_steps()
        w1 = ds.capture(steps=10, logdir=str(tmp_path / "a")).start()
        assert w1.active
        before = _counters().get("devicescope/devicescope.declined", 0)
        w2 = ds.capture(steps=10, logdir=str(tmp_path / "b")).start()
        assert w2.state == "declined"
        assert _counters()["devicescope/devicescope.declined"] \
            == before + 1
        # a declined window creates NOTHING on disk — it must never
        # count against (or evict artifacts from) the rotation budget
        assert not os.path.exists(str(tmp_path / "b"))
        w2.step(1)                      # all no-ops, never raise
        w2.stop()
        assert w2.summary() is None
        w1.stop()
        assert ds.last_window() is w1

    def test_summary_is_lazy_and_cached(self, tmp_path):
        f, x = _run_jit_steps()
        win = ds.capture(steps=1, logdir=str(tmp_path / "w")).start()
        float(f(x))
        win.step(1)
        assert win._summary is None     # ingestion deferred out of loop
        s1 = win.summary()
        assert s1 is win.summary()      # cached
        assert ds.window_summary() is s1

    def test_rotation_bounds_artifact_dirs(self, tmp_path):
        base = tmp_path / "rot"
        base.mkdir()
        for i in range(5):
            d = base / f"win_old_{i}"
            d.mkdir()
            (d / "x").write_text("x")
            t = time.time() - 100 + i
            os.utime(d, (t, t))
        from incubator_mxnet_tpu.devicescope import window as wmod
        n = wmod.rotate_dirs(str(base), keep=3)
        assert n == 3
        left = sorted(p.name for p in base.iterdir())
        assert left == ["win_old_3", "win_old_4"]
        # keep honors MXTPU_DEVICESCOPE_KEEP when not passed explicitly
        assert wmod.rotate_dirs(str(base)) == 0

    def test_window_off_means_no_state(self):
        assert ds.window_summary() is None
        assert ds.last_window_path() is None
        assert ds.bench_extra()["window"] is None

    def test_async_dispatch_sync_barrier_captures_work(self, tmp_path):
        """Async dispatch: without the boundary sync the window can
        close with its own steps still in flight (zero device events);
        the per-mark `sync` barrier fixes exactly that — so a window
        over fully-async marks WITH the barrier must capture events."""
        f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
        x = jnp.ones((64, 64), jnp.float32)
        float(f(x))
        win = ds.capture(steps=3, logdir=str(tmp_path / "w")).start()
        v = None
        for _ in range(3):
            v = f(x)                       # NO fetch: dispatch only
            win.step(1, sync=lambda: float(v))
        assert not win.active
        s = win.summary()
        assert s["device_events"] > 0
        assert s["per_step"]["device_busy_ms"] > 0

    def test_trainloop_marks_active_window(self, tmp_path):
        net = gluon.nn.Dense(4, in_units=8)
        net.initialize()
        L = gluon.loss.L2Loss()
        opt = mx.optimizer.create("sgd", learning_rate=0.01)
        loop = mx.TrainLoop(net, L, opt, chunk=2)
        xs = nd.array(np.random.rand(2, 4, 8).astype(np.float32))
        ys = nd.array(np.random.rand(2, 4, 4).astype(np.float32))
        loop.run_chunk(xs, ys)          # compile outside the window
        win = ds.capture(steps=4, logdir=str(tmp_path / "w")).start()
        loop.run_chunk(xs, ys)          # marks 2 steps itself
        assert win.steps_done == 2
        loop.run_chunk(xs, ys)
        assert not win.active           # bounded at 4
        assert win.summary()["window"]["steps"] == 4
        # no double-count: run_chunk already feeds trainloop.dispatch_ms,
        # so the window's dispatch delta must be the COUNTER delta alone
        # (the caller-accumulated channel is for counter-less loops)
        assert win.dispatch_ms == 0.0
        ctr = prof.counters().get("trainloop/trainloop.dispatch_ms")
        assert win._counters_delta["dispatch_ms"] <= float(ctr) + 1e-6

    def test_profile_xla_session_never_steals_window_trace(self, tmp_path):
        """set_state(profile_xla=True) must not stop a trace a
        devicescope window owns — jax allows one per process, and a
        failed start confers no right to stop."""
        from incubator_mxnet_tpu import profiler as profmod
        f, x = _run_jit_steps()
        win = ds.capture(steps=2, logdir=str(tmp_path / "w")).start()
        assert win.active
        profmod.set_config(profile_xla=True,
                           xla_logdir=str(tmp_path / "xla"))
        try:
            profmod.start()             # start declined (window owns it)
            profmod.stop()              # must NOT stop the window trace
            assert prof_tpu.tracing(), \
                "profiler session killed the window's trace"
            for _ in range(2):
                float(f(x))
                win.step(1)
            s = win.summary()
            assert s["device_events"] > 0       # capture survived intact
        finally:
            profmod.set_config(profile_xla=False)


# ---------------------------------------------------------------------------
# program join map (perfscope compile-site hook)
# ---------------------------------------------------------------------------

class TestProgramJoin:
    def test_module_name_of(self):
        def my_step(a):
            return a + 1
        low = jax.jit(my_step).lower(
            jax.ShapeDtypeStruct((4,), jnp.float32))
        assert ds.module_name_of(low) == "jit_my_step"
        assert ds.module_name_of(object()) is None

    def test_module_collision_poisons_join(self):
        # HLO module names are not unique (every hybridized Block jits
        # `raw_fn` → `jit_raw_fn`): a collision must unjoin, not pick
        # whichever program compiled last
        ds.enable()
        ds.register_program("jit:dense0:64x8", "jit_raw_fn")
        assert ds.program_map()["jit_raw_fn"] == "jit:dense0:64x8"
        ds.register_program("jit:dense0:64x8", "jit_raw_fn")  # re-analysis
        assert ds.program_map()["jit_raw_fn"] == "jit:dense0:64x8"
        ds.register_program("jit:dense1:32x4", "jit_raw_fn")  # collision
        assert ds.program_map()["jit_raw_fn"] is None
        ds.register_program("jit:dense0:64x8", "jit_raw_fn")
        assert ds.program_map()["jit_raw_fn"] is None  # stays poisoned
        # a poisoned key renders as an unjoined op, never a guess
        events, _ = ingest.load_trace_events(FIXTURE)
        s = ingest.summarize(events, wall_ms=50.0, steps=3,
                             program_map={"jit_step_fn": None})
        assert all(t["program"] is None for t in s["top_ops"])

    def test_fused_step_registers_module(self):
        ps.enable()
        ds.enable()
        net = gluon.nn.Dense(4, in_units=8)
        net.initialize()
        L = gluon.loss.L2Loss()
        opt = mx.optimizer.create("sgd", learning_rate=0.01)
        from incubator_mxnet_tpu.parallel import FusedTrainStep
        step = FusedTrainStep(net, L, opt)
        x = nd.array(np.random.rand(4, 8).astype(np.float32))
        y = nd.array(np.random.rand(4, 4).astype(np.float32))
        float(step(x, y))
        assert ds.program_map().get("jit_train_step") == "fused_step"

    def test_disabled_no_registration(self):
        ps.enable()
        assert ds._DS is None
        net = gluon.nn.Dense(4, in_units=8)
        net.initialize()
        L = gluon.loss.L2Loss()
        opt = mx.optimizer.create("sgd", learning_rate=0.01)
        from incubator_mxnet_tpu.parallel import FusedTrainStep
        step = FusedTrainStep(net, L, opt)
        x = nd.array(np.random.rand(4, 8).astype(np.float32))
        y = nd.array(np.random.rand(4, 4).astype(np.float32))
        float(step(x, y))
        assert ds.program_map() == {}


# ---------------------------------------------------------------------------
# step-budget reconciliation (provenance pinned both ways)
# ---------------------------------------------------------------------------

def _fake_summary(busy_per_step, coll_per_step, busy_fraction=0.5):
    return {"per_step": {"device_busy_ms": busy_per_step,
                         "collective_ms": coll_per_step,
                         "idle_ms": 1.0},
            "busy_fraction": busy_fraction,
            "window": {"path": "/tmp/fake_win", "steps": 5}}


class TestBudgetReconciliation:
    def _budget(self, steps=4, steady_s=0.4):
        ps.enable()
        b = ps.StepBudget().begin()
        b.end(steps=steps, steady_s=steady_s)
        return b

    def test_no_window_falls_back_exactly_as_today(self):
        b = self._budget()
        d = b.finish()
        assert d["source"] == "residual"
        assert d["collective_source"] == "measured"
        assert d["reconciliation"] is None

    def test_devicescope_off_never_overrides(self, monkeypatch):
        # even with a (stale) summary lying around, an unarmed
        # devicescope must not touch the budget
        monkeypatch.setattr(ds, "window_summary",
                            lambda: _fake_summary(50.0, 0.0))
        assert ds._DS is None
        d = self._budget().finish()
        assert d["source"] == "residual"
        assert d["reconciliation"] is None

    def test_window_upgrades_provenance(self, monkeypatch):
        ds.enable()
        monkeypatch.setattr(ds, "window_summary",
                            lambda: _fake_summary(80.0, 0.0))
        d = self._budget().finish()       # step_ms = 100
        assert d["source"] == "measured(profile)"
        assert d["device_compute_ms"] == pytest.approx(80.0)
        # measured 0 collective does NOT override the kvstore path
        assert d["collective_source"] == "measured"
        r = d["reconciliation"]
        assert r is not None
        assert r["measured"]["device_compute_ms"] == pytest.approx(80.0)
        assert r["analytic"]["source"] == "residual"
        # components still sum to the step wall
        total = sum(d[k] for k in ("device_compute_ms", "collective_ms",
                                   "input_wait_ms", "host_gap_ms",
                                   "other_ms"))
        assert total == pytest.approx(d["step_ms"], rel=1e-6)

    def test_measured_collective_upgrades_collective_source(
            self, monkeypatch):
        ds.enable()
        monkeypatch.setattr(ds, "window_summary",
                            lambda: _fake_summary(80.0, 12.0))
        d = self._budget().finish()
        assert d["collective_source"] == "measured(profile)"
        assert d["collective_ms"] == pytest.approx(12.0)
        # busy minus its collective share: never double-counted
        assert d["device_compute_ms"] == pytest.approx(68.0)

    def test_drift_warning_fires_over_threshold(self, monkeypatch):
        ds.enable()
        before = _counters().get(
            "devicescope/devicescope.drift_warnings", 0)
        monkeypatch.setattr(ds, "window_summary",
                            lambda: _fake_summary(80.0, 0.0))
        b = self._budget()
        b.probe(lambda: time.sleep(0.0005))   # analytic ~0.5 ms/step
        with pytest.warns(UserWarning, match="devicescope"):
            d = b.finish()
        assert d["reconciliation"]["drift_warning"] is True
        assert _counters()["devicescope/devicescope.drift_warnings"] \
            > before

    def test_no_drift_warning_under_threshold(self, monkeypatch):
        import warnings as _w
        ds.enable()
        fake = _fake_summary(100.0, 0.0)
        monkeypatch.setattr(ds, "window_summary", lambda: fake)
        b = self._budget()                   # step_ms=100; measured=100
        with _w.catch_warnings():
            _w.simplefilter("error")
            d = b.finish()
        r = d["reconciliation"]
        assert r["drift_warning"] is False
        # reconciliation lands in the window summary for extra.devicescope
        assert fake["reconciliation"] is r

    def test_overheated_window_still_sums_to_step_wall(self, monkeypatch):
        # a traced step pays profiler overhead, so the window's busy
        # time can exceed the UNTRACED steady per-step wall — the
        # settled components must still sum to step_ms
        ds.enable()
        monkeypatch.setattr(ds, "window_summary",
                            lambda: _fake_summary(150.0, 60.0))
        with pytest.warns(UserWarning):
            d = self._budget().finish()        # step_ms = 100
        assert d["collective_ms"] == pytest.approx(60.0)
        assert d["device_compute_ms"] == pytest.approx(40.0)
        total = sum(d[k] for k in ("device_compute_ms", "collective_ms",
                                   "input_wait_ms", "host_gap_ms",
                                   "other_ms"))
        assert total == pytest.approx(d["step_ms"], rel=1e-6)

    def test_overlapped_input_wait_yields_to_measured_device(
            self, monkeypatch):
        # prefetch wait that OVERLAPS measured device busy must not
        # double-claim wall time: with busy 95/step and io.wait 40/step
        # on a 100 ms step, input_wait keeps only the 5 ms the device
        # was actually idle — the components still sum to step_ms and
        # trace_check keeps accepting the artifact
        ds.enable()
        monkeypatch.setattr(ds, "window_summary",
                            lambda: _fake_summary(95.0, 0.0))
        b = self._budget()                   # step_ms = 100
        b._snap0["io/io.wait_ms"] = 0.0
        b._snap1["io/io.wait_ms"] = 160.0    # 40 ms/step over 4 steps
        d = b.finish()
        assert d["device_compute_ms"] == pytest.approx(95.0)
        assert d["input_wait_ms"] == pytest.approx(5.0)
        total = sum(d[k] for k in ("device_compute_ms", "collective_ms",
                                   "input_wait_ms", "host_gap_ms",
                                   "other_ms"))
        assert total == pytest.approx(d["step_ms"], rel=1e-6)

    def test_busy_zero_window_never_overrides(self, monkeypatch):
        ds.enable()
        monkeypatch.setattr(ds, "window_summary",
                            lambda: _fake_summary(0.0, 0.0))
        d = self._budget().finish()
        assert d["source"] == "residual"
        assert d["reconciliation"] is None

    def test_stale_window_never_upgrades_a_later_budget(self, tmp_path):
        """A window completed BEFORE a budget began measured someone
        else's steady phase — it must not be presented as that budget's
        measured truth (the strongest provenance on a wrong number)."""
        ps.enable()
        f, x = _run_jit_steps()
        with ds.capture(steps=1, logdir=str(tmp_path / "w")) as win:
            float(f(x))
            win.step(1)
        assert ds.window_summary()["busy_fraction"] is not None
        # a NEW budget begins after that window completed
        b = ps.StepBudget().begin()
        b.end(steps=4, steady_s=0.4)
        d = b.finish()
        assert d["source"] == "residual"
        assert d["reconciliation"] is None

    def test_serving_stamped_window_never_upgrades_a_train_budget(
            self, tmp_path):
        """A fresh window stepped by the SERVING batcher (train and
        serve share a process) measured dispatches this train budget
        never issued — workload identity, not just freshness, gates
        the measured(profile) upgrade. A 'mixed' window is rejected
        the same way; an unstamped (None) one stays accepted."""
        ps.enable()
        f, x = _run_jit_steps()
        b = ps.StepBudget().begin()
        with ds.capture(steps=1, logdir=str(tmp_path / "w")) as win:
            float(f(x))
            win.step(1, workload="serving")
        assert ds.window_summary()["busy_fraction"] is not None
        assert ds.last_window().workload == "serving"
        b.end(steps=4, steady_s=0.4)
        d = b.finish()
        assert d["source"] == "residual"
        assert d["reconciliation"] is None

    def test_mixed_steppers_degrade_window_to_mixed(self, tmp_path):
        with ds.capture(steps=5, logdir=str(tmp_path / "w")) as win:
            win.step(1, workload="train")
            win.step(1, workload="serving")
            win.step(1)                    # unstamped mark: no change
        assert win.workload == "mixed"

    def test_end_to_end_real_window(self, tmp_path):
        """A REAL capture window around real jit steps upgrades a real
        budget — the full measured path with no monkeypatching."""
        ps.enable()
        f, x = _run_jit_steps()
        b = ps.StepBudget().begin()
        win = ds.capture(steps=3, logdir=str(tmp_path / "w")).start()
        t0 = time.perf_counter()
        for _ in range(3):
            td = time.perf_counter()
            # fetch per step: a mark must only land once its device work
            # is DONE, or the auto-stop at step N can close the trace
            # with step N still in flight (async dispatch)
            float(f(x))
            b.add_dispatch(time.perf_counter() - td)
            win.step(1)
        b.end(steps=3, steady_s=time.perf_counter() - t0)
        win.stop()
        d = b.finish()
        assert d["source"] == "measured(profile)"
        assert d["device_compute_ms"] > 0
        assert d["reconciliation"]["measured"]["busy_fraction"] > 0


# ---------------------------------------------------------------------------
# healthmon post-mortems attach the window path
# ---------------------------------------------------------------------------

class TestHealthmonAttach:
    def test_nan_and_stall_alerts_carry_window_path(self, tmp_path,
                                                    monkeypatch):
        from incubator_mxnet_tpu import healthmon as hm
        monkeypatch.setattr(ds, "last_window_path",
                            lambda: "/tmp/mxtpu_devicescope/win_x")
        mon = hm.enable(hm_dir=str(tmp_path), stall_timeout_s=0,
                        run_id="r-test", rank=0)
        try:
            mon.observe_loss(float("nan"))
            mon.regress.observe(5.0)    # prime the EWMA path
        finally:
            hm.disable()
        recs = [json.loads(ln) for ln in
                open(os.path.join(str(tmp_path), "events_rank0.jsonl"))]
        nan = [r for r in recs if r["name"] == "healthmon.nan_loss"]
        assert nan and nan[0]["args"]["devicescope_window"] \
            == "/tmp/mxtpu_devicescope/win_x"

    def test_no_window_no_key(self, tmp_path):
        from incubator_mxnet_tpu import healthmon as hm
        assert ds.last_window_path() is None
        mon = hm.enable(hm_dir=str(tmp_path), stall_timeout_s=0,
                        run_id="r-test", rank=0)
        try:
            mon.observe_loss(float("inf"))
        finally:
            hm.disable()
        recs = [json.loads(ln) for ln in
                open(os.path.join(str(tmp_path), "events_rank0.jsonl"))]
        nan = [r for r in recs if r["name"] == "healthmon.nan_loss"]
        assert nan and "devicescope_window" not in nan[0]["args"]


# ---------------------------------------------------------------------------
# trace_check: counter family + extra.devicescope schema
# ---------------------------------------------------------------------------

def _valid_extra():
    return {
        "window": {"path": "/tmp/w", "steps": 10, "requested_steps": 10,
                   "wall_ms": 120.5, "complete": True},
        "busy_fraction": 0.42,
        "per_step": {"device_busy_ms": 5.0, "collective_ms": 0.5,
                     "idle_ms": 7.0},
        "top_ops": [{"op": "dot", "count": 10, "total_ms": 30.0,
                     "module": "jit_step_fn", "program": "fused_step",
                     "verdict": "compute_bound"}],
        "collectives": {"union_ms": 5.0, "sum_ms": 20.0,
                        "by_kind": [{"kind": "all-reduce", "count": 10,
                                     "total_ms": 20.0, "axis": "dp"}]},
        "gaps": {"count": 3, "total_ms": 2.0, "max_ms": 1.0,
                 "histogram_ms": {"0.1": 1, "1.0": 2, "10.0": 0,
                                  "100.0": 0, "+Inf": 0},
                 "taxonomy": {"input_starved_ms": 1.0,
                              "dispatch_serialized_ms": 0.5,
                              "host_gap_ms": 0.5}},
        "reconciliation": {
            "analytic": {"device_compute_ms": 6.0, "collective_ms": 0.6,
                         "collective_source": "estimated",
                         "source": "probe"},
            "measured": {"device_compute_ms": 4.5, "collective_ms": 0.5,
                         "busy_fraction": 0.42},
            "drift": {"device_compute": 0.25, "collective": None},
            "threshold": 0.25, "drift_warning": False},
    }


class TestTraceCheck:
    def test_families_accept_known_reject_unknown(self):
        tc = _load_tool("trace_check")
        ok = {k: v for k, v in tc.DEVICESCOPE_FAMILIES.items()}
        assert tc.check_healthmon_kinds(ok) == []
        bad = dict(ok)
        bad["devicescope/devicescope.made_up"] = "counter"
        assert any("made_up" in e for e in tc.check_healthmon_kinds(bad))
        flipped = dict(ok)
        flipped["devicescope/devicescope.windows"] = "gauge"
        assert any("kind" in e for e in tc.check_healthmon_kinds(flipped))

    def test_collective_sources_include_measured_profile(self):
        tc = _load_tool("trace_check")
        assert "measured(profile)" in tc.COLLECTIVE_SOURCES
        errs = tc.check_perfscope_extra({
            "peaks": {"peak_flops_f32": 1.0, "peak_flops_bf16": 2.0,
                      "hbm_bytes_per_s": 1.0},
            "programs": [],
            "decomposition": {"step_ms": 10.0, "device_compute_ms": 10.0,
                              "collective_ms": 0.0, "input_wait_ms": 0.0,
                              "host_gap_ms": 0.0, "other_ms": 0.0,
                              "collective_source": "measured(profile)"}})
        assert errs == []

    def test_valid_extra_passes(self):
        tc = _load_tool("trace_check")
        assert tc.check_devicescope_extra(_valid_extra()) == []
        assert tc.check_devicescope_extra(None) == []

    def test_zero_step_window_validates(self, tmp_path):
        # a window stopped before any mark is honest, not malformed
        tc = _load_tool("trace_check")
        f, x = _run_jit_steps()
        with ds.capture(steps=5, logdir=str(tmp_path / "w")):
            float(f(x))                 # work, but no step mark
        extra = ds.bench_extra()
        assert extra["window"]["steps"] == 0
        assert tc.check_devicescope_extra(extra) == []

    def test_armed_no_window_shape(self):
        tc = _load_tool("trace_check")
        assert tc.check_devicescope_extra(
            {"window": None, "busy_fraction": None, "per_step": None,
             "top_ops": [], "gaps": None, "reconciliation": None}) == []
        errs = tc.check_devicescope_extra(
            {"window": None, "busy_fraction": 0.5})
        assert any("null" in e for e in errs)

    def test_invalid_shapes_rejected(self):
        tc = _load_tool("trace_check")
        e = _valid_extra()
        e["busy_fraction"] = 1.7
        assert any("busy_fraction" in x
                   for x in tc.check_devicescope_extra(e))
        e = _valid_extra()
        e["top_ops"][0]["count"] = 0
        assert any("count" in x for x in tc.check_devicescope_extra(e))
        e = _valid_extra()
        e["collectives"]["by_kind"][0]["kind"] = "warp-shuffle"
        assert any("warp-shuffle" in x
                   for x in tc.check_devicescope_extra(e))
        e = _valid_extra()
        del e["gaps"]["taxonomy"]["host_gap_ms"]
        assert any("host_gap_ms" in x
                   for x in tc.check_devicescope_extra(e))
        e = _valid_extra()
        e["reconciliation"]["drift_warning"] = "yes"
        assert any("drift_warning" in x
                   for x in tc.check_devicescope_extra(e))
        e = _valid_extra()
        e["top_ops"][0]["verdict"] = "gpu_bound"
        assert any("gpu_bound" in x
                   for x in tc.check_devicescope_extra(e))

    def test_bench_json_wiring(self, tmp_path):
        tc = _load_tool("trace_check")
        doc = {"metric": "m", "value": 1.0, "unit": "x",
               "extra": {"mfu": 0.1, "devicescope": _valid_extra()}}
        p = tmp_path / "BENCH_ok.json"
        p.write_text(json.dumps(doc))
        assert tc.check_bench_json(str(p)) == []
        doc["extra"]["devicescope"]["busy_fraction"] = -2
        p2 = tmp_path / "BENCH_bad.json"
        p2.write_text(json.dumps(doc))
        assert any("devicescope" in e
                   for e in tc.check_bench_json(str(p2)))


# ---------------------------------------------------------------------------
# perf_regress: measured busy-fraction gate
# ---------------------------------------------------------------------------

def _artifact(tmp_path, name, value=100.0, busy=None):
    doc = {"metric": "m", "value": value, "unit": "img/s", "extra": {}}
    if busy is not None:
        doc["extra"]["devicescope"] = {"busy_fraction": busy}
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


class TestPerfRegressBusyGate:
    def _load(self, pr, path):
        rec, why = pr.load_artifact(path)
        assert rec is not None, why
        return rec

    def test_drop_beyond_threshold_regresses(self, tmp_path):
        pr = _load_tool("perf_regress")
        b = self._load(pr, _artifact(tmp_path, "b.json", busy=0.50))
        c = self._load(pr, _artifact(tmp_path, "c.json", busy=0.40))
        regs, _notes = pr.compare(b, c)
        assert any("busy fraction" in r for r in regs)

    def test_small_drop_ok(self, tmp_path):
        pr = _load_tool("perf_regress")
        b = self._load(pr, _artifact(tmp_path, "b.json", busy=0.50))
        c = self._load(pr, _artifact(tmp_path, "c.json", busy=0.48))
        regs, notes = pr.compare(b, c)
        assert not any("busy" in r for r in regs)
        assert any("busy fraction" in n for n in notes)

    def test_zero_to_nonzero_window_transition_skips(self, tmp_path):
        # the FIRST run that carries a window must not be indicted for
        # measuring (baseline has no devicescope data at all)
        pr = _load_tool("perf_regress")
        b = self._load(pr, _artifact(tmp_path, "b.json", busy=None))
        c = self._load(pr, _artifact(tmp_path, "c.json", busy=0.05))
        regs, notes = pr.compare(b, c)
        assert regs == []
        assert any("busy gate skipped" in n for n in notes)
        # ... and symmetrically when the candidate dropped its window
        regs2, notes2 = pr.compare(c, b)
        assert regs2 == []
        assert any("busy gate skipped" in n for n in notes2)

    def test_threshold_is_configurable(self, tmp_path):
        pr = _load_tool("perf_regress")
        b = self._load(pr, _artifact(tmp_path, "b.json", busy=0.50))
        c = self._load(pr, _artifact(tmp_path, "c.json", busy=0.40))
        regs, _ = pr.compare(b, c, busy_threshold=0.5)
        assert not any("busy" in r for r in regs)


# ---------------------------------------------------------------------------
# mxdiag rendering
# ---------------------------------------------------------------------------

class TestMxdiag:
    def _bench_doc(self):
        return {
            "metric": "m", "value": 100.0, "unit": "img/s",
            "extra": {
                "model": "lenet", "batch": 64, "dtype": "float32",
                "mfu": 0.1,
                "perfscope": {
                    "peaks": {"device_kind": "cpu", "table_row": "cpu",
                              "peak_flops_f32": 5e10,
                              "peak_flops_bf16": 5e10,
                              "hbm_bytes_per_s": 2e10},
                    "programs": [],
                    "decomposition": {
                        "step_ms": 10.0, "device_compute_ms": 4.5,
                        "collective_ms": 0.5, "input_wait_ms": 0.0,
                        "host_gap_ms": 2.0, "other_ms": 3.0,
                        "collective_source": "measured(profile)",
                        "source": "measured(profile)", "steps": 50,
                        "coverage": 1.0,
                        "reconciliation":
                            _valid_extra()["reconciliation"]},
                },
                "devicescope": _valid_extra(),
            },
        }

    def test_perf_renders_both_sources(self, capsys):
        md = _load_tool("mxdiag")
        assert md.print_perf(self._bench_doc()) == 0
        out = capsys.readouterr().out
        assert "[measured: devicescope window]" in out
        assert "analytic vs measured" in out
        assert "device_compute" in out
        # both numbers visible, not just one source
        assert "6.000" in out and "4.500" in out

    def test_perf_keeps_unavailable_tag(self, capsys):
        md = _load_tool("mxdiag")
        doc = self._bench_doc()
        d = doc["extra"]["perfscope"]["decomposition"]
        d["collective_source"] = "unavailable"
        d["reconciliation"] = None
        md.print_perf(doc)
        out = capsys.readouterr().out
        assert "UNAVAILABLE" in out

    def test_perf_renders_drift_warning(self, capsys):
        md = _load_tool("mxdiag")
        doc = self._bench_doc()
        rec = doc["extra"]["perfscope"]["decomposition"]["reconciliation"]
        rec["drift_warning"] = True
        rec["drift"]["device_compute"] = 0.6
        md.print_perf(doc)
        out = capsys.readouterr().out
        assert "DRIFT WARNING" in out
        assert "<< DRIFT" in out

    def test_device_renders_summary(self, capsys):
        md = _load_tool("mxdiag")
        assert md.print_device(self._bench_doc()) == 0
        out = capsys.readouterr().out
        assert "busy fraction: 42.0%" in out
        assert "top device ops" in out
        assert "all-reduce" in out
        assert "input-starved" in out
        # the SHARED reconciliation renderer (one home for perf+device)
        assert "analytic vs measured" in out

    def test_device_without_section(self, capsys):
        md = _load_tool("mxdiag")
        doc = self._bench_doc()
        del doc["extra"]["devicescope"]
        assert md.print_device(doc) == 1
        assert "devicescope was off" in capsys.readouterr().out

    def test_device_armed_no_window(self, capsys):
        md = _load_tool("mxdiag")
        doc = self._bench_doc()
        doc["extra"]["devicescope"] = {"window": None}
        assert md.print_device(doc) == 1
        assert "no capture window" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# bench payload shape
# ---------------------------------------------------------------------------

class TestBenchExtra:
    def test_armed_no_window_shape_validates(self):
        tc = _load_tool("trace_check")
        ds.enable()
        assert tc.check_devicescope_extra(ds.bench_extra()) == []

    def test_real_window_shape_validates(self, tmp_path):
        tc = _load_tool("trace_check")
        f, x = _run_jit_steps()
        with ds.capture(steps=2, logdir=str(tmp_path / "w")) as win:
            for _ in range(2):
                float(f(x))
                win.step(1)
        extra = ds.bench_extra()
        assert tc.check_devicescope_extra(extra) == []
        assert extra["window"]["steps"] == 2
        assert extra["top_ops"]
