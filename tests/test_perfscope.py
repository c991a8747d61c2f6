"""mxtpu.perfscope: roofline cost analysis, step-time decomposition,
and the BENCH regression gate (tools/perf_regress.py) — plus the
trace_check schema enforcement for the new perfscope.* counter family
and `extra.perfscope` BENCH section."""
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import diagnostics as diag
from incubator_mxnet_tpu import gluon, nd
from incubator_mxnet_tpu import perfscope as ps
from incubator_mxnet_tpu import profiler as prof


def _load_tool(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _perfscope_teardown():
    yield
    ps.disable()
    ps.reset_programs()
    diag.disable()


def _counters(prefix="perfscope/"):
    return {k: v for k, v in prof.counters().items()
            if k.startswith(prefix)}


# ---------------------------------------------------------------------------
# roofline classification
# ---------------------------------------------------------------------------

@pytest.fixture
def v5e_table(monkeypatch):
    """Steer the code that looks the device up itself (record_program,
    StepBudget.finish, bench_extra) to the v5e row for one test."""
    from incubator_mxnet_tpu.perfscope import cost
    monkeypatch.setattr(cost, "device_peaks", lambda device=None: dict(V5E))
    monkeypatch.setattr(ps, "device_peaks", lambda device=None: dict(V5E))


class TestClassify:
    def test_compute_bound(self):
        # AI far above any ridge
        r = ps.classify(1e12, 1e6, V5E)
        assert r["verdict"] == "compute_bound"
        assert r["ai"] == pytest.approx(1e6)
        assert r["est_compute_ms"] > 0

    def test_hbm_bound(self):
        # 1 FLOP per byte is below every ridge in the table
        r = ps.classify(1e9, 1e9, V5E)
        assert r["verdict"] == "hbm_bound"
        assert r["ai"] == pytest.approx(1.0)

    def test_zero_flops_is_trivial(self):
        r = ps.classify(0, 0)
        assert r["verdict"] == "trivial"
        assert r["flops"] == 0.0

    def test_small_flops_is_trivial(self):
        assert ps.classify(100.0, 1e12)["verdict"] == "trivial"

    def test_missing_flops_is_unknown(self):
        r = ps.classify(None, None)
        assert r["verdict"] == "unknown"
        assert r["flops"] is None and r["ai"] is None

    def test_garbage_inputs_are_unknown(self):
        assert ps.classify("not-a-number", {})["verdict"] == "unknown"

    def test_flops_without_bytes_is_compute_bound(self):
        # real FLOPs, zero reported traffic -> compute is the only ceiling
        r = ps.classify(1e10, 0, V5E)
        assert r["verdict"] == "compute_bound"
        assert r["ai"] is None

    def test_trivial_threshold_env_override(self, monkeypatch):
        monkeypatch.setenv("MXTPU_PERFSCOPE_TRIVIAL_FLOPS", "1")
        assert ps.classify(100.0, 1e12, V5E)["verdict"] == "hbm_bound"

    def test_verdict_taxonomy_is_closed(self):
        for args in ((1e12, 1e6), (1e9, 1e9), (0, 0), (None, None)):
            assert ps.classify(*args)["verdict"] in ps.ROOFLINE_VERDICTS


class _FakeDevice:
    def __init__(self, kind):
        self.device_kind = kind


# a table for verdict tests that run on the CPU, which has none of its own
V5E = {"device_kind": "tpu v5 lite", "table_row": "v5e",
       "peak_flops_f32": 99e12, "peak_flops_bf16": 197e12,
       "hbm_bytes_per_s": 819e9}


class TestPeaks:
    def test_unknown_device_has_no_peaks(self):
        """The CPU is not in the table: no peaks, no ridge, no verdict
        beyond "trivial" — never a stand-in row."""
        p = ps.device_peaks()
        assert p["device_kind"] == "cpu" and p["table_row"] is None
        assert p["peak_flops_f32"] is None and p["peak_flops_bf16"] is None
        assert p["hbm_bytes_per_s"] is None
        rec = ps.classify(1e12, 1e6)
        assert rec["verdict"] == "unknown" and rec["ridge"] is None
        assert rec["est_compute_ms"] is None and rec["peak_flops"] is None
        assert ps.classify(10.0, 10.0)["verdict"] == "trivial"

    @pytest.mark.parametrize("kind,row", [
        ("TPU v5 lite", "v5e"),       # what jax reports for a v5e
        ("v5litepod-8", "v5e"),       # the GCE accelerator type
        ("TPU v5e", "v5e"),
        ("TPU v4", "v4"),
        ("TPU v5p", "v5p"),           # must not fall into the v5e row
        ("weird accelerator", None),
    ])
    def test_device_kind_matching(self, kind, row):
        p = ps.device_peaks(_FakeDevice(kind))
        assert p["table_row"] == row

    def test_v5e_bf16_peak_matches_bench_constant(self):
        # PERF.md's MFU numbers were computed against 197 Tf bf16; the
        # table must reproduce that for the real chip's kind string
        p = ps.device_peaks(_FakeDevice("TPU v5 lite"))
        assert p["peak_flops_bf16"] == pytest.approx(197e12)
        assert p["peak_flops_f32"] == pytest.approx(99e12)

    def test_environment_cannot_invent_peaks(self, monkeypatch):
        """The retired MXTPU_PEAK_* overrides let any backend print an
        "MFU"; they are gone, so setting them changes nothing."""
        monkeypatch.setenv("MXTPU_PEAK_FLOPS", "123e12")
        monkeypatch.setenv("MXTPU_PEAK_BW", "456e9")
        assert ps.device_peaks()["peak_flops_f32"] is None
        assert ps.device_peaks(_FakeDevice("TPU v4"))["peak_flops_bf16"] \
            == pytest.approx(275e12)

    def test_malformed_trivial_threshold_never_raises(self, monkeypatch):
        monkeypatch.setenv("MXTPU_PERFSCOPE_TRIVIAL_FLOPS", "tiny")
        assert ps.classify(1e12, 1e6, V5E)["verdict"] == "compute_bound"
        ps.record_program("t_env", 1e12, 1e6)       # never raises

    def test_bf16_uses_doubled_peak(self):
        from incubator_mxnet_tpu.perfscope.cost import peak_flops_for
        peaks = {"peak_flops_f32": 1.0, "peak_flops_bf16": 2.0}
        assert peak_flops_for("bfloat16", peaks) == 2.0
        assert peak_flops_for(jnp.float32, peaks) == 1.0


# ---------------------------------------------------------------------------
# cost analysis of real programs (CPU backend)
# ---------------------------------------------------------------------------

class TestAnalyze:
    def test_matmul_lowered(self, v5e_table):
        ps.enable()
        lowered = jax.jit(lambda a, b: (a @ b).sum()).lower(
            jax.ShapeDtypeStruct((256, 256), jnp.float32),
            jax.ShapeDtypeStruct((256, 256), jnp.float32))
        rec = ps.analyze_lowered(lowered, "t_matmul")
        assert rec["flops"] and rec["flops"] > 2 * 256 ** 3 * 0.9
        assert rec["verdict"] in ("compute_bound", "hbm_bound")
        names = [p["name"] for p in ps.programs()]
        assert "t_matmul" in names
        c = _counters()
        assert c["perfscope/perfscope.programs_analyzed"] >= 1

    def test_identity_program_missing_keys_is_unknown(self):
        # XLA:CPU reports an EMPTY analysis for data-movement-only
        # programs — the satellite's missing-cost_analysis-keys case
        ps.enable()
        lowered = jax.jit(lambda a: a).lower(
            jax.ShapeDtypeStruct((8,), jnp.float32))
        rec = ps.analyze_lowered(lowered, "t_identity")
        assert rec["verdict"] == "unknown"
        assert rec["flops"] is None
        assert _counters()["perfscope/perfscope.unknown"] >= 1

    def test_analyze_lowered_never_raises(self):
        ps.enable()
        rec = ps.analyze_lowered(object(), "t_garbage")
        assert rec["verdict"] == "unknown"

    def test_analyze_jit_never_raises(self):
        ps.enable()
        rec = ps.analyze_jit(object(), (jnp.ones(3),), "t_garbage_jit")
        assert rec["verdict"] == "unknown"

    def test_flight_compile_span_gains_cost_fields(self, tmp_path):
        # satellite: compile-span records carry flops/bytes/roofline
        diag.enable_flight_recorder(dump_dir=str(tmp_path),
                                    dump_on_crash=False)
        ps.enable()
        lowered = jax.jit(lambda a, b: a @ b).lower(
            jax.ShapeDtypeStruct((128, 128), jnp.float32),
            jax.ShapeDtypeStruct((128, 128), jnp.float32))
        ps.analyze_lowered(lowered, "t_flight")
        path = diag.dump_flight(reason="test")
        doc = json.load(open(path))
        spans = [e for e in doc["events"]
                 if e["kind"] == "compile"
                 and e["name"] == "perfscope.cost:t_flight"]
        assert len(spans) == 1
        args = spans[0]["args"]
        assert args["flops"] > 0
        assert args["bytes_accessed"] > 0
        assert args["roofline"] in ps.ROOFLINE_VERDICTS
        # the pretty-printer renders the enriched span without crashing
        mxdiag = _load_tool("mxdiag")
        mxdiag.print_flight(doc, 10)

    def test_last_analysis_wins_per_name(self, v5e_table):
        ps.enable()
        ps.record_program("t_dup", 1e12, 1e6)
        ps.record_program("t_dup", 1e9, 1e9)
        recs = [p for p in ps.programs() if p["name"] == "t_dup"]
        assert len(recs) == 1 and recs[0]["verdict"] == "hbm_bound"


# ---------------------------------------------------------------------------
# compile-site integration
# ---------------------------------------------------------------------------

def _tiny_net(units=8, in_units=16):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(units, in_units=in_units))
    net.initialize(init=mx.init.Xavier())
    return net


class TestCompileSites:
    def test_fused_step_capture(self):
        from incubator_mxnet_tpu.parallel import FusedTrainStep
        ps.enable()
        net = _tiny_net()
        L = gluon.loss.SoftmaxCrossEntropyLoss()
        step = FusedTrainStep(net, L, mx.optimizer.create("sgd"))
        x = nd.array(np.random.rand(4, 16).astype(np.float32))
        y = nd.array(np.random.randint(0, 8, 4))
        float(step(x, y))
        by_name = {p["name"]: p for p in ps.programs()}
        assert "fused_step" in by_name
        assert by_name["fused_step"]["kind"] == "train_step"
        assert by_name["fused_step"]["verdict"] in ps.ROOFLINE_VERDICTS
        # analysis happens once, not per step
        n0 = _counters()["perfscope/perfscope.programs_analyzed"]
        float(step(x, y))
        assert _counters()["perfscope/perfscope.programs_analyzed"] == n0

    def test_reanalysis_on_batch_signature_change(self):
        # a shape-driven recompile must refresh the program record —
        # the table has to describe the program actually being timed
        from incubator_mxnet_tpu.parallel import FusedTrainStep
        ps.enable()
        net = _tiny_net()
        L = gluon.loss.SoftmaxCrossEntropyLoss()
        step = FusedTrainStep(net, L, mx.optimizer.create("sgd"))
        x4 = nd.array(np.random.rand(4, 16).astype(np.float32))
        y4 = nd.array(np.random.randint(0, 8, 4))
        float(step(x4, y4))
        flops4 = {p["name"]: p["flops"] for p in ps.programs()}["fused_step"]
        x16 = nd.array(np.random.rand(16, 16).astype(np.float32))
        y16 = nd.array(np.random.randint(0, 8, 16))
        float(step(x16, y16))
        flops16 = {p["name"]: p["flops"] for p in ps.programs()}["fused_step"]
        assert flops16 > flops4

    def test_capture_does_not_double_count_selection(self):
        # perfscope's re-lowering must not re-increment the pallas
        # selection counters (ops/select quiet scope)
        from incubator_mxnet_tpu.ops import select as sel
        ps.enable()
        before = prof.counters().get("ops/pallas.selected.t_fake", 0) or 0
        with sel.quiet():
            sel._decide("t_fake", True, "ok")
        after = prof.counters().get("ops/pallas.selected.t_fake", 0) or 0
        assert after == before
        sel._decide("t_fake", True, "ok")    # un-quieted still counts
        assert prof.counters()["ops/pallas.selected.t_fake"] == before + 1

    def test_run_k_capture(self):
        from incubator_mxnet_tpu.parallel import FusedTrainStep
        ps.enable()
        net = _tiny_net()
        L = gluon.loss.SoftmaxCrossEntropyLoss()
        step = FusedTrainStep(net, L, mx.optimizer.create("sgd"))
        x = nd.array(np.random.rand(4, 16).astype(np.float32))
        y = nd.array(np.random.randint(0, 8, 4))
        xs = jnp.broadcast_to(x._data, (2,) + x._data.shape)
        ys = jnp.broadcast_to(y._data, (2,) + y._data.shape)
        float(step.run_k(xs, ys)[1])
        by_name = {p["name"]: p for p in ps.programs()}
        assert "fused_step_k2" in by_name
        assert by_name["fused_step_k2"]["k"] == 2

    def test_disabled_no_capture(self):
        from incubator_mxnet_tpu.parallel import FusedTrainStep
        assert not ps.enabled()
        net = _tiny_net()
        L = gluon.loss.SoftmaxCrossEntropyLoss()
        step = FusedTrainStep(net, L, mx.optimizer.create("sgd"))
        x = nd.array(np.random.rand(4, 16).astype(np.float32))
        y = nd.array(np.random.randint(0, 8, 4))
        float(step(x, y))
        assert all(not p["name"].startswith("fused_step")
                   for p in ps.programs())

    def test_jit_cache_capture(self):
        ps.enable()
        net = _tiny_net()
        net.hybridize()
        x = nd.array(np.random.rand(4, 16).astype(np.float32))
        net(x)
        jit_progs = [p for p in ps.programs() if p["kind"] == "jit_cache"]
        assert len(jit_progs) == 1
        assert jit_progs[0]["name"].startswith("jit:")
        assert jit_progs[0]["name"].endswith("4x16")

    def test_jit_cache_capture_opt_out(self):
        ps.enable(capture_jit_cache=False)
        net = _tiny_net()
        net.hybridize()
        net(nd.array(np.random.rand(4, 16).astype(np.float32)))
        assert not [p for p in ps.programs() if p["kind"] == "jit_cache"]

    def test_frozen_bucket_capture(self):
        from incubator_mxnet_tpu.serving import FrozenModel
        ps.enable()
        net = _tiny_net(units=4)
        FrozenModel(net, (16,), batch_buckets=(1, 2))
        buckets = sorted(p["bucket"] for p in ps.programs()
                         if p["kind"] == "serving_bucket")
        assert buckets == [1, 2]


# ---------------------------------------------------------------------------
# step-time decomposition
# ---------------------------------------------------------------------------

class TestStepBudget:
    @pytest.mark.parametrize("probe_ms,device_ms,host_gap_ms,other_ms", [
        (6.0, 6.0, 1.0, 3.0),     # the probe fits the 10 ms wall
        (12.0, 10.0, 0.0, 0.0),   # it cannot: clipped to the wall
    ], ids=["probe-under-wall", "probe-clipped"])
    def test_components_sum_to_step(self, v5e_table, monkeypatch, probe_ms,
                                    device_ms, host_gap_ms, other_ms):
        """Fixed durations, not this machine's: 8 steps of 10 ms with 1 ms
        of dispatch each, a probe on a clock the test winds, and a counter
        registry no other thread of the worker writes to. (The test used
        to time a real loop and hold the sum within 15% of the wall; under
        six workers that was the tree's one failing test.)"""
        from incubator_mxnet_tpu.perfscope import decomp
        ps.enable()
        monkeypatch.setattr(decomp, "_registry_snapshot", dict)
        budget = ps.StepBudget().begin()
        for _ in range(8):
            budget.add_dispatch(0.001)
        budget.end(steps=8, steady_s=0.080)
        clock = [0.0]

        def sync_step():
            clock[0] += probe_ms / 1e3

        with monkeypatch.context() as wound:
            wound.setattr(decomp.time, "perf_counter", lambda: clock[0])
            budget.probe(sync_step, iters=3)
        d = budget.finish(model_flops_per_step=2 * 64 ** 3)
        assert d["step_ms"] == pytest.approx(10.0)
        assert d["probe"]["median_ms"] == pytest.approx(probe_ms)
        # device is probe-clipped to the wall, so the sum never exceeds
        # step_ms; host gap is capped by the dispatch time measured
        assert d["device_compute_ms"] == pytest.approx(device_ms)
        assert d["host_gap_ms"] == pytest.approx(host_gap_ms)
        assert d["other_ms"] == pytest.approx(other_ms)
        comps = (d["device_compute_ms"] + d["collective_ms"]
                 + d["input_wait_ms"] + d["host_gap_ms"] + d["other_ms"])
        assert comps == pytest.approx(d["sum_ms"], abs=1e-3)
        assert comps == pytest.approx(d["step_ms"], abs=1e-3)
        assert d["mfu"] is not None and d["mfu"] > 0
        g = _counters()
        assert g["perfscope/perfscope.step_ms"] == d["step_ms"]
        assert g["perfscope/perfscope.device_compute_ms"] == \
            d["device_compute_ms"]

    def test_input_wait_from_io_counter(self):
        ps.enable()
        budget = ps.StepBudget().begin()
        prof.counter("io.wait_ms", "io").increment(40.0)
        budget.end(steps=4, steady_s=0.1)   # 25 ms/step, 10 ms input wait
        d = budget.finish()
        assert d["input_wait_ms"] == pytest.approx(10.0)
        assert d["step_ms"] == pytest.approx(25.0)

    def test_collective_from_kvstore_counter(self):
        ps.enable()
        budget = ps.StepBudget().begin()
        prof.counter("kvstore.collective_ms").increment(20.0)
        budget.end(steps=4, steady_s=0.1)
        d = budget.finish()
        assert d["collective_ms"] == pytest.approx(5.0)

    def test_host_gap_capped_by_dispatch(self):
        ps.enable()
        budget = ps.StepBudget().begin()
        budget.add_dispatch(0.004)          # 1 ms/step measured host time
        budget.end(steps=4, steady_s=0.1)   # 25 ms/step wall
        d = budget.finish()
        # no probe: unexplained middle goes to device, host_gap <= 1ms
        assert d["host_gap_ms"] <= 1.0 + 1e-6
        assert d["device_compute_ms"] >= 23.0

    def test_probe_feeds_histogram(self):
        prof.reset_counters()
        p = ps.probe_device_time(lambda: None, iters=4)
        assert p["iters"] == 4 and p["median_ms"] >= 0
        h = prof.counters()["perfscope/perfscope.device_step_ms"]
        assert h["count"] == 4

    def test_mfu_counterfactuals(self, v5e_table):
        ps.enable()
        budget = ps.StepBudget().begin()
        prof.counter("io.wait_ms", "io").increment(200.0)  # 50 ms/step
        budget.end(steps=4, steady_s=0.4)                  # 100 ms/step
        d = budget.finish(model_flops_per_step=1e9)
        # removing 50 ms of input wait from a 100 ms step doubles MFU
        assert d["mfu_if_removed"]["input_wait"] == \
            pytest.approx(2 * d["mfu"], rel=1e-3)

    def test_no_mfu_on_a_device_without_peaks(self):
        """This CPU has no row in the peak table: the budget still
        decomposes the step, and reports no utilisation at all."""
        ps.enable()
        budget = ps.StepBudget().begin()
        budget.end(steps=4, steady_s=0.4)
        d = budget.finish(model_flops_per_step=1e9)
        assert d["step_ms"] == pytest.approx(100.0)
        assert d["mfu"] is None and d["peak_flops"] is None
        assert d["mfu_device_only"] is None
        assert set(d["mfu_if_removed"].values()) == {None}


class TestKVStoreCollectiveCounter:
    def test_timed_increments_when_perfscope_on(self):
        from incubator_mxnet_tpu.kvstore import _timed
        ps.enable()
        before = prof.counters().get("mxtpu/kvstore.collective_ms", 0)
        out = _timed("push", lambda: 42)
        assert out == 42
        after = prof.counters().get("mxtpu/kvstore.collective_ms", 0)
        assert after >= before >= 0 and after > 0

    def test_timed_passthrough_when_all_off(self):
        from incubator_mxnet_tpu.kvstore import _timed
        assert not ps.enabled()
        assert _timed("push", lambda: 7) == 7


# ---------------------------------------------------------------------------
# histogram percentiles under the perfscope family (satellite)
# ---------------------------------------------------------------------------

class TestPerfscopeHistogram:
    def test_percentile_interpolation(self):
        prof.reset_counters()
        h = prof.histogram("perfscope.device_step_ms", "perfscope")
        for v in (1.0, 2.0, 3.0, 4.0, 100.0):
            h.observe(v)
        snap = h.value
        assert snap["count"] == 5
        assert snap["min"] == 1.0 and snap["max"] == 100.0
        assert snap["p50"] <= snap["p95"] <= snap["p99"]
        # p50 of {1,2,3,4,100} lives in a low bucket; p99 near the max
        assert snap["p50"] <= 5.0
        assert snap["p99"] >= 50.0

    def test_single_observation_percentiles_clamped(self):
        prof.reset_counters()
        h = prof.histogram("perfscope.device_step_ms", "perfscope")
        h.observe(7.5)
        snap = h.value
        assert snap["p50"] == snap["p95"] == snap["p99"] == 7.5

    def test_empty_histogram(self):
        prof.reset_counters()
        h = prof.histogram("perfscope.device_step_ms", "perfscope")
        snap = h.value
        assert snap["count"] == 0 and snap["p50"] is None

    def test_family_table_accepts_histogram_kind(self):
        tc = _load_tool("trace_check")
        assert tc.check_healthmon_kinds(
            {"perfscope/perfscope.device_step_ms": "histogram"}) == []
        # a flipped kind is a schema violation
        assert tc.check_healthmon_kinds(
            {"perfscope/perfscope.device_step_ms": "counter"})


# ---------------------------------------------------------------------------
# trace_check: perfscope families + extra.perfscope schema
# ---------------------------------------------------------------------------

class TestTraceCheckPerfscope:
    def _good_section(self):
        return {
            "peaks": {"device_kind": "cpu", "table_row": "cpu",
                      "peak_flops_f32": 5e10, "peak_flops_bf16": 5e10,
                      "hbm_bytes_per_s": 2e10},
            "programs": [{"name": "fused_step", "verdict": "compute_bound",
                          "flops": 1e9, "bytes_accessed": 1e6, "ai": 1000.0}],
            "decomposition": {"step_ms": 100.0, "device_compute_ms": 90.0,
                              "collective_ms": 2.0, "input_wait_ms": 3.0,
                              "host_gap_ms": 4.0, "other_ms": 1.0,
                              "mfu": 0.2},
        }

    def test_good_section_validates(self):
        tc = _load_tool("trace_check")
        assert tc.check_perfscope_extra(self._good_section()) == []
        assert tc.check_perfscope_extra(None) == []

    def test_bad_verdict_fails(self):
        tc = _load_tool("trace_check")
        bad = self._good_section()
        bad["programs"][0]["verdict"] = "gpu_bound"
        assert any("verdict" in e for e in tc.check_perfscope_extra(bad))

    def test_sum_tolerance_enforced(self):
        tc = _load_tool("trace_check")
        bad = self._good_section()
        bad["decomposition"]["device_compute_ms"] = 10.0  # sum 20 vs 100
        assert any("sum" in e for e in tc.check_perfscope_extra(bad))

    def test_mfu_bounds(self):
        tc = _load_tool("trace_check")
        bad = self._good_section()
        bad["decomposition"]["mfu"] = 3.0
        assert any("mfu" in e for e in tc.check_perfscope_extra(bad))

    def test_unknown_family_fails(self):
        tc = _load_tool("trace_check")
        errs = tc.check_healthmon_kinds(
            {"perfscope/perfscope.invented": "counter"})
        assert errs and "PERFSCOPE_FAMILIES" in errs[0]

    def test_bench_json_with_perfscope(self, tmp_path):
        tc = _load_tool("trace_check")
        doc = {"metric": "m", "value": 1.0, "unit": "images/sec",
               "extra": {"mfu": 0.1, "perfscope": self._good_section()}}
        p = tmp_path / "BENCH_t.json"
        p.write_text(json.dumps(doc))
        assert tc.check_bench_json(str(p)) == []
        doc["extra"]["perfscope"]["programs"][0]["verdict"] = "nope"
        p.write_text(json.dumps(doc))
        assert tc.check_bench_json(str(p))


# ---------------------------------------------------------------------------
# perf_regress: the regression gate (satellite + acceptance)
# ---------------------------------------------------------------------------

def _bench_doc(value=1000.0, mfu=0.12, metric="m_img_s", p99=None,
               **over):
    doc = {"metric": metric, "value": value, "unit": "images/sec",
           "vs_baseline": None, "extra": {"mfu": mfu}}
    if p99 is not None:
        doc["extra"]["serving"] = {"p99_ms": p99}
    doc.update(over)
    return doc


class TestPerfRegress:
    def _write(self, tmp_path, name, doc):
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        return str(p)

    def test_self_comparison_passes(self, tmp_path):
        pr = _load_tool("perf_regress")
        a = self._write(tmp_path, "BENCH_a.json", _bench_doc())
        assert pr.main([a, a]) == 0

    def test_20pct_regression_fails(self, tmp_path):
        pr = _load_tool("perf_regress")
        a = self._write(tmp_path, "BENCH_a.json", _bench_doc(1000.0))
        b = self._write(tmp_path, "BENCH_b.json",
                        _bench_doc(800.0, mfu=0.096))
        assert pr.main([a, b]) == 1

    def test_mfu_only_regression_fails(self, tmp_path):
        pr = _load_tool("perf_regress")
        a = self._write(tmp_path, "BENCH_a.json", _bench_doc(1000.0, 0.12))
        b = self._write(tmp_path, "BENCH_b.json", _bench_doc(1000.0, 0.08))
        assert pr.main([a, b]) == 1

    def test_p99_regression_fails(self, tmp_path):
        pr = _load_tool("perf_regress")
        a = self._write(tmp_path, "BENCH_a.json", _bench_doc(p99=10.0))
        b = self._write(tmp_path, "BENCH_b.json", _bench_doc(p99=20.0))
        assert pr.main([a, b]) == 1

    def test_small_drop_within_threshold_passes(self, tmp_path):
        pr = _load_tool("perf_regress")
        a = self._write(tmp_path, "BENCH_a.json", _bench_doc(1000.0, 0.12))
        b = self._write(tmp_path, "BENCH_b.json", _bench_doc(970.0, 0.1175))
        assert pr.main([a, b]) == 0

    def test_env_failure_candidate_skipped(self, tmp_path):
        pr = _load_tool("perf_regress")
        a = self._write(tmp_path, "BENCH_a.json", _bench_doc())
        b = self._write(tmp_path, "BENCH_b.json",
                        {"metric": "m_img_s", "value": 0.0,
                         "unit": "images/sec", "status": "env_failure",
                         "error": "preflight: probe hung"})
        assert pr.main([a, b]) == 0

    def test_legacy_error_artifact_skipped(self, tmp_path):
        # the BENCH_r02-r05 shape: driver wrapper, watchdog error line
        pr = _load_tool("perf_regress")
        a = self._write(tmp_path, "BENCH_a.json", _bench_doc())
        b = self._write(tmp_path, "BENCH_b.json", {
            "n": 2, "cmd": "python tools/serve_load.py", "rc": 3,
            "parsed": {"metric": "m_img_s", "value": 0.0,
                       "unit": "images/sec", "vs_baseline": 0.0,
                       "error": "hard watchdog: backend init exceeded"}})
        assert pr.main([a, b]) == 0
        rec, why = pr.load_artifact(b)
        assert rec is None and "errored" in why

    def test_wrapper_with_null_parsed_skipped(self, tmp_path):
        pr = _load_tool("perf_regress")
        b = self._write(tmp_path, "BENCH_b.json",
                        {"n": 1, "cmd": "x", "rc": 1, "parsed": None})
        rec, why = pr.load_artifact(b)
        assert rec is None and "parsed" in why

    def test_trajectory_skips_env_failures(self, tmp_path):
        pr = _load_tool("perf_regress")
        self._write(tmp_path, "BENCH_r01.json", _bench_doc(1000.0))
        self._write(tmp_path, "BENCH_r02.json",
                    {"n": 2, "cmd": "x", "rc": 3,
                     "parsed": {"metric": "m_img_s", "value": 0.0,
                                "error": "hard watchdog"}})
        self._write(tmp_path, "BENCH_r03.json", _bench_doc(1020.0))
        self._write(tmp_path, "BENCH_r04.json", _bench_doc(990.0))
        # newest (r04) vs median of r01/r03: fine
        assert pr.main(["--dir", str(tmp_path)]) == 0
        # a degraded newest artifact trips the gate
        self._write(tmp_path, "BENCH_r05.json", _bench_doc(700.0, 0.08))
        assert pr.main(["--dir", str(tmp_path)]) == 1

    def test_trajectory_all_env_failures_is_ok(self, tmp_path):
        pr = _load_tool("perf_regress")
        self._write(tmp_path, "BENCH_r01.json",
                    {"n": 1, "cmd": "x", "rc": 3, "parsed": None})
        assert pr.main(["--dir", str(tmp_path)]) == 0

    def test_noise_widens_threshold(self, tmp_path):
        pr = _load_tool("perf_regress")
        # noisy trajectory: ±10% scatter; a 12% drop on the newest run
        # must NOT be flagged against a 2x noise band (20%)
        for i, v in enumerate((900.0, 1100.0, 1000.0), 1):
            self._write(tmp_path, f"BENCH_r0{i}.json",
                        _bench_doc(v, mfu=None))
        self._write(tmp_path, "BENCH_r04.json", _bench_doc(880.0, mfu=None))
        assert pr.main(["--dir", str(tmp_path)]) == 0

    def test_metric_mismatch_not_compared(self, tmp_path):
        pr = _load_tool("perf_regress")
        a = self._write(tmp_path, "BENCH_a.json",
                        _bench_doc(metric="resnet"))
        b = self._write(tmp_path, "BENCH_b.json",
                        _bench_doc(value=1.0, metric="lenet"))
        assert pr.main([a, b]) == 0


# ---------------------------------------------------------------------------
# mxdiag perf report
# ---------------------------------------------------------------------------

class TestMxdiagPerf:
    def test_report_renders(self, tmp_path, capsys):
        mxdiag = _load_tool("mxdiag")
        doc = _bench_doc()
        doc["extra"]["perfscope"] = {
            "peaks": {"device_kind": "cpu", "table_row": "cpu",
                      "peak_flops_f32": 5e10, "peak_flops_bf16": 5e10,
                      "hbm_bytes_per_s": 2e10},
            "programs": [{"name": "fused_step", "verdict": "compute_bound",
                          "flops": 8.7e8, "bytes_accessed": 2.2e8,
                          "ai": 3.9}],
            "decomposition": {"step_ms": 100.0, "device_compute_ms": 80.0,
                              "collective_ms": 5.0, "input_wait_ms": 10.0,
                              "host_gap_ms": 5.0, "other_ms": 0.0,
                              "steps": 50, "source": "probe",
                              "coverage": 1.0, "mfu": 0.1,
                              "mfu_device_only": 0.125,
                              "mfu_if_removed": {"input_wait": 0.111}},
        }
        p = tmp_path / "BENCH_x.json"
        p.write_text(json.dumps(doc))
        assert mxdiag.main(["perf", str(p)]) == 0
        out = capsys.readouterr().out
        assert "step budget" in out
        assert "device_compute" in out
        assert "MFU decomposition" in out
        assert "compute_bound" in out

    def test_report_without_perfscope_section(self, tmp_path, capsys):
        mxdiag = _load_tool("mxdiag")
        p = tmp_path / "BENCH_y.json"
        p.write_text(json.dumps(_bench_doc()))
        assert mxdiag.main(["perf", str(p)]) == 1
        assert "no extra.perfscope" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# bench_extra payload
# ---------------------------------------------------------------------------

class TestBenchExtra:
    def test_payload_shape_validates(self, v5e_table):
        ps.enable()
        lowered = jax.jit(lambda a, b: a @ b).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
            jax.ShapeDtypeStruct((64, 64), jnp.float32))
        ps.analyze_lowered(lowered, "t_payload")
        payload = ps.bench_extra({"step_ms": 10.0, "device_compute_ms": 9.0,
                                  "collective_ms": 0.0,
                                  "input_wait_ms": 0.5, "host_gap_ms": 0.5,
                                  "other_ms": 0.0})
        tc = _load_tool("trace_check")
        assert tc.check_perfscope_extra(payload) == []
        assert json.loads(json.dumps(payload))  # JSON-serializable

    def test_enable_from_env(self, monkeypatch):
        monkeypatch.setenv("MXTPU_PERFSCOPE", "1")
        ps.enable_from_env()
        assert ps.enabled() and ps._PS.capture_jit_cache
        ps.disable()
        monkeypatch.setenv("MXTPU_PERFSCOPE", "jit0")
        ps.enable_from_env()
        assert ps.enabled() and not ps._PS.capture_jit_cache
