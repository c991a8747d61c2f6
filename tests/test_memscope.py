"""mxtpu.memscope: static per-program footprints (capture through the
perfscope funnel and direct lowered/compiled handoff, unavailable
backends degrade to the honest all-None shape), the bounded watermark
ring, capacity/headroom math with the like-with-like pairing,
analytic-vs-measured reconciliation incl. the drift warning, OOM
forensics assembled from a synthesized RESOURCE_EXHAUSTED, the off
path's one-predicate contract, the deep-/healthz headroom embed, and the
tooling satellites (trace_check check_memscope_extra both ways,
perf_regress peak-memory gate incl. both-sides and same-instrument
skips, mxdiag mem rendering, profiler.device_memory_stats
normalization)."""
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd
from incubator_mxnet_tpu import memscope as ms
from incubator_mxnet_tpu import perfscope as ps
from incubator_mxnet_tpu import profiler as prof
from incubator_mxnet_tpu.memscope import footprint as fp
from incubator_mxnet_tpu.memscope import forensics as forens
from incubator_mxnet_tpu.memscope.watermark import (WatermarkRing,
                                                    host_rss_bytes)
from incubator_mxnet_tpu.profiler import tpu as prof_tpu

GiB = 2 ** 30


def _load_tool(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _memscope_teardown(monkeypatch):
    # the capacity/headroom knobs must come from THIS test, never from
    # the invoking shell
    for var in ("MXTPU_MEMSCOPE", "MXTPU_MEMSCOPE_RING",
                "MXTPU_MEMSCOPE_HEADROOM", "MXTPU_MEMSCOPE_CAPACITY"):
        monkeypatch.delenv(var, raising=False)
    yield
    ms.disable()
    ms.reset()
    ps.disable()          # ms.enable() arms perfscope too
    ps.reset_programs()
    assert not prof_tpu.tracing(), \
        "a test leaked an active jax profiler trace"


def _counters(prefix="memscope/"):
    return {k: v for k, v in prof.counters().items()
            if k.startswith(prefix)}


def _lowered(n=8):
    def f(x):
        return jnp.tanh(x @ x.T).sum()
    return jax.jit(f).lower(jnp.zeros((n, n), jnp.float32))


# ---------------------------------------------------------------------------
# static footprints
# ---------------------------------------------------------------------------

class TestFootprint:
    def test_capture_from_lowered_derives_peak_on_cpu(self):
        before = _counters().get("memscope/memscope.programs_captured", 0)
        low = _lowered()
        rec = fp.capture("prog_lowered", lowered=low)
        assert rec["available"] is True
        # a peak is "reported" only where the backend's analysis carries
        # one (jaxlib 0.9.0's XLA:CPU does); otherwise it must be DERIVED
        # (arg+out+temp+code), never invented as "reported"
        said = getattr(low.compile().memory_analysis(),
                       "peak_memory_in_bytes", None)
        if isinstance(said, int) and said >= 0:
            assert rec["provenance"] == "reported"
            assert rec["peak_bytes"] == said
        else:
            assert rec["provenance"] == "derived"
            assert rec["peak_bytes"] == sum(
                rec[f] or 0 for f in ("argument_bytes", "output_bytes",
                                      "temp_bytes", "generated_code_bytes"))
        assert isinstance(rec["peak_bytes"], int) and rec["peak_bytes"] > 0
        for f in fp.BYTE_FIELDS:
            v = rec[f]
            assert v is None or (isinstance(v, int) and v >= 0), (f, v)
        assert fp.footprint_of("prog_lowered") == rec
        after = _counters()["memscope/memscope.programs_captured"]
        assert after == before + 1

    def test_peak_is_derived_when_backend_carries_none(self):
        class _Analysis:
            argument_size_in_bytes = 100
            output_size_in_bytes = 10
            temp_size_in_bytes = 50
            generated_code_size_in_bytes = 5

        class _Compiled:
            def memory_analysis(self):
                return _Analysis()

        rec = fp.capture("prog_no_peak", compiled=_Compiled())
        assert rec["provenance"] == "derived"
        assert rec["peak_bytes"] == 165      # arg + out + temp + code

    def test_capture_from_compiled_is_equivalent(self):
        low = _lowered()
        via_lowered = fp.capture("prog_a", lowered=low)
        via_compiled = fp.capture("prog_b", compiled=low.compile())
        for f in fp.BYTE_FIELDS + ("peak_bytes", "provenance"):
            assert via_lowered[f] == via_compiled[f], f

    def test_reported_peak_when_backend_carries_one(self):
        class _Analysis:
            argument_size_in_bytes = 100
            output_size_in_bytes = 10
            temp_size_in_bytes = 50
            generated_code_size_in_bytes = 5
            peak_memory_in_bytes = 999

        class _Compiled:
            def memory_analysis(self):
                return _Analysis()

        rec = fp.capture("prog_tpu_like", compiled=_Compiled())
        assert rec["provenance"] == "reported"
        assert rec["peak_bytes"] == 999      # the backend's word wins

    def test_unavailable_backend_degrades_counted_not_raised(self):
        class _Compiled:
            def memory_analysis(self):
                raise NotImplementedError("no analysis on this backend")

        before = _counters().get("memscope/memscope.capture_unknown", 0)
        rec = fp.capture("prog_dark", compiled=_Compiled())
        assert rec["available"] is False
        assert rec["provenance"] == "unavailable"
        # honest Nones, not invented zeros (trace_check pins this too)
        for f in fp.BYTE_FIELDS + ("peak_bytes",):
            assert rec[f] is None, f
        assert _counters()["memscope/memscope.capture_unknown"] \
            == before + 1

    def test_capture_never_raises_on_garbage(self):
        # object() has no .compile / .memory_analysis: the record
        # degrades instead of the compile site blowing up
        rec = fp.capture("prog_junk", lowered=object())
        assert rec["available"] is False

    def test_recompile_overwrites_by_name(self):
        fp.capture("prog_x", lowered=_lowered(4))
        small = fp.footprint_of("prog_x")["peak_bytes"]
        fp.capture("prog_x", lowered=_lowered(64))
        big = fp.footprint_of("prog_x")["peak_bytes"]
        assert big > small
        assert sum(1 for r in fp.footprints()
                   if r["name"] == "prog_x") == 1

    def test_perfscope_funnel_captures_when_armed(self):
        ms.enable()
        assert ps.enabled()          # memscope arms its host layer
        net = gluon.nn.Dense(4, in_units=6)
        net.initialize()
        net.hybridize()
        net(nd.array(np.zeros((2, 6), np.float32)))
        recs = fp.footprints()
        assert recs, "hybridize jit cache compile produced no footprint"
        assert any(r["available"] for r in recs)
        # the join key: every footprint name must resolve a perfscope
        # roofline verdict in the bench payload
        joined = ms.bench_extra()["programs"]
        assert any(r.get("roofline") is not None for r in joined), \
            [r.get("name") for r in joined]

    def test_off_path_funnel_does_not_capture(self):
        ps.enable()                  # perfscope alone, memscope off
        net = gluon.nn.Dense(4, in_units=6)
        net.initialize()
        net.hybridize()
        net(nd.array(np.zeros((2, 6), np.float32)))
        assert fp.footprints() == []


# ---------------------------------------------------------------------------
# watermark ring
# ---------------------------------------------------------------------------

class TestWatermarkRing:
    def test_ring_stays_bounded_while_samples_count_total(self):
        r = WatermarkRing(4)
        for i in range(10):
            r.sample(step=i)
        s = r.summary()
        assert s["samples"] == 10
        assert s["ring"] == 4 and s["ring_limit"] == 4
        # oldest evicted: the survivors are the LAST four steps
        assert [t["step"] for t in r.snapshot()] == [6, 7, 8, 9]
        assert len(s["tail"]) <= 8

    def test_cpu_devices_degrade_but_host_rss_is_real(self):
        r = WatermarkRing(8)
        rec = r.sample(step=1)
        # XLA:CPU devices report no allocator stats
        assert rec["available"] is False
        assert all(d == {"available": False}
                   for d in rec["devices"].values())
        assert rec["host_rss_bytes"] and rec["host_rss_bytes"] > 0
        s = r.summary()
        assert s["device"] is None
        rss = s["host_rss"]
        assert rss["peak"] >= rss["latest"] > 0
        assert rss["p50"] <= rss["p95"] <= rss["peak"]

    def test_limit_sanitized(self):
        assert WatermarkRing("bogus").limit == 256
        assert WatermarkRing(0).limit == 1
        assert WatermarkRing(-3).limit == 1

    def test_module_sample_off_is_none_and_uncounted(self):
        before = _counters().get("memscope/memscope.samples", 0)
        assert ms.sample(step=1) is None     # _MS is None: one predicate
        assert ms.watermark_summary() is None
        assert _counters().get("memscope/memscope.samples", 0) == before

    def test_module_sample_armed_counts_and_respects_ring_knob(
            self, monkeypatch):
        monkeypatch.setenv("MXTPU_MEMSCOPE_RING", "3")
        before = _counters().get("memscope/memscope.samples", 0)
        ms.enable()
        for i in range(5):
            ms.sample(step=i, workload="train")
        s = ms.watermark_summary()
        assert s["ring_limit"] == 3 and s["ring"] == 3
        assert s["samples"] == 5
        assert _counters()["memscope/memscope.samples"] == before + 5

    def test_host_rss_bytes_positive_here(self):
        v = host_rss_bytes()
        assert v is not None and v > 0


# ---------------------------------------------------------------------------
# capacity + headroom
# ---------------------------------------------------------------------------

class TestHeadroom:
    def test_target_default_override_and_sanitation(self, monkeypatch):
        assert ms.headroom_target() == ms.DEFAULT_HEADROOM
        monkeypatch.setenv("MXTPU_MEMSCOPE_HEADROOM", "0.5")
        assert ms.headroom_target() == 0.5
        monkeypatch.setenv("MXTPU_MEMSCOPE_HEADROOM", "1.7")
        assert ms.headroom_target() == ms.DEFAULT_HEADROOM

    def test_capacity_env_override_beats_probing(self, monkeypatch):
        monkeypatch.setenv("MXTPU_MEMSCOPE_CAPACITY", str(8 * GiB))
        assert ms.device_capacity() == {"bytes": 8 * GiB,
                                        "source": "env"}

    def test_capacity_on_cpu_is_host_ram(self):
        cap = ms.device_capacity()
        # no allocator limits on XLA:CPU: host RAM is the honest bound
        assert cap["source"] == "host_ram"
        assert cap["bytes"] > 0

    def test_headroom_ok_under_roomy_capacity(self, monkeypatch):
        monkeypatch.setenv("MXTPU_MEMSCOPE_CAPACITY", str(1 << 45))
        hs = ms.headroom_state()
        assert hs["verdict"] == "ok"
        assert hs["in_use_source"] == "host_rss"   # like-with-like
        assert 0.0 < hs["headroom_fraction"] <= 1.0
        assert hs["in_use_bytes"] > 0
        assert hs["capacity_source"] == "env"

    def test_headroom_tight_when_capacity_tiny(self, monkeypatch):
        monkeypatch.setenv("MXTPU_MEMSCOPE_CAPACITY", "1024")
        hs = ms.headroom_state()
        assert hs["verdict"] == "tight"
        assert hs["headroom_fraction"] == 0.0     # clamped, never < 0

    def test_headroom_unknown_without_capacity(self, monkeypatch):
        monkeypatch.setattr(ms, "device_capacity",
                            lambda: {"bytes": None, "source": "unknown"})
        hs = ms.headroom_state()
        assert hs["verdict"] == "unknown"
        assert hs["headroom_fraction"] is None


# ---------------------------------------------------------------------------
# analytic-vs-measured reconciliation
# ---------------------------------------------------------------------------

class _FakeRing:
    """A ring whose device column reports — CPU can't produce one."""

    def __init__(self, peak):
        self._peak = peak

    def summary(self):
        return {"device": {"p50": self._peak, "p95": self._peak,
                           "peak": self._peak, "latest": self._peak}}

    def latest(self):
        return None

    def reset(self):
        pass


class TestReconciliation:
    def test_analytic_registers_and_reports(self, monkeypatch):
        # quiet the measured side: the ledger census would otherwise
        # report whatever live arrays earlier tests left behind
        from incubator_mxnet_tpu.diagnostics import memory as dmem
        monkeypatch.setattr(dmem, "reconcile", lambda: {})
        ms.register_analytic({"param_bytes_per_device": 1000,
                              "state_bytes_per_device": 2000,
                              "reduction": "3.3x"})
        rec = ms.reconciliation()
        assert rec["analytic"]["total_per_device"] == 3000
        assert rec["analytic"]["reduction"] == "3.3x"
        assert rec["drift_warning"] is False

    def test_malformed_analytic_dropped(self):
        ms.register_analytic("not a dict")
        assert ms.reconciliation()["analytic"] is None
        ms.register_analytic({"state_bytes_per_device": 5})  # no params
        assert ms.reconciliation()["analytic"] is None

    def test_drift_beyond_threshold_warns_and_counts(self):
        ms.enable()
        ms._MS.ring = _FakeRing(10 * GiB)     # measured says 10 GiB
        ms.register_analytic({"param_bytes_per_device": 1 * GiB,
                              "state_bytes_per_device": 0})
        before = _counters().get("memscope/memscope.drift_warnings", 0)
        with pytest.warns(UserWarning, match="gone stale"):
            rec = ms.reconciliation()
        assert rec["drift_warning"] is True
        assert rec["drift"]["per_device_bytes"] == 9.0
        assert rec["measured"]["source"] == "memory_stats"
        assert _counters()["memscope/memscope.drift_warnings"] \
            == before + 1

    def test_drift_within_threshold_is_quiet(self):
        ms.enable()
        ms._MS.ring = _FakeRing(int(1.1 * GiB))
        ms.register_analytic({"param_bytes_per_device": GiB,
                              "state_bytes_per_device": 0})
        rec = ms.reconciliation()
        assert rec["drift_warning"] is False
        assert rec["drift"]["per_device_bytes"] == pytest.approx(
            0.1, abs=1e-6)


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

class TestForensics:
    @pytest.mark.parametrize("exc,want", [
        (MemoryError(), True),
        (RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying "
                      "to allocate 17179869184 bytes."), True),
        (RuntimeError("Resource exhausted: ran out of HBM"), True),
        (RuntimeError("failed to allocate request for 2.0GiB"), True),
        (RuntimeError("std::bad_alloc"), True),
        (ValueError("shapes (3,4) and (5,6) not aligned"), False),
        (RuntimeError("INVALID_ARGUMENT: mesh mismatch"), False),
    ])
    def test_is_oom_error_taxonomy(self, exc, want):
        assert forens.is_oom_error(exc) is want

    def test_non_oom_error_records_nothing(self):
        before = _counters().get("memscope/memscope.oom_events", 0)
        assert ms.record_oom(ValueError("nope"), program="p") is None
        assert ms.last_post_mortem() is None
        assert _counters().get("memscope/memscope.oom_events", 0) \
            == before

    def test_post_mortem_from_synthesized_resource_exhausted(self):
        ms.enable(ring_limit=8)
        fp.capture("fused_step_b64", lowered=_lowered(16))
        for i in range(12):
            ms.sample(step=i, workload="train")
        err = RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to "
            "allocate 17179869184 bytes.")
        before = _counters().get("memscope/memscope.oom_events", 0)
        pm = ms.record_oom(err, program="fused_step_b64", step=11)
        assert pm is not None
        assert pm["schema"] == forens.OOM_SCHEMA
        assert pm["error_type"] == "RuntimeError"
        assert "RESOURCE_EXHAUSTED" in pm["error"]
        assert pm["program"] == "fused_step_b64" and pm["step"] == 11
        # the offending program's static footprint rides along
        assert pm["footprint"]["peak_bytes"] > 0
        # the watermark tail: what memory did in the steps before death
        assert 0 < len(pm["watermark_tail"]) <= 8
        assert pm["watermark_tail"][-1]["step"] == 11
        # the resolved settings that produced the shape
        assert pm["knobs"] == {"loop_chunk": 0, "prefetch_depth": 2,
                               "io_workers": 2, "pallas": "auto"}
        assert pm["capacity"]["source"] == "host_ram"
        assert _counters()["memscope/memscope.oom_events"] == before + 1
        # the last post-mortem is what extra.memscope.oom publishes
        assert ms.last_post_mortem() is pm
        assert ms.bench_extra()["oom"] is pm

    def test_forensics_never_masks_the_error(self):
        class _Hostile:
            def __str__(self):
                raise RuntimeError("even str() is broken")
        # is_oom_error and record_oom both swallow: the caller's
        # re-raise of the ORIGINAL error is never replaced
        assert forens.is_oom_error(_Hostile()) is False
        assert ms.record_oom(_Hostile()) is None


# ---------------------------------------------------------------------------
# bench payload + trace_check schema (satellite)
# ---------------------------------------------------------------------------

def _armed_extra():
    ms.enable(ring_limit=8)
    fp.capture("fused_step_b64", lowered=_lowered(16))
    for i in range(10):
        ms.sample(step=i)
    return ms.bench_extra()


class TestBenchExtraSchema:
    def test_real_payload_validates(self):
        tc = _load_tool("trace_check")
        extra = _armed_extra()
        extra = json.loads(json.dumps(extra))   # the BENCH round-trip
        assert tc.check_memscope_extra(extra) == []

    def test_absent_section_is_fine(self):
        tc = _load_tool("trace_check")
        assert tc.check_memscope_extra(None) == []

    def test_violations_flagged(self):
        tc = _load_tool("trace_check")
        base = json.loads(json.dumps(_armed_extra()))

        bad = json.loads(json.dumps(base))
        bad["programs"][0]["provenance"] = "guessed"
        assert any("provenance" in e
                   for e in tc.check_memscope_extra(bad))

        bad = json.loads(json.dumps(base))
        bad["watermarks"]["ring"] = bad["watermarks"]["ring_limit"] + 1
        assert any("unbounded ring" in e
                   for e in tc.check_memscope_extra(bad))

        bad = json.loads(json.dumps(base))
        bad["programs"][0].update(available=False,
                                  provenance="unavailable")
        # unavailable record must NOT keep its bytes
        assert any("unavailable record carries" in e
                   for e in tc.check_memscope_extra(bad))

        bad = json.loads(json.dumps(base))
        bad["headroom"]["verdict"] = "plenty"
        assert any("verdict" in e for e in tc.check_memscope_extra(bad))

        bad = json.loads(json.dumps(base))
        bad["capacity"] = {"bytes": None, "source": "host_ram"}
        assert any("bytes is null" in e
                   for e in tc.check_memscope_extra(bad))

        bad = json.loads(json.dumps(base))
        bad["oom"] = {"schema": "wrong/0", "error": "boom"}
        assert any("oom.schema" in e
                   for e in tc.check_memscope_extra(bad))

    def test_families_registered(self):
        tc = _load_tool("trace_check")
        fam = tc.MEMSCOPE_FAMILIES
        assert "memscope/memscope.programs_captured" in fam
        assert "memscope/memscope.oom_events" in fam
        assert "memscope/memscope.headroom_fraction" in fam


# ---------------------------------------------------------------------------
# perf_regress peak-memory gate (satellite)
# ---------------------------------------------------------------------------

def _artifact(tmp_path, name, peak=None, sect="host_rss", static=None,
              value=100.0):
    extra = {}
    if peak is not None:
        extra["memscope"] = {
            "programs": [],
            "watermarks": {"samples": 10, "ring": 8, "ring_limit": 8,
                           "available": sect == "device",
                           sect: {"p50": peak, "p95": peak,
                                  "peak": peak, "latest": peak}},
        }
    elif static is not None:
        extra["memscope"] = {
            "programs": [{"name": "fused", "peak_bytes": static}]}
    doc = {"metric": "images_sec", "value": value, "unit": "img/s",
           "extra": extra}
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


class TestPerfRegressPeakGate:
    def test_loader_extracts_peak_and_instrument(self, tmp_path):
        pr = _load_tool("perf_regress")
        rec, skip = pr.load_artifact(
            _artifact(tmp_path, "a.json", peak=GiB))
        assert skip is None
        assert rec["peak_bytes"] == GiB
        assert rec["peak_source"] == "watermark host_rss"
        rec2, _ = pr.load_artifact(
            _artifact(tmp_path, "b.json", static=GiB))
        assert rec2["peak_source"] == "static footprint"

    def test_growth_beyond_threshold_flags(self, tmp_path):
        pr = _load_tool("perf_regress")
        b, _ = pr.load_artifact(_artifact(tmp_path, "b.json", peak=GiB))
        c, _ = pr.load_artifact(
            _artifact(tmp_path, "c.json", peak=int(GiB * 1.3)))
        regs, _notes = pr.compare(b, c)
        assert any("peak memory" in r for r in regs), regs
        # within threshold: quiet
        c2, _ = pr.load_artifact(
            _artifact(tmp_path, "d.json", peak=int(GiB * 1.05)))
        regs2, _ = pr.compare(b, c2)
        assert not any("peak memory" in r for r in regs2), regs2

    def test_one_sided_is_a_note_not_a_gate(self, tmp_path):
        pr = _load_tool("perf_regress")
        b, _ = pr.load_artifact(_artifact(tmp_path, "b.json", peak=GiB))
        c, _ = pr.load_artifact(_artifact(tmp_path, "c.json"))
        regs, notes = pr.compare(b, c)
        assert not any("peak memory" in r for r in regs)
        assert any("peak" in n for n in notes), notes

    def test_instrument_mismatch_skips(self, tmp_path):
        pr = _load_tool("perf_regress")
        b, _ = pr.load_artifact(
            _artifact(tmp_path, "b.json", peak=GiB, sect="device"))
        c, _ = pr.load_artifact(
            _artifact(tmp_path, "c.json", peak=3 * GiB,
                      sect="host_rss"))
        regs, notes = pr.compare(b, c)
        # a host-RSS number is not comparable to a device watermark
        assert not any("peak memory" in r for r in regs)
        assert any("instrument" in n for n in notes), notes


# ---------------------------------------------------------------------------
# mxdiag mem renderer (satellite)
# ---------------------------------------------------------------------------

class TestMxdiagMem:
    def test_renders_real_payload(self, capsys):
        md = _load_tool("mxdiag")
        extra = json.loads(json.dumps(_armed_extra()))
        md.print_mem({"metric": "images_sec", "value": 100.0,
                      "extra": {"memscope": extra}})
        out = capsys.readouterr().out
        assert "fused_step_b64" in out
        assert "headroom" in out
        assert "no OOM recorded" in out

    def test_renders_oom_post_mortem(self, capsys):
        md = _load_tool("mxdiag")
        ms.enable(ring_limit=8)
        fp.capture("fused_step_b64", lowered=_lowered(16))
        for i in range(6):
            ms.sample(step=i)
        ms.record_oom(RuntimeError("RESOURCE_EXHAUSTED: boom"),
                      program="fused_step_b64", step=5)
        extra = json.loads(json.dumps(ms.bench_extra()))
        md.print_mem({"extra": {"memscope": extra}})
        out = capsys.readouterr().out
        assert "RESOURCE_EXHAUSTED" in out
        assert "fused_step_b64" in out

    def test_handles_missing_section(self, capsys):
        md = _load_tool("mxdiag")
        md.print_mem({"extra": {}})
        assert "memscope" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# deep /healthz headroom embed (serving)
# ---------------------------------------------------------------------------

def _tiny_frozen():
    from incubator_mxnet_tpu.serving import FrozenModel
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4, in_units=6))
    net.initialize(init=mx.init.Xavier())
    return FrozenModel(net, input_shape=(6,), batch_buckets=(1, 2))


class TestHealthzHeadroom:
    def test_armed_server_embeds_live_headroom(self, monkeypatch):
        from incubator_mxnet_tpu.serving import ModelServer
        monkeypatch.setenv("MXTPU_MEMSCOPE_CAPACITY", str(1 << 45))
        ms.enable()
        srv = ModelServer(_tiny_frozen(), max_delay_ms=2)
        srv.start()
        try:
            code, body = srv.health()
            assert code == 200
            blk = body["checks"]["memscope"]
            assert blk["verdict"] == "ok"
            assert 0.0 < blk["headroom_fraction"] <= 1.0
            assert blk["capacity_bytes"] == 1 << 45
            assert blk["in_use_bytes"] > 0
            assert blk["oom_events"] == prof.counters().get(
                "memscope/memscope.oom_events", 0)
        finally:
            srv.stop()

    def test_unarmed_server_reports_no_memscope_block(self):
        from incubator_mxnet_tpu.serving import ModelServer
        srv = ModelServer(_tiny_frozen(), max_delay_ms=2)
        srv.start()
        try:
            code, body = srv.health()
            assert code == 200
            assert "memscope" not in body["checks"]
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# profiler.device_memory_stats normalization (satellite bugfix)
# ---------------------------------------------------------------------------

class TestDeviceMemoryStats:
    def test_cpu_device_degrades_counted(self):
        before = _counters().get(
            "memscope/memscope.stats_unavailable", 0)
        st = prof.device_memory_stats(jax.local_devices()[0])
        # XLA:CPU returns None from memory_stats(): the helper must
        # hand back the one-flag shape, not None, not a raise
        assert st == {"available": False}
        assert _counters()["memscope/memscope.stats_unavailable"] \
            == before + 1

    def test_reporting_device_normalized(self):
        class _Dev:
            def memory_stats(self):
                return {"bytes_in_use": 5, "peak_bytes_in_use": 7,
                        "bytes_limit": 10}
        st = prof.device_memory_stats(_Dev())
        assert st["available"] is True
        assert (st["bytes_in_use"], st["peak_bytes_in_use"],
                st["bytes_limit"]) == (5, 7, 10)

    def test_hostile_device_degrades(self):
        class _Dev:
            def memory_stats(self):
                raise RuntimeError("backend says no")
        assert prof.device_memory_stats(_Dev()) == {"available": False}
