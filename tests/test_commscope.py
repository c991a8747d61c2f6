"""mxtpu.commscope: static HLO collective extraction, mesh-axis
attribution, ICI link-time estimates, the resharding detector, the step
budget's collective-provenance fix, and the tooling that rides on it
(trace_check schema enforcement, perf_regress collective-bytes gate,
mxdiag comms renderer) — plus the 4-fake-device subprocess matrix
asserting each layout's expected collective signature."""
import importlib.util
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import incubator_mxnet_tpu as mx  # noqa: F401 — registers the package
from incubator_mxnet_tpu import commscope as cs
from incubator_mxnet_tpu import perfscope as ps
from incubator_mxnet_tpu import profiler as prof
from incubator_mxnet_tpu.commscope import extract, hlo
from incubator_mxnet_tpu.parallel import sharding as shmod


def _load_tool(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _commscope_teardown():
    yield
    cs.disable()
    cs.reset_programs()
    ps.disable()
    ps.reset_programs()
    shmod.clear_mesh()
    shmod._LAST.clear()    # last-published layout feeds provenance too


# captured from a real XLA:CPU fsdp4 compile of the tier-1 MLP (shapes
# hand-checkable): one param all-gather, one grad all-reduce, the
# reduce-scatter-as-all-to-all decomposition, and an async pair
_HLO_FIXTURE = """\
HloModule jit_step_fn, is_scheduled=true

%fused_computation (param_0: f32[16,32]) -> f32[32,16] {
  %param_0 = f32[16,32]{1,0} parameter(0)
  ROOT %transpose.1 = f32[32,16]{0,1} transpose(f32[16,32]{1,0} %param_0), dimensions={1,0}
}

ENTRY %main {
  %param.1 = f32[4,8]{1,0} parameter(0), sharding={devices=[4,1]<=[4]}
  %copy.2 = f32[4,8]{1,0} copy(f32[4,8]{1,0} %param.1)
  %all-gather = f32[16,8]{1,0} all-gather(f32[4,8]{1,0} %copy.2), channel_id=1, replica_groups=[1,4]<=[4], dimensions={0}, use_global_device_ids=true
  %dot.1 = f32[16,32]{1,0} dot(f32[16,8]{1,0} %all-gather, f32[8,32]{1,0} %w)
  %all-reduce = f32[16,32]{1,0} all-reduce(f32[16,32]{1,0} %dot.1), channel_id=2, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%add.clone
  %all-to-all.3 = (f32[1,4,1]{2,1,0}, f32[1,4,1]{2,1,0}) all-to-all(f32[1,4,1]{2,1,0} %slice_fusion, f32[1,4,1]{2,1,0} %slice_fusion.1), channel_id=3, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={1}
  %all-gather-start = f32[8]{0} all-gather-start(f32[2]{0} %mul_fusion), channel_id=4, replica_groups=[1,4]<=[4], dimensions={0}
  %all-gather-done = f32[8]{0} all-gather-done(f32[8]{0} %all-gather-start)
  ROOT %tuple = tuple(%all-reduce)
}
"""


# ---------------------------------------------------------------------------
# HLO parsing
# ---------------------------------------------------------------------------

class TestShapeParsing:
    def test_simple_shape(self):
        assert hlo.parse_shape("f32[64,32]{1,0}") == [("f32", (64, 32))]

    def test_scalar(self):
        assert hlo.parse_shape("f32[]") == [("f32", ())]

    def test_tuple_shape(self):
        leaves = hlo.parse_shape("(f32[1,4,1]{2,1,0}, s32[2]{0})")
        assert leaves == [("f32", (1, 4, 1)), ("s32", (2,))]

    def test_bytes_f32(self):
        assert hlo.shape_bytes("f32[64,32]{1,0}") == 64 * 32 * 4

    def test_bytes_bf16_and_tuple(self):
        assert hlo.shape_bytes("(bf16[8,8]{1,0}, s32[4]{0})") \
            == 8 * 8 * 2 + 4 * 4

    def test_bytes_scalar_and_garbage(self):
        assert hlo.shape_bytes("f32[]") == 4
        assert hlo.shape_bytes("not a shape") == 0
        assert hlo.shape_bytes(None) == 0

    def test_unknown_dtype_counts_zero(self):
        # an unknown primitive type must not invent bytes
        assert hlo.shape_bytes("q77[64]{0}") == 0


class TestReplicaGroups:
    def test_explicit(self):
        assert hlo.parse_replica_groups("{{0,1},{2,3}}") == [[0, 1], [2, 3]]

    def test_iota_flat(self):
        assert hlo.parse_replica_groups("[1,4]<=[4]") == [[0, 1, 2, 3]]

    def test_iota_grouped(self):
        assert hlo.parse_replica_groups("[2,2]<=[4]") == [[0, 1], [2, 3]]

    def test_iota_transposed(self):
        # [2,2]<=[2,2]T(1,0): iota reshaped 2x2, transposed -> strided
        # groups — the dp axis of a (dp, mp) 2x2 mesh
        assert hlo.parse_replica_groups("[2,2]<=[2,2]T(1,0)") \
            == [[0, 2], [1, 3]]

    def test_malformed_returns_none(self):
        assert hlo.parse_replica_groups("") is None
        assert hlo.parse_replica_groups("[2,2]<=") is None
        assert hlo.parse_replica_groups("nonsense") is None


class TestParseCollectives:
    def test_empty_and_garbage_never_raise(self):
        assert hlo.parse_collectives("") == []
        assert hlo.parse_collectives(None) == []
        assert hlo.parse_collectives("ENTRY %main { garbage }") == []

    def test_no_collectives(self):
        txt = "ENTRY %m {\n  %dot = f32[8,8]{1,0} dot(%a, %b)\n}"
        assert hlo.parse_collectives(txt) == []

    def test_fixture_inventory(self):
        colls = hlo.parse_collectives(_HLO_FIXTURE)
        kinds = sorted(c["kind"] for c in colls)
        # the -done half of the async pair is NOT counted; -start is,
        # normalized to its base kind
        assert kinds == ["all-gather", "all-gather", "all-reduce",
                         "all-to-all"]

    def test_fused_computation_transpose_not_a_collective(self):
        # the fusion body above contains no collectives; nothing in it
        # may leak into the inventory
        colls = hlo.parse_collectives(_HLO_FIXTURE)
        assert all(not c["name"].startswith("transpose") for c in colls)

    def test_byte_accounting_vs_hand_computed(self):
        colls = {c["name"]: c for c in hlo.parse_collectives(_HLO_FIXTURE)}
        ag = colls["all-gather"]
        # all-gather: result f32[16,8] = 512 B > operand f32[4,8] = 128 B
        assert ag["result_bytes"] == 16 * 8 * 4
        assert ag["operand_bytes"] == 4 * 8 * 4
        assert ag["bytes"] == 16 * 8 * 4
        ar = colls["all-reduce"]
        assert ar["bytes"] == 16 * 32 * 4
        a2a = colls["all-to-all.3"]
        # tuple result: two f32[1,4,1] leaves
        assert a2a["result_bytes"] == 2 * 4 * 4

    def test_replica_group_and_channel_fields(self):
        colls = {c["name"]: c for c in hlo.parse_collectives(_HLO_FIXTURE)}
        assert colls["all-gather"]["replica_groups"] == [[0, 1, 2, 3]]
        assert colls["all-gather"]["group_size"] == 4
        assert colls["all-reduce"]["replica_groups"] == [[0, 1], [2, 3]]
        assert colls["all-to-all.3"]["replica_groups"] == [[0, 2], [1, 3]]
        assert colls["all-gather"]["channel_id"] == 1
        assert colls["all-gather"]["dims"] == [0]

    def test_unknown_collective_kind_never_raises(self):
        txt = ("ENTRY %m {\n"
               "  %collective-frobnicate = f32[8]{0} "
               "collective-frobnicate(f32[8]{0} %x), channel_id=1, "
               "replica_groups=[1,4]<=[4]\n}")
        colls = hlo.parse_collectives(txt)
        assert len(colls) == 1
        assert colls[0]["kind"] == "other"
        assert colls[0]["raw_kind"] == "collective-frobnicate"

    def test_async_start_tuple_not_double_counted(self):
        # a real TPU all-gather-start result bundles the source shard
        # NEXT TO the destination: (f32[2], f32[8]) — payload is the
        # 8-element destination (32 B), not the 40 B tuple sum
        txt = ("  %ag = (f32[2]{0}, f32[8]{0}) all-gather-start"
               "(f32[2]{0} %x), channel_id=5, replica_groups=[1,4]<=[4], "
               "dimensions={0}\n")
        colls = hlo.parse_collectives(txt)
        assert len(colls) == 1
        assert colls[0]["result_bytes"] == 8 * 4
        assert colls[0]["bytes"] == 8 * 4

    def test_sync_variadic_tuple_still_sums(self):
        # sync all-to-all's tuple result is N real payload buffers —
        # summing is correct there
        colls = {c["name"]: c for c in hlo.parse_collectives(_HLO_FIXTURE)}
        assert colls["all-to-all.3"]["result_bytes"] == 2 * 4 * 4

    def test_long_tuple_with_index_comments(self):
        # XLA marks every fifth element of a long tuple "/*index=5*/":
        # the combined gradient all-reduce of a dp step is such a tuple
        txt = ("  %all-reduce.49 = (f32[], f32[8]{0}, f32[8]{0}, f32[8]{0},"
               " f32[8]{0}, /*index=5*/f32[4]{0}) all-reduce(%a, %b, %c, "
               "%d, %e, %f), channel_id=1, replica_groups=[1,4]<=[4], "
               "to_apply=%add\n")
        colls = hlo.parse_collectives(txt)
        assert [c["kind"] for c in colls] == ["all-reduce"]
        assert colls[0]["result_bytes"] == 4 + 4 * 8 * 4 + 4 * 4
        assert hlo.parse_instructions(txt)["all-reduce.49"][0] == \
            "all-reduce"

    def test_collective_broadcast_buckets_as_other(self):
        txt = ("  %collective-broadcast = f32[8]{0} "
               "collective-broadcast(f32[8]{0} %x), channel_id=9\n")
        colls = hlo.parse_collectives(txt)
        assert [c["kind"] for c in colls] == ["other"]


class TestProvenanceChase:
    def test_direct_parameter(self):
        defs = hlo.parse_instructions(_HLO_FIXTURE)
        assert defs["param.1"][0] == "parameter"
        # %copy.2 -> %param.1: one passthrough hop
        assert hlo.chases_to_parameter(defs, "copy.2")
        assert hlo.chases_to_parameter(defs, "param.1")

    def test_computed_value_is_not_a_parameter(self):
        defs = hlo.parse_instructions(_HLO_FIXTURE)
        assert not hlo.chases_to_parameter(defs, "dot.1")
        assert not hlo.chases_to_parameter(defs, "missing-name")

    def test_chase_depth_bounded(self):
        defs = {"a": ("copy", "a")}     # self-loop: must terminate
        assert not hlo.chases_to_parameter(defs, "a")


# ---------------------------------------------------------------------------
# estimates + peaks
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, kind):
        self.device_kind = kind


class TestPeaksAndEstimates:
    def test_cpu_fallback_row(self):
        p = cs.ici_peaks(_FakeDevice("cpu"))
        assert p["table_row"] == "cpu"
        assert p["ici_bytes_per_s"] == cs.ICI_TABLE["cpu"]

    def test_v5e_spellings(self):
        for kind in ("TPU v5 lite", "v5litepod-8", "tpu v5e"):
            assert cs.ici_peaks(_FakeDevice(kind))["table_row"] == "v5e", kind

    def test_v5p_not_shadowed_by_v5e(self):
        assert cs.ici_peaks(_FakeDevice("TPU v5p"))["table_row"] == "v5p"

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("MXTPU_PEAK_ICI_BW", "5e9")
        assert cs.ici_peaks(_FakeDevice("cpu"))["ici_bytes_per_s"] == 5e9

    def test_malformed_override_keeps_table(self, monkeypatch):
        monkeypatch.setenv("MXTPU_PEAK_ICI_BW", "not-a-number")
        assert cs.ici_peaks(_FakeDevice("cpu"))["ici_bytes_per_s"] \
            == cs.ICI_TABLE["cpu"]

    def test_all_reduce_ring_factor(self):
        # 2(n-1)/n * B / bw: n=4, 1 MiB at 1 GB/s -> 1.5 * 1.048576 ms
        ms = cs.estimate_ms("all-reduce", 2 ** 20, 4, 1e9)
        assert ms == pytest.approx(1.5 * 2 ** 20 / 1e9 * 1e3)

    def test_gather_scatter_factor(self):
        for kind in ("all-gather", "reduce-scatter", "all-to-all"):
            ms = cs.estimate_ms(kind, 4e6, 4, 1e9)
            assert ms == pytest.approx(0.75 * 4e6 / 1e9 * 1e3), kind

    def test_permute_full_payload(self):
        assert cs.estimate_ms("collective-permute", 1e6, 4, 1e9) \
            == pytest.approx(1.0)

    def test_degenerate_inputs_zero(self):
        assert cs.estimate_ms("all-reduce", 1e6, 1, 1e9) == 0.0
        assert cs.estimate_ms("all-reduce", 0, 4, 1e9) == 0.0
        assert cs.estimate_ms("all-reduce", None, None, None) == 0.0


# ---------------------------------------------------------------------------
# mesh-axis attribution (pure grid math — no devices needed)
# ---------------------------------------------------------------------------

class TestAxisAttribution:
    GRID_2X2 = np.arange(4).reshape(2, 2)    # (dp, mp): dp strided

    def test_single_axis_full_group(self):
        grid = np.arange(4)
        assert cs.attribute_axis([[0, 1, 2, 3]], grid, ["dp"]) == "dp"

    def test_2x2_mp_axis(self):
        # contiguous pairs vary the LAST axis: mp
        assert cs.attribute_axis([[0, 1], [2, 3]], self.GRID_2X2,
                                 ["dp", "mp"]) == "mp"

    def test_2x2_dp_axis(self):
        assert cs.attribute_axis([[0, 2], [1, 3]], self.GRID_2X2,
                                 ["dp", "mp"]) == "dp"

    def test_2x2_all_devices(self):
        assert cs.attribute_axis([[0, 1, 2, 3]], self.GRID_2X2,
                                 ["dp", "mp"]) == "all"

    def test_unrecognized_partition_is_mixed(self):
        assert cs.attribute_axis([[0, 3], [1, 2]], self.GRID_2X2,
                                 ["dp", "mp"]) == "mixed"

    def test_empty_groups_none(self):
        assert cs.attribute_axis(None, self.GRID_2X2, ["dp", "mp"]) is None
        assert cs.attribute_axis([], self.GRID_2X2, ["dp", "mp"]) is None


# ---------------------------------------------------------------------------
# resharding detector (synthetic records)
# ---------------------------------------------------------------------------

def _coll(kind, operands=(), name="c"):
    return {"kind": kind, "name": name, "operands": list(operands),
            "result_shape": "f32[16,8]{1,0}",
            "operand_shapes": ["f32[4,8]{1,0}"], "bytes": 512,
            "replica_groups": [[0, 1, 2, 3]], "group_size": 4}


class TestReshardingDetector:
    DEFS = {"param.1": ("parameter", None), "copy.2": ("copy", "param.1"),
            "dot.1": ("dot", "param.1")}

    def test_dp_all_reduce_clean(self):
        assert cs.detect_resharding([_coll("all-reduce")], self.DEFS,
                                    "dp") == []

    def test_dp_computed_gather_clean(self):
        # the loss-plumbing gather of a computed value: legitimate
        assert cs.detect_resharding([_coll("all-gather", ["dot.1"])],
                                    self.DEFS, "dp") == []

    def test_dp_param_gather_flagged(self):
        out = cs.detect_resharding([_coll("all-gather", ["copy.2"])],
                                   self.DEFS, "dp")
        assert len(out) == 1 and out[0]["reason"] == "param-gather"

    def test_dp_unexpected_kind_flagged(self):
        out = cs.detect_resharding([_coll("collective-permute")],
                                   self.DEFS, "dp")
        assert len(out) == 1 and out[0]["reason"] == "unexpected-kind"

    def test_fsdp_param_gather_is_the_mode(self):
        assert cs.detect_resharding([_coll("all-gather", ["param.1"]),
                                     _coll("all-to-all")],
                                    self.DEFS, "fsdp") == []

    def test_auto_accepts_cpu_reduce_scatter_decomposition(self):
        # XLA:CPU spells reduce-scatter as all-to-all + local reduce;
        # a healthy auto-mode layout must not be indicted for the
        # backend's spelling (the computed-value operand is the tell)
        assert cs.detect_resharding(
            [_coll("reduce-scatter", ["dot.1"]),
             _coll("all-to-all", ["dot.1"])], self.DEFS, "auto") == []

    def test_auto_param_gather_flagged(self):
        out = cs.detect_resharding([_coll("all-gather", ["param.1"])],
                                   self.DEFS, "auto")
        assert len(out) == 1

    def test_unknown_mode_conservative(self):
        # jit-cache/serving programs: nothing is out of signature
        assert cs.detect_resharding([_coll("all-gather", ["param.1"]),
                                     _coll("collective-permute")],
                                    self.DEFS, None) == []

    def test_other_kind_never_indicted(self):
        # an unknown HLO spelling (renamed op after an XLA upgrade) is
        # inventoried but must not trip the detector in ANY mode — the
        # parser's never-raise contract would otherwise hard-fail CI on
        # a correct layout
        for mode in ("dp", "fsdp", "auto", None):
            assert cs.detect_resharding([_coll("other")], self.DEFS,
                                        mode) == [], mode


# ---------------------------------------------------------------------------
# record_inventory / capture / counters
# ---------------------------------------------------------------------------

def _commscope_counters():
    return {k: v for k, v in prof.counters().items()
            if k.startswith("commscope/")}


class TestRecordInventory:
    def test_aggregation_and_counters(self):
        colls = hlo.parse_collectives(_HLO_FIXTURE)
        defs = hlo.parse_instructions(_HLO_FIXTURE)
        before = _commscope_counters().get(
            "commscope/commscope.collectives", 0)
        rec = cs.record_inventory("prog_a", colls, defs=defs, mode="fsdp",
                                  kind="train_step")
        assert rec["totals"]["count"] == 4
        assert rec["totals"]["bytes"] > 0
        assert rec["resharding_collectives"] == 0
        after = _commscope_counters()
        assert after["commscope/commscope.collectives"] == before + 4
        assert after["commscope/commscope.step_collective_bytes"] \
            == rec["totals"]["bytes"]

    def test_resharding_warns_and_counts(self):
        colls = hlo.parse_collectives(_HLO_FIXTURE)
        defs = hlo.parse_instructions(_HLO_FIXTURE)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            rec = cs.record_inventory("prog_bad", colls, defs=defs,
                                      mode="dp")
        # the fixture's param all-gather + the all-to-all are both out
        # of a pure-dp program's signature
        assert rec["resharding_collectives"] >= 2
        assert any("resharding" in str(w.message) for w in caught)
        assert rec["resharding"][0]["operand_shapes"]  # offending shapes

    def test_step_estimate_prefers_latest_train_step(self):
        cs.record_inventory("prog_x", [], kind="program")
        assert cs.step_estimate() is None
        cs.record_inventory(
            "fused_step", hlo.parse_collectives(_HLO_FIXTURE),
            kind="train_step")
        est = cs.step_estimate()
        assert est["program"] == "fused_step"
        assert est["bytes"] > 0 and est["est_ms"] >= 0

    def test_capture_without_mesh_records_empty(self):
        cs.enable()
        rec = cs.capture("unsharded_prog", kind="program")
        assert rec["totals"] == {"count": 0, "bytes": 0, "est_ms": 0.0}
        assert rec["hlo_available"] is True
        assert [p["name"] for p in cs.programs()] == ["unsharded_prog"]

    def test_enable_arms_perfscope(self):
        assert ps._PS is None
        cs.enable()
        assert ps._PS is not None

    def test_bench_extra_shape(self):
        cs.enable()
        cs.capture("p1")
        extra = cs.bench_extra()
        assert {"programs", "peaks", "step"} <= set(extra)
        assert extra["peaks"]["ici_bytes_per_s"] > 0


# ---------------------------------------------------------------------------
# StepBudget collective provenance (the PR's satellite bug fix)
# ---------------------------------------------------------------------------

class _FakeMesh:
    size = 4


class TestCollectiveProvenance:
    def _finish(self, probe=None):
        b = ps.StepBudget().begin()
        b.end(steps=10, steady_s=1.0)
        if probe is not None:
            b._probe = dict(median_ms=probe, min_ms=probe, max_ms=probe,
                            iters=1, steps_per_call=1)
        return b.finish()

    def test_unsharded_is_measured(self):
        d = self._finish()
        assert d["collective_source"] == "measured"

    def test_sharded_without_commscope_is_unavailable(self, monkeypatch):
        monkeypatch.setattr(shmod, "_MESH", _FakeMesh())
        d = self._finish()
        assert d["collective_source"] == "unavailable"
        assert d["collective_ms"] == 0.0

    @staticmethod
    def _record_sharded_train_step():
        # the captured program carries its OWN mesh shape — the
        # provenance decision reads it from here, not the registry
        cs.record_inventory(
            "fused_step", hlo.parse_collectives(_HLO_FIXTURE),
            kind="train_step", extra={"mesh": {"dp": 4}})

    def test_sharded_with_commscope_is_estimated(self, monkeypatch):
        monkeypatch.setattr(shmod, "_MESH", _FakeMesh())
        cs.enable()
        self._record_sharded_train_step()
        est = cs.step_estimate()["est_ms"]
        d = self._finish()
        assert d["collective_source"] == "estimated"
        # decomp rounds components to 4 decimals
        assert d["collective_ms"] == pytest.approx(min(est, d["step_ms"]),
                                                   abs=1e-4)
        assert d["collective_est"]["program"] == "fused_step"

    def test_explicit_mesh_without_registry_is_estimated(self):
        # a FusedTrainStep built with mesh= never registers a global
        # mesh; the captured program's mesh must still drive provenance
        # (the review finding: registry-only checking reported a
        # measured zero here)
        assert shmod.get_mesh() is None
        cs.enable()
        self._record_sharded_train_step()
        d = self._finish()
        assert d["collective_source"] == "estimated"

    def test_unsharded_capture_stays_measured(self):
        # commscope armed on a 1-device run: the captured program has
        # no mesh, so the honest zero stays "measured"
        cs.enable()
        cs.record_inventory("fused_step", [], kind="train_step")
        d = self._finish()
        assert d["collective_source"] == "measured"

    def test_unreadable_hlo_is_unavailable_not_estimated(self):
        # commscope LOOKED at a sharded program and could not read its
        # HLO: the zero inventory is ignorance — reporting it as an
        # estimated zero would reintroduce the measured-zero lie
        cs.enable()
        cs.record_inventory("fused_step", [], kind="train_step",
                            hlo_available=False,
                            extra={"mesh": {"dp": 4}})
        d = self._finish()
        assert d["collective_source"] == "unavailable"
        assert d["collective_ms"] == 0.0

    def test_estimated_zero_inventory_is_honest(self):
        # readable HLO, genuinely zero collectives on a mesh (fully
        # replicated compute): THAT zero is a finding, not ignorance
        cs.enable()
        cs.record_inventory("fused_step", [], kind="train_step",
                            extra={"mesh": {"dp": 4}})
        d = self._finish()
        assert d["collective_source"] == "estimated"
        assert d["collective_ms"] == 0.0

    def test_probe_peels_estimate_out_of_device(self, monkeypatch):
        monkeypatch.setattr(shmod, "_MESH", _FakeMesh())
        cs.enable()
        self._record_sharded_train_step()
        d = self._finish(probe=80.0)
        # device + collective must not double-count the probe's wall
        assert d["device_compute_ms"] + d["collective_ms"] \
            == pytest.approx(80.0, rel=1e-3)

    def test_components_still_sum(self, monkeypatch):
        monkeypatch.setattr(shmod, "_MESH", _FakeMesh())
        cs.enable()
        self._record_sharded_train_step()
        d = self._finish(probe=80.0)
        total = sum(d[k] for k in ("device_compute_ms", "collective_ms",
                                   "input_wait_ms", "host_gap_ms",
                                   "other_ms"))
        assert total == pytest.approx(d["step_ms"], rel=0.01)

    def test_measured_kvstore_wins_over_estimate(self, monkeypatch):
        # when the explicit-collective path DID measure time, the
        # estimate must not replace it
        monkeypatch.setattr(shmod, "_MESH", _FakeMesh())
        cs.enable()
        self._record_sharded_train_step()
        b = ps.StepBudget()
        b._snap0 = {k: 0.0 for k in b._TRACKED}
        b.end(steps=10, steady_s=1.0)
        b._snap1 = dict(b._snap1,
                        **{"mxtpu/kvstore.collective_ms": 50.0})
        d = b.finish()
        assert d["collective_source"] == "measured"
        assert d["collective_ms"] == pytest.approx(5.0)


# ---------------------------------------------------------------------------
# trace_check: commscope family + extra schema + provenance taxonomy
# ---------------------------------------------------------------------------

def _valid_commscope_extra():
    return {
        "peaks": {"device_kind": "cpu", "table_row": "cpu",
                  "ici_bytes_per_s": 1e9},
        "programs": [{
            "name": "fused_step", "mode": "fsdp", "mesh": {"dp": 4},
            "hlo_available": True,
            "collectives": [
                {"kind": "all-gather", "axis": "dp", "count": 7,
                 "bytes": 5000, "est_ms": 0.01},
                {"kind": "all-reduce", "axis": "dp", "count": 3,
                 "bytes": 2000, "est_ms": 0.02}],
            "totals": {"count": 10, "bytes": 7000, "est_ms": 0.03},
            "resharding_collectives": 0, "resharding": [],
            "estimated": True}],
        "step": {"program": "fused_step", "est_ms": 0.03, "bytes": 7000,
                 "count": 10, "resharding_collectives": 0},
    }


class TestTraceCheck:
    @pytest.fixture(scope="class")
    def tc(self):
        return _load_tool("trace_check")

    def test_valid_extra_passes(self, tc):
        assert tc.check_commscope_extra(_valid_commscope_extra()) == []

    def test_absent_extra_passes(self, tc):
        assert tc.check_commscope_extra(None) == []

    def test_unknown_kind_fails(self, tc):
        bad = _valid_commscope_extra()
        bad["programs"][0]["collectives"][0]["kind"] = "all-toaster"
        assert any("all-toaster" in e
                   for e in tc.check_commscope_extra(bad))

    def test_negative_bytes_fails(self, tc):
        bad = _valid_commscope_extra()
        bad["programs"][0]["collectives"][0]["bytes"] = -1
        assert tc.check_commscope_extra(bad)

    def test_non_numeric_est_fails(self, tc):
        bad = _valid_commscope_extra()
        bad["programs"][0]["totals"]["est_ms"] = "fast"
        assert tc.check_commscope_extra(bad)

    def test_count_mismatch_fails(self, tc):
        bad = _valid_commscope_extra()
        bad["programs"][0]["totals"]["count"] = 99
        assert any("totals.count" in e
                   for e in tc.check_commscope_extra(bad))

    def test_negative_resharding_fails(self, tc):
        bad = _valid_commscope_extra()
        bad["programs"][0]["resharding_collectives"] = -2
        assert tc.check_commscope_extra(bad)

    def test_missing_peaks_fails(self, tc):
        bad = _valid_commscope_extra()
        del bad["peaks"]
        assert tc.check_commscope_extra(bad)

    def test_commscope_family_enforced(self, tc):
        errs = tc.check_healthmon_kinds(
            {"commscope/commscope.collectives": "counter"})
        assert errs == []
        errs = tc.check_healthmon_kinds(
            {"commscope/commscope.invented": "counter"})
        assert any("COMMSCOPE_FAMILIES" in e for e in errs)
        errs = tc.check_healthmon_kinds(
            {"commscope/commscope.collectives": "gauge"})
        assert any("kind" in e for e in errs)

    def test_collective_source_taxonomy(self, tc):
        psx = {"peaks": {"peak_flops_f32": 1e12, "peak_flops_bf16": 2e12,
                         "hbm_bytes_per_s": 1e11},
               "programs": [],
               "decomposition": {
                   "step_ms": 10.0, "device_compute_ms": 8.0,
                   "collective_ms": 1.0, "input_wait_ms": 0.0,
                   "host_gap_ms": 1.0, "other_ms": 0.0,
                   "collective_source": "estimated"}}
        assert tc.check_perfscope_extra(psx) == []
        psx["decomposition"]["collective_source"] = "guessed"
        assert any("collective_source" in e
                   for e in tc.check_perfscope_extra(psx))

    def test_bench_json_validates_commscope(self, tc, tmp_path):
        doc = {"metric": "m", "value": 1.0, "unit": "x",
               "extra": {"mfu": 0.1, "commscope": _valid_commscope_extra()}}
        p = tmp_path / "BENCH_ok.json"
        p.write_text(json.dumps(doc))
        assert tc.check_bench_json(str(p)) == []
        doc["extra"]["commscope"]["programs"][0]["collectives"][0][
            "kind"] = "nope"
        p.write_text(json.dumps(doc))
        assert any("extra.commscope" in e
                   for e in tc.check_bench_json(str(p)))


# ---------------------------------------------------------------------------
# perf_regress: the collective-bytes layout gate
# ---------------------------------------------------------------------------

def _artifact(tmp_path, name, value=100.0, coll_bytes=None, reshard=None):
    doc = {"metric": "m_samples", "value": value, "unit": "samples/sec",
           "extra": {"mfu": 0.1}}
    if coll_bytes is not None:
        step = {"program": "fused_step", "est_ms": 0.1,
                "bytes": coll_bytes, "count": 4}
        if reshard is not None:
            step["resharding_collectives"] = reshard
        doc["extra"]["commscope"] = {
            "peaks": {"ici_bytes_per_s": 1e9}, "programs": [],
            "step": step}
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


class TestPerfRegressCollectiveGate:
    @pytest.fixture(scope="class")
    def pr(self):
        return _load_tool("perf_regress")

    def test_same_bytes_ok(self, pr, tmp_path):
        a = _artifact(tmp_path, "a.json", coll_bytes=7000)
        b = _artifact(tmp_path, "b.json", coll_bytes=7000)
        assert pr.main([a, b]) == 0

    def test_inflated_bytes_regress(self, pr, tmp_path):
        a = _artifact(tmp_path, "a.json", coll_bytes=7000)
        b = _artifact(tmp_path, "b.json", coll_bytes=14000)
        assert pr.main([a, b]) == 1

    def test_small_drift_within_threshold(self, pr, tmp_path):
        a = _artifact(tmp_path, "a.json", coll_bytes=7000)
        b = _artifact(tmp_path, "b.json", coll_bytes=7100)
        assert pr.main([a, b]) == 0

    def test_zero_to_nonzero_always_regress(self, pr, tmp_path):
        a = _artifact(tmp_path, "a.json", coll_bytes=0)
        b = _artifact(tmp_path, "b.json", coll_bytes=64)
        assert pr.main([a, b]) == 1

    def test_new_resharding_regress(self, pr, tmp_path):
        a = _artifact(tmp_path, "a.json", coll_bytes=7000, reshard=0)
        b = _artifact(tmp_path, "b.json", coll_bytes=7000, reshard=2)
        assert pr.main([a, b]) == 1

    def test_artifacts_without_commscope_skip_gate(self, pr, tmp_path):
        a = _artifact(tmp_path, "a.json")
        b = _artifact(tmp_path, "b.json", coll_bytes=9999)
        assert pr.main([a, b]) == 0

    def test_preexisting_resharding_vs_commscope_less_baseline_ok(
            self, pr, tmp_path):
        # a baseline predating commscope cannot indict a candidate's
        # known resharding count (same contract as the bytes gate)
        a = _artifact(tmp_path, "a.json")
        b = _artifact(tmp_path, "b.json", coll_bytes=7000, reshard=2)
        assert pr.main([a, b]) == 0


# ---------------------------------------------------------------------------
# mxdiag comms renderer
# ---------------------------------------------------------------------------

class TestMxdiagComms:
    @pytest.fixture(scope="class")
    def md(self):
        return _load_tool("mxdiag")

    def test_renders_table(self, md, capsys):
        doc = {"metric": "m", "value": 1.0, "unit": "x",
               "extra": {"commscope": _valid_commscope_extra()}}
        assert md.print_comms(doc) == 0
        out = capsys.readouterr().out
        assert "all-gather" in out and "axis dp" in out
        assert "fused_step" in out

    def test_resharding_rendered_loudly(self, md, capsys):
        extra = _valid_commscope_extra()
        extra["programs"][0]["resharding_collectives"] = 1
        extra["programs"][0]["resharding"] = [
            {"kind": "all-gather", "reason": "param-gather",
             "result_shape": "f32[32,8]{1,0}",
             "operand_shapes": ["f32[8,8]{1,0}"]}]
        doc = {"metric": "m", "value": 1.0, "unit": "x",
               "extra": {"commscope": extra}}
        assert md.print_comms(doc) == 0
        out = capsys.readouterr().out
        assert "RESHARD" in out and "param-gather" in out

    def test_missing_section_fails(self, md, capsys):
        assert md.print_comms({"metric": "m", "value": 1.0,
                               "extra": {}}) == 1

    def test_perf_renders_provenance(self, md, capsys):
        doc = {"metric": "m", "value": 1.0, "unit": "x",
               "extra": {"perfscope": {
                   "peaks": {"device_kind": "cpu", "table_row": "cpu",
                             "peak_flops_f32": 1e12,
                             "peak_flops_bf16": 2e12,
                             "hbm_bytes_per_s": 1e11},
                   "programs": [],
                   "decomposition": {
                       "step_ms": 10.0, "steps": 5,
                       "device_compute_ms": 8.0, "collective_ms": 1.0,
                       "input_wait_ms": 0.0, "host_gap_ms": 1.0,
                       "other_ms": 0.0, "source": "probe",
                       "collective_source": "unavailable"}}}}
        md.print_perf(doc)
        out = capsys.readouterr().out
        assert "UNAVAILABLE" in out


# ---------------------------------------------------------------------------
# the 4-fake-device subprocess matrix: expected signatures per layout
# ---------------------------------------------------------------------------

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "commscope_matrix_worker.py")


def _run_worker(layout):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)     # the worker pins its own
    proc = subprocess.run([sys.executable, _WORKER, layout],
                          capture_output=True, text=True, timeout=240,
                          env=env)
    assert proc.returncode == 0, \
        f"worker {layout} rc={proc.returncode}:\n{proc.stderr[-2000:]}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


class TestSubprocessMatrix:
    @pytest.fixture(scope="class")
    def matrix(self):
        return {layout: _run_worker(layout)
                for layout in ("single", "dp4", "dp2mp2", "fsdp4",
                               "misannotated")}

    def test_single_device_no_collectives(self, matrix):
        rec = matrix["single"]
        assert rec["kinds"] == {}
        assert rec["program"]["totals"]["count"] == 0
        assert rec["collective_source"] == "measured"

    def test_dp4_all_reduce_signature(self, matrix):
        rec = matrix["dp4"]
        assert rec["devices"] == 4
        assert rec["kinds"].get("all-reduce", 0) > 0
        # pure data parallel must not reduce-scatter or permute
        assert "reduce-scatter" not in rec["kinds"]
        assert "collective-permute" not in rec["kinds"]
        assert rec["program"]["resharding_collectives"] == 0
        assert rec["axes"] == ["dp"]

    def test_fsdp4_gather_scatter_signature(self, matrix):
        rec = matrix["fsdp4"]
        kinds = rec["kinds"]
        assert kinds.get("all-gather", 0) > 0, kinds
        # the grad reduce-scatter: literal on TPU, decomposed into
        # all-to-all (+ local reduce) by XLA:CPU — either spelling
        assert kinds.get("reduce-scatter", 0) + kinds.get("all-to-all",
                                                          0) > 0, kinds
        assert rec["program"]["resharding_collectives"] == 0

    def test_dp2mp2_model_axis_collectives(self, matrix):
        rec = matrix["dp2mp2"]
        assert "mp" in rec["axes"], rec["axes"]
        assert rec["kinds"].get("all-reduce", 0) > 0
        assert rec["program"]["resharding_collectives"] == 0

    def test_misannotated_trips_detector(self, matrix):
        rec = matrix["misannotated"]
        assert rec["program"]["resharding_collectives"] > 0
        reasons = {r["reason"] for r in rec["program"]["resharding"]}
        assert "param-gather" in reasons or "unexpected-kind" in reasons
        assert rec["resharding_warned"]
        # the offending operand shapes are recorded for the human
        flagged = rec["program"]["resharding"][0]
        assert flagged.get("result_shape") or flagged.get("operand_shapes")
        assert rec["counters"][
            "commscope/commscope.resharding_collectives"] > 0

    def test_sharded_bytes_nonzero_and_estimated(self, matrix):
        for layout in ("dp4", "dp2mp2", "fsdp4"):
            rec = matrix[layout]
            assert rec["program"]["totals"]["bytes"] > 0, layout
            assert rec["step_estimate"]["bytes"] > 0, layout
            assert rec["collective_source"] == "estimated", layout

    def test_byte_accounting_scales_with_mode(self, matrix):
        # fsdp gathers every param each step: its payload must exceed
        # pure-dp's grad-reduce-only traffic on the same net
        assert matrix["fsdp4"]["program"]["totals"]["bytes"] \
            > matrix["dp4"]["program"]["totals"]["bytes"]
