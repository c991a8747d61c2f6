"""The benchmark's own yardstick (benchmark/lib), its manifest and one
rehearsal of every cell. Nothing here is a device number: the rehearsals
run at toy sizes on the CPU and are never `correct`."""
import gzip
import importlib.util
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from lib import intervals, peaks, xplane  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "resnet50_train_b256.trimmed.xplane.pb.gz")


def _module(path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", os.path.basename(path)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- interval arithmetic ----------------------------------------------------

@pytest.mark.parametrize("spans,merged", [
    ([], []),
    ([(0, 1)], [(0, 1)]),
    ([(0, 2), (1, 3)], [(0, 3)]),                 # overlap
    ([(0, 1), (1, 2)], [(0, 2)]),                 # touch
    ([(5, 6), (0, 1)], [(0, 1), (5, 6)]),         # unsorted, disjoint
    ([(0, 10), (2, 3), (4, 5)], [(0, 10)]),       # nested
    ([(0, 1), (3, 3), (4, 2)], [(0, 1)]),         # empty and reversed drop
], ids=["none", "one", "overlap", "touch", "unsorted", "nested", "empty"])
def test_union(spans, merged):
    assert intervals.union(spans) == merged
    assert intervals.covered(spans) == sum(e - s for s, e in merged)


@pytest.mark.parametrize("spans,window,holes", [
    ([(0, 1), (3, 4)], None, [(1, 3)]),
    ([(0, 1), (3, 4)], (-1, 6), [(-1, 0), (1, 3), (4, 6)]),
    ([(0, 4), (1, 2)], None, []),
    ([], (0, 2), [(0, 2)]),
    ([(0, 1), (2, 3), (5, 9)], (2, 6), [(3, 5)]),
], ids=["inner", "edges", "nested", "all-idle", "clipped"])
def test_gaps(spans, window, holes):
    assert intervals.gaps(spans, window) == holes


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_is_numpys(q):
    values = list(np.random.RandomState(q).lognormal(size=137))
    assert intervals.percentile(values, q) == pytest.approx(
        np.percentile(values, q), rel=1e-12)


def test_percentile_small_and_empty():
    assert intervals.percentile([3.0], 95) == 3.0
    assert intervals.percentile([1.0, 2.0], 50) == 1.5
    with pytest.raises(ValueError):
        intervals.percentile([], 50)


def _run_py():
    return _module(os.path.join(BENCH, "run.py"))


@pytest.mark.parametrize("stats,program,filled", [
    # the reserved region is the program's temporaries, to 0.4%
    ({"bytes_in_use": 430e6, "peak_bytes_in_use": 3.2e9,
      "peak_bytes_reserved": 8.71e9},
     {"argument": 334e6, "output": 257e6, "alias": 257e6, "temp": 8.747e9},
     True),
    # a pool reserved far beyond what the program fills
    ({"bytes_in_use": 430e6, "peak_bytes_in_use": 3.2e9,
      "peak_bytes_reserved": 8.71e9},
     {"argument": 334e6, "output": 257e6, "alias": 257e6, "temp": 4.2e9},
     False),
    # fewer bytes in use than the program's own arguments
    ({"bytes_in_use": 100e6, "peak_bytes_in_use": 3.2e9,
      "peak_bytes_reserved": 8.71e9},
     {"argument": 334e6, "output": 257e6, "alias": 257e6, "temp": 8.747e9},
     False),
    # nothing to compare: XLA:CPU has no statistics; a driver no program
    ({}, {"argument": 1, "output": 1, "alias": 1, "temp": 1}, True),
    ({"bytes_in_use": 1, "peak_bytes_reserved": 1}, None, True),
])
def test_reserved_memory_counts_only_where_the_program_fills_it(
        stats, program, filled):
    assert _run_py().reserved_is_filled(stats, program) is filled


def test_held_bytes_is_in_use_and_reserved_or_an_earlier_peak():
    held = _run_py().held_bytes
    assert held({"bytes_in_use": 4, "peak_bytes_in_use": 9,
                 "peak_bytes_reserved": 7}) == 11
    assert held({"bytes_in_use": 4, "peak_bytes_in_use": 30,
                 "peak_bytes_reserved": 7}) == 30
    assert held({}) == 0


def test_overlap():
    assert intervals.overlap((0, 4), (2, 9)) == 2
    assert intervals.overlap((0, 1), (2, 3)) == 0


# -- the trace reduction ----------------------------------------------------

def _planes(ops, host=()):
    return {"/device:TPU:0": {xplane.OPS_LINE: list(ops)},
            xplane.HOST_PLANE: {"main": list(host)}}


def test_reduce_hand_made_trace():
    ops = [("fusion.1", 0, 4e9), ("fusion.2", 1e9, 2e9),     # nested
           ("convolution.7", 6e9, 8e9), ("fusion.1", 9e9, 10e9)]
    host = [("bench.dispatch", 3.9e9, 5.0e9), ("bench.wait_loss", 5.0e9, 6e9),
            ("other.span", 0, 10e9), ("bench.wait_loss", 8e9, 9e9)]
    got = xplane.reduce(_planes(ops, host))
    assert got["chips"] == 1
    assert got["busy_s"] == pytest.approx(7.0)
    assert got["window_s"] == pytest.approx(10.0)
    assert got["device_ops"][0] == ["fusion", pytest.approx(6.0)]
    assert dict(map(tuple, got["device_op_kinds"])) == {
        "fusion": pytest.approx(6.0), "convolution": pytest.approx(2.0)}
    # the 4..6 gap goes to the span that covers most of it (a tie takes
    # the first), the 8..9 gap to the wait; other.span never names a gap
    assert dict(map(tuple, got["idle_gaps"])) == {
        "bench.dispatch": pytest.approx(2.0),
        "bench.wait_loss": pytest.approx(1.0)}


def test_reduce_joins_one_operation_on_the_same_shapes_across_layers():
    ops = [("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p.3)", 0, 2e9),
           ("%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %p.7)", 2e9, 3e9),
           ("%fusion.4 = bf16[4]{0} fusion(bf16[4]{0} %p.5)", 3e9, 4e9)]
    assert xplane.reduce(_planes(ops))["device_ops"] == [
        ["%fusion = bf16[8] fusion(bf16[8] %p)", 3.0],
        ["%fusion = bf16[4] fusion(bf16[4] %p)", 1.0]]


def test_reduce_averages_over_chips_and_names_unspanned_gaps():
    planes = _planes([("a", 0, 1e9), ("a", 3e9, 4e9)])
    planes["/device:TPU:1"] = {xplane.OPS_LINE: [("a", 0, 4e9)]}
    got = xplane.reduce(planes)
    assert (got["chips"], got["busy_s"], got["window_s"]) == (2, 3.0, 4.0)
    assert got["idle_gaps"] == [["no-host-span", 1.0]]


def test_reduce_sets_holes_between_back_to_back_ops_apart():
    ops = [("a", 0, 1000), ("b", 1010, 2000), ("c", 9000, 9500)]
    host = [("bench.dispatch", 0, 9000)]
    got = xplane.reduce(_planes(ops, host))
    assert dict(map(tuple, got["idle_gaps"])) == {
        "bench.dispatch": pytest.approx(7e-6),
        "between-ops": pytest.approx(1e-8)}


@pytest.mark.parametrize("planes", [
    {}, {xplane.HOST_PLANE: {"main": [("bench.dispatch", 0, 1)]}},
    {"/device:TPU:0": {"Steps": [("1", 0, 1)]}},
], ids=["empty", "host-only", "no-ops-line"])
def test_reduce_raises_without_a_device_plane(planes):
    with pytest.raises(xplane.NoDevicePlane):
        xplane.reduce(planes)


def test_reduce_recorded_chip_trace(tmp_path):
    """Three ResNet-50 steps of chip call A (PR 24, TPU v5 lite), trimmed
    to the device's operation line and the benchmark's own host spans."""
    path = tmp_path / "recorded.xplane.pb"
    with gzip.open(RECORDED) as f:
        path.write_bytes(f.read())
    planes = xplane.load(str(path))
    assert set(planes) == {"/device:TPU:0", xplane.HOST_PLANE}
    assert len(planes["/device:TPU:0"][xplane.OPS_LINE]) == 12671
    got = xplane.reduce(planes)
    assert got["chips"] == 1
    assert got["busy_s"] == pytest.approx(0.326985992, rel=1e-9)
    assert got["window_s"] == pytest.approx(0.329555743, rel=1e-9)
    kinds = dict(map(tuple, got["device_op_kinds"]))
    assert list(kinds)[:3] == ["%fusion", "%multiply_reduce_fusion",
                               "%convert_reduce_fusion"]
    assert kinds["%fusion"] == pytest.approx(0.124107748, rel=1e-9)
    assert sum(kinds.values()) <= got["busy_s"] * (1 + 1e-9)
    assert all(len(name) <= xplane.LABEL and "{" not in name
               for name, _ in got["device_ops"])
    gaps = dict(map(tuple, got["idle_gaps"]))
    assert set(gaps) == {"bench.dispatch", "bench.wait_loss", "between-ops"}
    assert sum(gaps.values()) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)


@pytest.mark.parametrize("name,instr,kind", [
    ("%fusion.992 = (bf16[768]{0:T(1024)(128)(2,1)}, f32[16,1024]{1,0}) "
     "fusion(bf16[16,1024,768]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.55)",
     "%fusion.992", "%fusion"),
    ("%jvp__.59 = (bf16[192,1024,128]{2,1,0}) custom-call(bf16[1]{0} %p)",
     "%jvp__.59", "%jvp__"),
    ("%copy-done = f32[8]{0} copy-done(%copy-start)", "%copy-done",
     "%copy-done"),
    ("fusion.1", "fusion.1", "fusion"),
])
def test_operation_names(name, instr, kind):
    assert xplane.instruction(name) == instr
    assert xplane.kind(name) == kind
    assert not re.search(r"[{}]|\.\d", xplane.label(name))
    assert xplane.label(name).startswith(kind)


def test_newest_raises_where_no_trace_was_written(tmp_path):
    with pytest.raises(FileNotFoundError):
        xplane.newest(str(tmp_path))


def _reader(metric):
    return _module(os.path.join(BENCH, "layers", metric + ".py")).read


def test_trace_readers_on_a_hand_made_trace():
    """Idle share and busy-time utilisation from a reduced trace: 1 s of
    every 4 idle; 3 busy seconds at the v5e's peak would be 591e12."""
    summary = xplane.reduce(_planes([("a", 0, 1e9), ("a", 2e9, 4e9)]))
    bench = types.SimpleNamespace(
        trace_summary=summary, device_kind="TPU v5 lite",
        outcome={"traced_flops": 0.25 * 3 * 197e12})
    assert _reader("device_idle_pct")(bench) == pytest.approx(25.0)
    assert _reader("busy_flops_pct.train")(bench) == pytest.approx(25.0)
    bench.device_kind = "cpu"
    with pytest.raises(KeyError):
        _reader("busy_flops_pct.train")(bench)


@pytest.mark.parametrize("metric", ["device_idle_pct",
                                    "busy_flops_pct.train"])
def test_trace_readers_return_nothing_without_a_trace(metric):
    bench = types.SimpleNamespace(trace_summary=None, device_kind="cpu",
                                  outcome={})
    assert _reader(metric)(bench) is None


def test_peak_of_an_unknown_device_is_an_error():
    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu", "bf16_flops")


# -- the manifest -----------------------------------------------------------

def test_manifest_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_name_unit_and_cells(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_a_metric_of_its_cells(
        metric):
    assert os.path.isfile(os.path.join(BENCH, "layers",
                                       metric["name"] + ".py"))
    moved = [m for m in MANIFEST["end_to_end"]
             if m["name"] == metric["moves"]]
    assert len(moved) == 1
    assert set(metric.get("workloads", CELLS)) <= set(
        moved[0].get("workloads", CELLS))


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_resolves_to_files(cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    config = [c for c in MANIFEST["configs"] if c["name"] == cell["config"]]
    assert len(config) == 1
    assert config[0]["file"].startswith("benchmark/")
    with open(os.path.join(ROOT, config[0]["file"])) as f:
        doc = json.load(f)
    assert doc["source"] == config[0]["source"]
    assert doc["reduced"] == config[0]["reduced"]
    for beside in (".py", ".reference.py"):
        assert os.path.isfile(os.path.join(BENCH, "configs",
                                           cell["config"] + beside))
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        kind = json.load(f)["kind"]
    assert os.path.isfile(os.path.join(BENCH, "traffic", kind + ".py"))


# -- each configuration against its plain reference -------------------------

@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_first_step_loss_is_the_plain_references(config):
    """The fused step's first loss (forward on the initial weights)
    against benchmark/configs/<config>.reference.py at the file's toy
    sizes, both in float32 on the CPU, where matmuls are true float32:
    only the order of sums differs, so 1e-4 (a bf16 pass is off by 1e-3
    to 4e-2 and would fail)."""
    import jax.numpy as jnp

    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.parallel import FusedTrainStep

    with open(os.path.join(ROOT, config["file"])) as f:
        doc = json.load(f)
    doc.update(doc.pop("rehearse"), dtype="float32")
    traffic = {"batch": 4, "seq": 128}
    model = _module(os.path.join(BENCH, "configs", config["name"] + ".py"))
    reference = _module(os.path.join(BENCH, "configs",
                                     config["name"] + ".reference.py"))
    net = model.net(doc, 7)
    x, y = model.batch(doc, traffic, 7)
    with autograd.pause():
        net(x)                       # completes the deferred shapes
    params = [p.data().jax().astype(jnp.float32)
              for p in net.collect_params().values()]
    want = float(reference.loss(doc, params, x.jax(), y.jax()))
    step = FusedTrainStep(net, model.loss(doc), model.optimizer(doc))
    got = float(step(x, y).asscalar())
    assert got == pytest.approx(want, rel=1e-4)
    assert model.flops_per_sample(doc, traffic) > 0


# -- one rehearsal of every cell --------------------------------------------

def _run(*args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_line_and_is_never_correct(cell,
                                                                  trace):
    done = _run("--workload", cell, "--seed", "3000000019", "--seconds",
                "30", "--trace", str(trace), "--rehearse")
    assert done.returncode != 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}, done.stderr[-2000:]
    assert last["correct"] is False
    assert last["attempted"] >= 2 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert set(last["device"]) >= {"kind", "count", "memory_peak_bytes"}
    group = "per_layer" if trace else "end_to_end"
    named = {m["name"]: m["unit"] for m in MANIFEST[group]
             if cell in m.get("workloads", CELLS)}
    assert last["metrics"], last
    for name, metric in last["metrics"].items():
        assert metric["unit"] == named[name]
        assert isinstance(metric["value"], (int, float))
    if not trace:
        assert set(last["metrics"]) == set(named)
        assert all(m["value"] > 0 for m in last["metrics"].values())
    else:
        # no device plane on the CPU: the trace's metrics are left out
        assert last["metrics"]["compiles_in_window"]["value"] == 0


def test_without_a_tpu_a_run_fails_and_prints_no_result():
    done = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0")
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "needs a TPU" in done.stderr
