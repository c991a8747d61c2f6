"""The readers of the program's set-up account (lib/setup_account.py): each
returns its `setup/*` gauges, nothing where the program keeps no account,
and a rehearsal's traced line carries all five."""
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from incubator_mxnet_tpu import profiler  # noqa: E402

READS = {
    "step_trace_s.setup": {"step_trace_s": 1.25, "step_lower_s": 0.5},
    "step_load_s.setup": {"step_load_s": 2.5},
    "eager_programs_s.setup": {"other_programs_s": 3.75},
    "init_s.setup": {"init_s": 0.125},
    "shape_pass_s.setup": {"shapes_s": 0.0},
}


def _reader(metric):
    path = os.path.join(BENCH, "layers", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", metric), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.fixture
def registry():
    saved = {k: v for k, v in profiler.counters().items()
             if k.startswith("setup/")}
    profiler.reset_counters()
    yield
    profiler.reset_counters()
    for key, value in saved.items():
        profiler.set_gauge(key.split("/", 1)[1], value, "setup")


@pytest.mark.parametrize("metric", sorted(READS))
def test_each_reader_returns_its_gauges(metric, registry):
    for gauge, value in READS[metric].items():
        profiler.set_gauge(gauge, value, "setup")
    profiler.set_gauge("other_programs", 7, "setup")    # read by none
    assert _reader(metric)(None) == sum(READS[metric].values())


@pytest.mark.parametrize("metric", sorted(READS))
def test_a_program_without_the_account_reads_as_nothing(metric, registry):
    assert _reader(metric)(None) is None


def test_the_manifest_reports_them_in_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for metric in READS:
        entry = entries[metric]
        assert "workloads" not in entry
        assert (entry["unit"], entry["moves"], entry["source"]) == (
            "s", "setup_s", "program_counter")


def test_a_traced_rehearsal_reports_all_five_within_setup():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "resnet50_train_b256", "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    lines = [json.loads(line) for line in done.stdout.splitlines()
             if line.startswith("{")]
    metrics = lines[-1]["metrics"]
    values = {name: metrics[name]["value"] for name in READS}
    assert all(v >= 0 for v in values.values()), values
    for name in ("step_trace_s.setup", "step_load_s.setup",
                 "eager_programs_s.setup", "init_s.setup"):
        assert values[name] > 0, name
    setup = next(line for line in lines if "setup_s" in line)
    package = setup["setup_phases"]["package"]
    assert sum(values.values()) + package <= setup["setup_s"]
