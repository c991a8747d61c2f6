"""`cross_entropy_ms.train` (benchmark/layers/cross_entropy_ms.train.py):
the device time under the op scope `cross_entropy`, both phases, wherever
the loss's owner puts it; nothing from a program without the scope."""
import gzip
import importlib.util
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from lib import xplane  # noqa: E402

METRIC = "cross_entropy_ms.train"
# three steps of the GPT-2 cell on the chip: a program with the names and
# WITHOUT the op scope `cross_entropy` (its loss was log_softmax and a
# gather under `loss/softmax_cross_entropy_loss_0`)
SCOPED = os.path.join(HERE, "gpt2_train_b16_s1024.scoped.xplane.pb.gz")


def _read(bench):
    spec = importlib.util.spec_from_file_location(
        "bench_cross_entropy_reader", os.path.join(BENCH, "layers",
                                                   METRIC + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(bench)


def test_sums_the_scope_in_both_phases_under_any_owner():
    """An LM step's loss (`loss/cross_entropy`) and a Gluon loss block's
    (`loss/softmax_cross_entropy_loss_0/cross_entropy`) both count; the
    head's products, into which XLA fuses the loss's backward, do not."""
    events = [
        ("fusion.1", ["loss", "cross_entropy"], "forward", 2.0e6),
        ("fusion.2", ["loss", "softmax_cross_entropy_loss_0",
                      "cross_entropy"], "backward", 1.0e6),
        ("fusion.3", ["transformer_lm_0"], "backward", 5.0e6),
        ("fusion.4", ["loss"], "forward", 0.5e6),
    ]
    bench = types.SimpleNamespace(trace_summary={"busy_s": 1.0},
                                  scoped={"steps": 2},
                                  owned_events=(events, 2))
    assert _read(bench) == pytest.approx(1.5)


def test_reads_nothing_from_a_program_without_the_scope(tmp_path):
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    path = where / "host.xplane.pb"
    with gzip.open(SCOPED) as f:
        path.write_bytes(f.read())
    notes = []
    bench = types.SimpleNamespace(
        trace_dir=str(tmp_path), notes=notes, outcome={},
        trace_summary=xplane.reduce(xplane.load(str(path))),
        note=lambda **fields: notes.append(fields))
    assert _read(bench) is None
    assert _read(types.SimpleNamespace(trace_summary=None)) is None


def test_declared_for_every_cell_that_reports_the_rate():
    """No `workloads` list: every cell's loss runs the op (ResNet-50's
    Gluon loss too), so every cell reports it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = {m["name"]: m for m in manifest["per_layer"]}[METRIC]
    assert entry == {"name": METRIC, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "samples_per_s"}
    rate = {m["name"]: m for m in manifest["end_to_end"]}["samples_per_s"]
    assert "workloads" not in rate
