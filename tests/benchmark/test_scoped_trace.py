"""benchmark/lib/scopes.py and the eight readers that stand on it: owner and
phase of every device operation, read from a trace. On a hand-made trace,
on a recorded one from the chip, on a trace of a program without the names
(nothing is read, nothing raises), and without a trace. The grammar itself
is held, with the program's names, in tests/test_scopes.py (this file cannot
share that name: pytest imports test files by their base name)."""
import gzip
import importlib.util
import os
import re
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark")
sys.path.insert(0, BENCH)

from lib import scopes, xplane  # noqa: E402

# Three steps of `gpt2_train_b16_s1024 --trace 1` on the chip (chip call 2 of
# PR 25, seed 2200000013, TPU v5 lite), trimmed to the device plane's `XLA Ops` and `XLA Modules` lines,
# each operation's name (cut to 120 characters) and `tf_op`, and the host's
# `mxtpu.*` and `bench.*` spans; written as a text proto and serialised by
# `ProfileData.text_proto_to_serialized_xspace`.
SCOPED = os.path.join(HERE, "gpt2_train_b16_s1024.scoped.xplane.pb.gz")
# PR 24's: names and times only, from before the program had names
UNNAMED = os.path.join(HERE, "resnet50_train_b256.trimmed.xplane.pb.gz")
READERS = ["forward_ms.train", "backward_ms.train", "optimizer_ms.train",
           "attention_fwd_ms.train", "attention_bwd_ms.train",
           "norm_ms.train", "programs_per_step.train",
           "step_enqueue_ms.train"]


def _reader(metric):
    path = os.path.join(BENCH, "layers", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", metric), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _bench(tmp_path, recorded):
    """What run.py hands a reader after a `--trace 1` run whose trace is
    `recorded`."""
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    path = where / "host.xplane.pb"
    with gzip.open(recorded) as f:
        path.write_bytes(f.read())
    notes = []
    return types.SimpleNamespace(
        trace_dir=str(tmp_path), notes=notes,
        trace_summary=xplane.reduce(xplane.load(str(path))),
        note=lambda **fields: notes.append(fields))


# -- a hand-made trace ------------------------------------------------------

MS = 1e6        # the trace's clock is in nanoseconds
STEP = "jit(train_step)/"
NAMES = {
    "%fusion.1 = f32[8] fusion()": STEP + "jvp(net_0)/dense_0/dot_general",
    "%flash_attention_fwd.2 = bf16[8] custom-call()":
        STEP + "jvp(net_0)/cell_1/attention/flash_attention_fwd/pallas_call",
    "%fusion.3 = f32[8] fusion()":
        STEP + "transpose(jvp(net_0))/cell_1/attention/transpose",
    "%fusion.4 = f32[8] fusion()":
        STEP + "transpose(jvp(net_0))/cell_1/layer_norm_2/layer_norm/mul",
    "%fusion.5 = f32[8] fusion()": STEP + "jvp(net_0)/batch_norm_0/"
                                          "batch_norm/rsqrt",
    "%fusion.6 = f32[8] fusion()": STEP + "optimizer/mul",
    "%fusion.7 = u32[2] fusion()": "jit(_threefry_split)/threefry2x32",
}


def _step(at):
    """One step's operations from `at` ms on: 10 ms of device time and a
    2 ms hole before the optimizer's update; `%copy-done` has no name."""
    times = [("%fusion.1 = f32[8] fusion()", 0, 1),
             ("%flash_attention_fwd.2 = bf16[8] custom-call()", 1, 3),
             ("%fusion.3 = f32[8] fusion()", 3, 4.5),
             ("%fusion.4 = f32[8] fusion()", 4.5, 5),
             ("%fusion.5 = f32[8] fusion()", 5, 6),
             ("%copy-done.9 = f32[8] copy-done()", 6, 6.5),
             ("%fusion.7 = u32[2] fusion()", 6.5, 7),
             ("%fusion.6 = f32[8] fusion()", 9, 12)]
    return [(name, (at + s) * MS, (at + e) * MS) for name, s, e in times]


def _hand_made(steps=2):
    ops, programs, host = [], [], []
    for i in range(steps):
        at = 20.0 * i
        ops += _step(at)
        programs += [("jit__threefry_split(1)", (at + 6.5) * MS,
                      (at + 7) * MS),
                     ("jit_train_step(2)", at * MS, (at + 12) * MS)]
        host += [("mxtpu.step", (at + 6) * MS, (at + 11) * MS),
                 ("mxtpu.step.args", (at + 6) * MS, (at + 7.5) * MS),
                 ("mxtpu.step.enqueue", (at + 7.5) * MS,
                  (at + 9.5 + i) * MS),
                 ("mxtpu.step.rebind", (at + 10) * MS, (at + 11) * MS),
                 ("bench.dispatch", (at + 6) * MS, (at + 11) * MS)]
    return {"/device:TPU:0": {xplane.OPS_LINE: ops,
                              scopes.PROGRAMS_LINE: programs},
            xplane.HOST_PLANE: {"python3": host}}


def test_reduce_hand_made_trace():
    got = scopes.reduce(_hand_made(), NAMES)
    assert got["steps"] == 2
    assert got["phase_ms"] == pytest.approx(
        {"forward": 4.0, "backward": 2.0, "optimizer": 3.0, "other": 1.0})
    assert got["device_ms"] == pytest.approx(10.0)
    assert sum(got["phase_ms"].values()) == pytest.approx(got["device_ms"])
    assert got["attention_ms"] == pytest.approx(
        {"forward": 2.0, "backward": 1.5})
    assert got["norm_ms"] == pytest.approx(1.5)     # layer and batch norm
    assert got["unowned_pct"] == pytest.approx(10.0)
    assert got["programs_per_step"] == 2.0
    assert got["span_ms"] == pytest.approx(
        {"mxtpu.step": 5.0, "mxtpu.step.args": 1.5,
         "mxtpu.step.enqueue": 2.5, "mxtpu.step.rebind": 1.0})
    table = {(path, when): ms for path, when, ms in got["by_owner_class"]}
    assert table[("net/cell/attention/flash_attention_fwd", "forward")] == \
        pytest.approx(2.0)
    assert table[("optimizer", "optimizer")] == pytest.approx(3.0)
    assert table[("", "other")] == pytest.approx(1.0)
    # the 7..9 ms hole lies in `enqueue` (1.5 ms) and `args` (0.5 ms): the
    # child that covers most names it, not the parent that covers it all;
    # the 8 ms between the steps have no span of the program's
    assert got["idle_gaps_ms"] == pytest.approx(
        {"mxtpu.step.enqueue": 2.0, "no-mxtpu-span": 4.0})


def test_reduce_averages_over_chips():
    planes = _hand_made()
    planes["/device:TPU:1"] = planes["/device:TPU:0"]
    got = scopes.reduce(planes, NAMES)
    assert got["device_ms"] == pytest.approx(10.0)
    assert got["programs_per_step"] == 2.0


@pytest.mark.parametrize("planes,names", [
    ({}, NAMES),
    ({xplane.HOST_PLANE: _hand_made()[xplane.HOST_PLANE]}, NAMES),
    # a program from before the names: no step span, or no `tf_op`
    ({"/device:TPU:0": _hand_made()["/device:TPU:0"],
      xplane.HOST_PLANE: {"python3": [("bench.dispatch", 0, 1)]}}, NAMES),
    (_hand_made(), {}),
], ids=["empty", "host-only", "no-step-span", "no-op-names"])
def test_reduce_reads_nothing_where_the_names_are_not(planes, names):
    assert scopes.reduce(planes, names) is None


# -- the recorded trace -----------------------------------------------------

def test_op_names_of_the_recorded_trace(tmp_path):
    bench = _bench(tmp_path, SCOPED)
    names = scopes.op_names(xplane.newest(bench.trace_dir))
    assert len(names) == RECORDED["op_names"]
    kernels = {xplane.kind(name): scopes.owner_class(scopes.owner(op_name))
               for name, op_name in names.items() if "custom-call" in name
               and "flash" in name}
    attention = ("transformer_lm/transformer_lm_cell/causal_self_attention/"
                 "attention/")
    assert kernels == {
        "%flash_attention_fwd": attention + "flash_attention_fwd",
        "%flash_attention_dq": attention + "flash_attention_dq",
        "%flash_attention_dkv": attention + "flash_attention_dkv"}
    # `tf_op` is "<op_name>:<op type>", the type empty: the colon is cut
    assert not [name for name in names.values() if name.endswith(":")]
    assert sum(name.startswith("jit(") for name in names.values()) > 800


def test_op_names_of_a_trace_without_them(tmp_path):
    bench = _bench(tmp_path, UNNAMED)
    assert scopes.op_names(xplane.newest(bench.trace_dir)) == {}
    for metric in READERS:
        assert _reader(metric)(bench) is None
    assert bench.scoped is None and bench.notes == []


# the recorded trace's own numbers (my chip run, PR 25)
RECORDED = {
    "op_names": 851,
    "forward_ms.train": 134.03999033333332,
    "backward_ms.train": 196.21215633333333,
    "optimizer_ms.train": 1.7391776666666667,
    "attention_fwd_ms.train": 79.67539466666666,
    "attention_bwd_ms.train": 126.72457233333333,
    "norm_ms.train": 1.4919933333333333,
    "programs_per_step.train": 4.0,
    "step_enqueue_ms.train": 1.94796,
    "other_ms": 2.5611176666666666,
    "unowned_pct": 0.7655354871588911,
    "flash_attention_fwd": 73.20522466666667,
    "flash_attention_dq": 54.80172966666667,
    "flash_attention_dkv": 67.37757866666666,
}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    return _bench(tmp_path_factory.mktemp("scoped"), SCOPED)


@pytest.mark.parametrize("metric", READERS)
def test_reader_on_the_recorded_trace(recorded, metric):
    assert _reader(metric)(recorded) == pytest.approx(RECORDED[metric],
                                                      rel=1e-9)
    assert len(recorded.notes) == 1     # the trace is reduced once


def test_recorded_trace_sums_to_busy_time_and_names_every_kernel(recorded):
    scoped = scopes.of(recorded)
    assert recorded.notes[0]["scoped"]["busy_ms_per_step"] == \
        pytest.approx(recorded.trace_summary["busy_s"] * 1e3 / 3)
    assert scoped["steps"] == 3
    assert scoped["phase_ms"]["other"] == pytest.approx(RECORDED["other_ms"])
    # no operation runs beside another on this line: the sum IS busy time
    assert sum(scoped["phase_ms"].values()) == pytest.approx(
        recorded.trace_summary["busy_s"] * 1e3 / 3, rel=1e-6)
    assert scoped["unowned_pct"] == pytest.approx(RECORDED["unowned_pct"])
    assert len(scoped["by_owner_class"]) == scopes.TOP
    rows = {path.rsplit("/", 1)[-1]: ms
            for path, _, ms in scoped["by_owner_class"]}
    for kernel in ("flash_attention_fwd", "flash_attention_dq",
                   "flash_attention_dkv"):
        assert rows[kernel] == pytest.approx(RECORDED[kernel])
    # the kernels are the best part of the scope; the rest is the head
    # split, the padding of 64 to 128 and the merge around them
    assert rows["flash_attention_fwd"] < RECORDED["attention_fwd_ms.train"]
    assert set(scoped["idle_gaps_ms"]) <= {
        "between-ops", "no-mxtpu-span", "mxtpu.step", "mxtpu.step.args",
        "mxtpu.step.enqueue", "mxtpu.step.rebind"}


@pytest.mark.parametrize("metric", READERS)
def test_reader_returns_nothing_without_a_trace(metric):
    bench = types.SimpleNamespace(trace_summary=None, trace_dir="/nowhere")
    assert _reader(metric)(bench) is None
