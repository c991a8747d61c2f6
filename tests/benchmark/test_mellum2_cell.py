"""What PR 28 added to the benchmark: lib/owned.py and the three readers
that stand on it, the Mellum2 configuration's file against its published
source, its operation counts, and the traffic kind whose `correct` the
plain reference decides. Nothing here is a device number."""
import gzip
import importlib.util
import json
import os
import re
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from lib import owned, scopes, xplane  # noqa: E402

# three steps of the GPT-2 cell on the chip (PR 25): a program with the
# names and WITHOUT the op scope `moe`, as the parent commit's is
SCOPED = os.path.join(HERE, "gpt2_train_b16_s1024.scoped.xplane.pb.gz")
CONFIG = "mellum2_12b_a2.5b_ep8"
CELL = "mellum2_ep8_train_b1_s8192"
NEW = ["moe_ms.train", "moe_experts_roofline_pct.train",
       "attention_window_roofline_pct.train", "rms_norm_ms.train"]


def _module(kind, name):
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _bench(tmp_path, outcome=None):
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    path = where / "host.xplane.pb"
    with gzip.open(SCOPED) as f:
        path.write_bytes(f.read())
    notes = []
    return types.SimpleNamespace(
        trace_dir=str(tmp_path), notes=notes, outcome=outcome or {},
        trace_summary=xplane.reduce(xplane.load(str(path))),
        note=lambda **fields: notes.append(fields))


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _module("configs", CONFIG)


# -- lib/owned.py -----------------------------------------------------------

def test_owned_sums_what_scopes_reduce_sums(tmp_path):
    """The device time under the op scope `attention`, both phases, is what
    lib/scopes.py's own reduction reads for the two accepted metrics."""
    bench = _bench(tmp_path)
    scoped = scopes.of(bench)
    got = owned.ms_per_step(bench, owned.under("attention"))
    assert got == pytest.approx(sum(scoped["attention_ms"].values()),
                                rel=1e-9)
    forward = owned.ms_per_step(
        bench, lambda name, parts, phase: "attention" in parts
        and phase == "forward")
    assert forward == pytest.approx(scoped["attention_ms"]["forward"],
                                    rel=1e-9)
    every, steps = owned.events(bench)
    assert steps == scoped["steps"] == 3
    assert sum(ns for *_, ns in every) / 1e6 / steps == pytest.approx(
        scoped["device_ms"], rel=1e-9)
    assert owned.events(bench) is bench.owned_events     # read once


def test_under_matches_components_in_a_row():
    wanted = owned.under("moe", "experts")
    assert wanted("", ["net", "cell_1", "moe", "experts", "gmm"], "forward")
    assert not wanted("", ["net", "moe", "router"], "forward")
    assert not wanted("", ["net", "moe_lm_cell_1", "experts"], "forward")
    assert not wanted("", [""], "other")


@pytest.mark.parametrize("metric", NEW)
def test_new_readers_read_nothing_from_a_program_without_their_scopes(
        tmp_path, metric):
    """On the parent commit's program (no `moe` scope, no ideal times from
    the traffic driver) the readers return None and do not raise."""
    assert _module("layers", metric).read(_bench(tmp_path)) is None
    no_trace = types.SimpleNamespace(trace_summary=None, outcome={})
    assert _module("layers", metric).read(no_trace) is None


def test_attention_roofline_is_ideal_over_the_kernels_time(tmp_path):
    """The share is the ideal seconds a step over the device time of the
    `flash_attention_*` kernels alone, here the recorded GPT-2 step's."""
    bench = _bench(tmp_path, {"ideal_s_per_step": {"attention": 0.002,
                                                   "moe_experts": 0.001}})
    kernels = owned.ms_per_step(
        bench, lambda name, parts, phase: "flash_attention_" in name)
    scoped = scopes.of(bench)
    assert 0 < kernels < sum(scoped["attention_ms"].values())
    got = _module("layers", NEW[2]).read(bench)
    assert got == pytest.approx(100 * 0.002 / (kernels / 1e3), rel=1e-9)
    by_block = [n["attention_kernels_ms"] for n in bench.notes
                if "attention_kernels_ms" in n][0]
    assert len(by_block) == 12 and sum(by_block.values()) == pytest.approx(
        kernels, rel=1e-9)
    # no `moe` owner in this program: that share stays unread
    assert _module("layers", NEW[1]).read(bench) is None


# -- the configuration ------------------------------------------------------

def test_configuration_keeps_every_published_width(doc):
    """Every key of the catalog's `config` as published but the depth; the
    cuts are stated beside the published values."""
    assert doc["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (doc["hidden_size"], doc["num_attention_heads"],
            doc["num_key_value_heads"], doc["head_dim"]) == (2304, 32, 4, 128)
    assert (doc["moe_intermediate_size"], doc["num_experts"],
            doc["num_experts_per_tok"]) == (896, 64, 8)
    assert doc["sliding_window"] == 1024 and doc["rms_norm_eps"] == 1e-6
    assert doc["tie_word_embeddings"] is False
    assert doc["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"] and len(doc["layer_types"]) == 28
    assert doc["num_hidden_layers"] == 4
    assert doc["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                "vocab_size": 98304}
    assert doc["num_experts_held"] == {"first": 0, "count": 8}
    assert doc["vocab_rows_held"] * 8 == doc["vocab_size"] == 98304
    assert "8 chips" in doc["deployment"]
    assert len(doc["source"]) <= 200 and "config.json" in doc["source"]


def test_operation_counts(doc, config):
    """The counts of ISSUE 28: visible pairs exact, 391.5M operations a
    token forward, 9.6 TFLOP a step; a sliding layer's attention is 0.234
    of the full layer's."""
    traffic = {"batch": 1, "seq": 8192}
    assert config.visible_pairs(doc, 8192, "sliding_attention") == 7864832
    assert config.visible_pairs(doc, 8192, "full_attention") == 33558528
    assert config.flops_per_sample(doc, traffic) == pytest.approx(
        9.622e12, rel=1e-3)
    assert (config.attention_flops(doc, 8192, "sliding_attention")
            / config.attention_flops(doc, 8192, "full_attention")
            == pytest.approx(0.2344, rel=1e-3))
    # the experts' term follows the rows the program counted
    more = config.flops_per_sample(doc, traffic, [16384] * 4)
    assert more - config.flops_per_sample(doc, traffic) == pytest.approx(
        3 * 4 * config.expert_flops(doc, 8192))
    assert config.expert_flops(doc, 8192) == 8192 * 6 * 2304 * 896
    assert config.expert_bytes(doc, 8192) == 2 * (
        8 * 3 * 2304 * 896 + 8192 * 2 * 2304)


# -- the traffic kind -------------------------------------------------------

def test_traffic_file_states_limits_with_their_reason():
    with open(os.path.join(BENCH, "traffic", "train_b1_s8192_ref.json")) as f:
        traffic = json.load(f)
    assert (traffic["kind"], traffic["batch"], traffic["seq"]) == (
        "train_steps_ref", 1, 8192)
    reference = traffic["reference"]
    limits = reference["limits"]
    assert set(limits) == set(traffic["rehearse"]["reference"]["limits"]) == {
        "loss1", "loss2", "logits", "gradient", "update"}
    # between the readings of PERF.md: the program's largest over its
    # seeds, and the reference with float8 operands or a parameter unmoved
    assert 0.0363 < limits["logits"] < 0.1129
    assert 1.18e-4 < limits["loss1"] == limits["loss2"] < 1.15e-3
    assert 0.0775 < limits["gradient"] < 0.351
    assert 6.6e-4 < limits["update"] < 1
    assert len(reference["why"]) > 100


def _rehearsal(config, seed=3000000019):
    """The traffic kind's own run at the files' toy sizes, in this process:
    (what it returned, the lines it printed)."""
    import run
    manifest = run.read_json(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    traffic = run.sized(run.read_json(BENCH, "traffic",
                                      cell["traffic"] + ".json"), True)
    doc = run.sized(run.read_json(BENCH, "configs", CONFIG + ".json"), True)
    from lib import compile_log
    bench = run.Bench(types.SimpleNamespace(seed=seed, seconds=1.0, trace=0),
                      cell, doc, traffic, compile_log.CompileLog(), "cpu")
    notes = {}
    bench.note = lambda **fields: notes.update(fields)
    return run.load("traffic", traffic["kind"]).run(bench, config), notes


def _one_leaf_unmoved(config):
    def net(doc, seed):
        model = config.net(doc, seed)
        model.layers[1].ffn.up.lr_mult = 0.0
        return model
    return {"net": net}


def _gradient_scaled(config):
    def optimizer(doc):
        opt = config.optimizer(doc)
        opt.rescale_grad = 0.25
        return opt
    return {"optimizer": optimizer}


@pytest.mark.parametrize("fault, over", [
    (None, set()),
    (_one_leaf_unmoved, {"update"}),
    (_gradient_scaled, {"gradient"}),
], ids=["sound", "one-leaf-unmoved", "gradient-scaled"])
def test_the_reference_holds_the_steps_backward_and_update(config, fault,
                                                           over):
    """A sound step is inside every limit; a parameter the optimizer leaves
    where it was reads update = 1 and a gradient a quarter of its size
    reads 0.75, and `correct` comes out false, though both losses, the
    logits and `loss_fell` cannot tell (Adam's update does not see the
    gradient's scale)."""
    planted = types.SimpleNamespace(**{**vars(config),
                                       **(fault(config) if fault else {})})
    out, notes = _rehearsal(planted)
    verdict = {name: pair for name, pair in notes["reference"].items()
               if name in ("loss1", "loss2", "logits", "gradient", "update")}
    assert {name for name, (error, limit) in verdict.items()
            if not error < limit} == over
    assert out["correct"] == (notes["loss_fell"] and not over)
    by_parameter = notes["reference"]["by_parameter"]
    assert len(by_parameter["gradient"]) == len(by_parameter["update"]) \
        == len(notes["reference"]["trained"]) == 1 + 4 * 10 + 2
    if fault is _one_leaf_unmoved:
        assert max(by_parameter["update"]) == pytest.approx(1.0, abs=1e-6)
        assert sorted(by_parameter["update"])[-2] < 0.01
    if fault is _gradient_scaled:
        assert min(by_parameter["gradient"]) == pytest.approx(0.75, abs=0.05)


def test_control_sides_are_refused(config):
    """tools/reference_control.py's two sides at the toy sizes: the
    reference with float8 operands reads a gradient error several times the
    bfloat16 program's, and an unmoved parameter reads update = 1."""
    import run
    manifest = run.read_json(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    traffic = run.sized(run.read_json(BENCH, "traffic",
                                      cell["traffic"] + ".json"), True)
    doc = run.sized(run.read_json(BENCH, "configs", CONFIG + ".json"), True)
    bench = run.Bench(types.SimpleNamespace(seed=11, seconds=0, trace=0),
                      cell, doc, traffic, None, "cpu")
    bench.note = lambda **fields: None
    kind = run.load("traffic", traffic["kind"])
    sides = kind.control(bench, config, "float8_e4m3fn", unmoved=3)
    assert sides["unmoved"]["update"][0] == pytest.approx(1.0, abs=1e-6)
    assert not kind.inside(sides["unmoved"])
    assert sides["unmoved"]["gradient"][0] == 0
    low = sides["float8_e4m3fn"]
    assert 0.15 < low["gradient"][0] < 1 and low["update"][0] < 0.01
    assert low["logits"][0] > 0.01 and low["loss1"][0] > 1e-4


def test_ideal_seconds_from_the_configurations_counts(doc, config):
    kind = _module("traffic", "train_steps_ref")
    bench = types.SimpleNamespace(
        config=doc, traffic={"batch": 1, "seq": 8192},
        device_kind="TPU v5 lite")
    got = kind.ideal_seconds(bench, config, [8192] * 4)
    # compute-bound: 3 x 8192 x 6 x 2304 x 896 / 197e12 a layer
    assert got["moe_experts"] == pytest.approx(
        4 * 3 * 8192 * 6 * 2304 * 896 / 197e12)
    assert got["attention"] == pytest.approx(
        3 * 4 * 128 * 32 * (3 * 7864832 + 33558528) / 197e12)
    bench.device_kind = "cpu"
    assert kind.ideal_seconds(bench, config, [8192] * 4) is None


def test_sidecar_reads_a_process_threads_from_outside():
    """tools/host_sidecar.py's look at a process: its threads by scheduler
    state, and the ones that used CPU since the look before."""
    spec = importlib.util.spec_from_file_location(
        "host_sidecar", os.path.join(ROOT, "tools", "host_sidecar.py"))
    sidecar = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sidecar)
    ticks = {}
    states, busy = sidecar.threads(os.getpid(), ticks)
    assert sum(states.values()) >= 1 and busy == [] and ticks
    sum(i * i for i in range(3_000_000))        # a few ticks of this thread
    states, busy = sidecar.threads(os.getpid(), ticks)
    assert busy and busy[0][1] >= 1
