"""What PR 34 added to the benchmark: the ZAYA1 configuration's file against
its published source, its operation counts written out by hand, the two
readers of the mixer's and the router's scopes, and the cell under
`--rehearse` end to end with the plain reference deciding, sound and with a
fault planted in one stage of the mixer and in the router. Nothing here is a
device number."""
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

CONFIG = "zaya1_8b_ep2"
CELL = "zaya1_ep2_train_b1_s8192"
TRAFFIC = "train_b1_s8192_cca_ref"
NEW = ["compressed_attention_ms.train", "moe_router_ms.train"]
L = 8192


def _module(kind, name):
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _module("configs", CONFIG)


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the configuration ------------------------------------------------------

def test_configuration_keeps_every_published_width(doc):
    """Every key of the catalog's `config` as published but the depth; the
    cuts are stated beside the published values, and what the source lacks
    is under `assumed`."""
    assert doc["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (doc["hidden_size"], doc["moe_intermediate_size"],
            doc["router_hidden_size"]) == (2048, 2048, 256)
    assert (doc["num_attention_heads"], doc["num_key_value_heads"],
            doc["head_dim"]) == (8, 2, 128)
    assert (doc["cca_time0"], doc["cca_time1"]) == (2, 2)
    assert (doc["num_experts"], doc["num_experts_per_tok"]) == (16, 1)
    assert doc["partial_rotary_factor"] == 0.5
    assert doc["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
    assert doc["rms_norm_eps"] == 1e-5 and doc["hidden_act"] == "silu"
    assert doc["tie_word_embeddings"] is True
    assert doc["attention_bias"] is False and doc["lm_head_bias"] is False
    assert doc["sliding_window"] is None
    assert doc["layer_types"] == ["hybrid"] * 40
    assert doc["num_hidden_layers"] == 5
    assert doc["published"] == {"num_hidden_layers": 40, "num_experts": 16,
                                "vocab_size": 262272}
    assert doc["num_experts_held"] == {"first": 0, "count": 8}
    assert doc["vocab_rows_held"] * 8 == doc["vocab_size"] == 262272
    assert "2 chips" in doc["deployment"]
    assert len(doc["source"]) <= 200 and "config.json" in doc["source"]
    for key in ("depth", "experts", "vocabulary", "expert_width",
                "value_shift", "convolutions", "qk_mean", "unit_norms",
                "positions", "router", "residuals", "aux_loss", "embedding",
                "init", "learning_rate", "batch",
                "expert_load"):
        assert len(doc["assumed"][key]) > 40, key


def test_the_file_is_the_catalogs_config(doc):
    """Every number of the catalog's entry under the same key, but the
    depth (`reduced`); nested groups whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        entry = next(row for row in map(json.loads, f)
                     if row["name"] == "ZAYA1-8B")
    assert doc["source"] == entry["source_url"]
    differs = {key for key, value in entry["config"].items()
               if doc.get(key, "absent") != value}
    assert differs == {"num_hidden_layers"}


def test_manifest_entries(manifest):
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert all(w["chips"] == 1 for w in cells.values())
    cell = cells[CELL]
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    lists = {m["name"]: m.get("workloads")
             for m in manifest["per_layer"] + manifest["end_to_end"]}
    for name in NEW:
        assert lists[name] == [CELL]
    # every list that holds the two expert cells holds this one behind
    # them, in the order the cells came in
    order = list(cells)
    shared = 0
    for name, cells_ in lists.items():
        if cells_ and "mellum2_ep8_train_b1_s8192" in cells_:
            assert CELL in cells_, name
            assert cells_ == sorted(cells_, key=order.index), name
            shared += 1
    assert shared == 15


def test_the_kimi_cells_entries_stand(manifest):
    """Every check of `test_kimi_linear_cell.py::test_manifest_entries` but
    the two a fifth cell breaks (four cells; its own cell LAST in the lists
    it shares with the Mellum2 cell), which conftest.py marks as expected
    to fail: held here, line for line, until a `benchmark` PR rewords that
    test. In place of "last": right behind the Mellum2 cell."""
    config, cell_, traffic = ("kimi_linear_48b_a3b_ep32",
                              "kimi_linear_ep32_train_b1_s8192",
                              "train_b1_s8192_kda_ref")
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert all(w["chips"] == 1 for w in cells.values())
    cell = cells[cell_]
    assert (cell["config"], cell["traffic"]) == (config, traffic)
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in manifest["configs"]}[config]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert len(entry["why"]) <= 200
    lists = {m["name"]: m.get("workloads")
             for m in manifest["per_layer"] + manifest["end_to_end"]}
    for name in ("linear_attention_ms.train",
                 "linear_attention_roofline_pct.train"):
        assert lists[name] == [cell_]
    for name, cells_ in lists.items():
        if cells_ and "mellum2_ep8_train_b1_s8192" in cells_:
            at = cells_.index("mellum2_ep8_train_b1_s8192")
            assert cells_[at + 1] == cell_, name
    # the four cells the benchmark had, in their order, before this one
    assert list(cells)[:5] == [
        "resnet50_train_b256", "gpt2_train_b16_s1024",
        "mellum2_ep8_train_b1_s8192", cell_, CELL]


# -- operation counts, by hand ----------------------------------------------

def test_operation_counts(doc, config):
    """ISSUE 34's count: a layer forward on one sequence is projections
    85.9e9 + the mixing convolution 5.4e9 + attention 137.4e9 + router
    10.8e9 + experts on 4096 rows 103.1e9 = 342.6e9; six layers 2.056e12,
    the head over 32784 rows 1.100e12; x 3 = 9.47e12 a sequence at an even
    share."""
    traffic = {"batch": 1, "seq": L}
    six = dict(doc, num_hidden_layers=6)
    projections = 2 * L * 2048 * 128 * (8 + 2 + 2 + 8)
    mixing = 2 * L * 2 * 10 * 128 * 128
    pairs = L * (L + 1) // 2
    attention = 4 * pairs * 128 * 8
    router = 2 * L * (2048 * 256 + 256 * 256 + 256 * 256 + 256 * 16)
    experts = 4096 * 3 * 2 * 2048 * 2048
    head = 2 * L * 2048 * 32784
    assert pairs == 33558528
    assert config.attention_flops(doc, L, "hybrid") == attention
    assert config.expert_flops(doc, 4096) == experts
    assert (projections, mixing, attention, router, experts) == (
        pytest.approx(85.9e9, rel=1e-3), pytest.approx(5.4e9, rel=1e-2),
        pytest.approx(137.4e9, rel=1e-3), pytest.approx(10.8e9, rel=1e-3),
        pytest.approx(103.1e9, rel=1e-3))
    layer = projections + mixing + attention + router + experts
    assert layer == pytest.approx(342.6e9, rel=1e-3)
    assert head == pytest.approx(1.100e12, rel=1e-3)
    assert config.flops_per_sample(six, traffic) == 3 * (6 * layer + head)
    assert 3 * (6 * layer + head) == pytest.approx(9.47e12, rel=1e-3)
    # MFU % = samples_per_s x this
    assert 100 * 3 * (6 * layer + head) / 197e12 == pytest.approx(4.81,
                                                                  rel=1e-3)
    five = dict(doc, num_hidden_layers=5)
    assert config.flops_per_sample(five, traffic) == 3 * (5 * layer + head)
    # the experts' term follows the rows the program counted, a layer
    more = config.flops_per_sample(six, traffic, [5120] * 6)
    assert more - 3 * (6 * layer + head) == pytest.approx(
        3 * 6 * config.expert_flops(doc, 1024))
    assert config.expert_bytes(doc, 4096) == 2 * (
        8 * 3 * 2048 * 2048 + 4096 * 2 * 2048)


def test_ideal_seconds_of_the_accepted_kernels(doc, config):
    """benchmark/traffic/train_steps_ref.py's `ideal_seconds`, unedited, on
    this configuration: at ~512 rows an expert the grouped products are
    bound by their operations, where the Kimi cell's are by the weights'
    bytes; attention by the causal pairs of 8 heads a layer."""
    kind = _module("traffic", "train_steps_ref")
    depth = doc["num_hidden_layers"]
    bench = types.SimpleNamespace(config=doc, traffic={"batch": 1, "seq": L},
                                  device_kind="TPU v5 lite")
    got = kind.ideal_seconds(bench, config, [4096] * depth)
    by_flops = 3 * 4096 * 6 * 2048 * 2048 / 197e12
    by_bytes = 3 * 2 * (8 * 3 * 2048 * 2048 + 4096 * 2 * 2048) / 819e9
    assert by_flops > by_bytes
    assert got["moe_experts"] == pytest.approx(depth * by_flops)
    assert got["attention"] == pytest.approx(
        depth * 3 * 4 * (L * (L + 1) // 2) * 128 * 8 / 197e12)


# -- the two readers --------------------------------------------------------

def _traced(doc, events, steps=2):
    """A bench whose trace has been read: `owned.events`'s own stub."""
    notes = []
    return types.SimpleNamespace(
        config=doc, traffic={"batch": 1, "seq": L},
        cell={"config": CONFIG}, device_kind="TPU v5 lite",
        trace_summary={"busy_s": 1.0}, scoped={"steps": steps},
        owned_events=(events, steps), outcome={},
        note=lambda **fields: notes.append(fields))


def test_readers_sum_their_scopes(doc):
    cell = ["moe_lm_0", "moe_lm_cell_1"]
    mixer = cell + ["compressed_attention_cell_1", "compressed_attention"]
    experts = cell + ["sparse_experts_1"]
    events = [
        ("fusion.1", mixer + ["conv"], "forward", 2e6),
        ("fusion.2", mixer + ["mean"], "backward", 1e6),
        ("fusion.3", mixer + ["norm"], "forward", 1e6),
        ("fusion.4", mixer + ["rope"], "backward", 2e6),
        ("flash_attention_fwd.1", mixer + ["attention",
                                           "flash_attention_fwd"],
         "forward", 6e6),
        ("fusion.5", cell + ["compressed_attention_cell_1", "dense_4"],
         "forward", 50e6),
        ("fusion.6", experts + ["moe", "router", "down"], "forward", 3e6),
        ("fusion.7", experts + ["moe", "router", "mlp"], "backward", 4e6),
        ("fusion.8", experts + ["moe", "router"], "forward", 1e6),
        ("fusion.9", experts + ["moe", "experts"], "forward", 40e6),
        ("fusion.10", cell + ["router"], "forward", 9e6),
    ]
    bench = _traced(doc, events)
    assert _module("layers", NEW[0]).read(bench) == pytest.approx(6.0)
    assert _module("layers", NEW[1]).read(bench) == pytest.approx(4.0)
    # the accepted reader of the whole expert layer holds the router too
    assert _module("layers", "moe_ms.train").read(bench) == pytest.approx(
        24.0)


@pytest.mark.parametrize("metric", NEW)
def test_readers_read_nothing_where_there_is_nothing(doc, metric):
    """No trace; a program without the scope (the parent commit's, or
    another configuration's): None, no error."""
    read = _module("layers", metric).read
    assert read(types.SimpleNamespace(trace_summary=None, outcome={})) is None
    other = [("fusion.1", ["net", "cell_1", "moe", "experts"], "forward",
              5e6),
             ("fusion.2", ["net", "cell_1", "attention"], "forward", 5e6)]
    assert read(_traced(doc, other)) is None


# -- the traffic file and the cell, rehearsed -------------------------------

def test_traffic_file_states_limits_with_their_reason():
    with open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["kind"], traffic["batch"], traffic["seq"]) == (
        "train_steps_ref", 1, L)
    reference = traffic["reference"]
    assert (reference["rows"], reference["positions"]) == (256, 512)
    assert set(reference["limits"]) == set(
        traffic["rehearse"]["reference"]["limits"]) == {
            "loss1", "loss2", "logits", "gradient", "update"}
    assert all(0 < limit < 1 for limit in reference["limits"].values())
    assert len(reference["why"]) > 100
    assert (traffic["rehearse"]["batch"], traffic["rehearse"]["seq"]) == (
        2, 128)


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


def _taps_in_the_wrong_order(config):
    """A fault in one stage of the mixer: layer 1's depthwise convolution
    weighs the token before with the tap of the token itself."""
    def net(doc, seed):
        from incubator_mxnet_tpu import nd, ops
        model = config.net(doc, seed)
        cell = model.layers[1].attention

        def forward(x):
            inv_freq, factor = cell._rope
            taps = cell.conv0.data()
            out = ops.compressed_attention(
                cell.q(x), cell.k(x), cell.v(x),
                nd.concat(taps[1:2], taps[0:1], dim=0), cell.conv1.data(),
                cell.temp.data(), inv_freq, *cell._heads, cell._rotary_dim,
                factor)
            return cell.proj(out)
        cell.forward = forward
        return model
    return {"net": net}


def _state_not_handed_on(config):
    """A fault in the router: layer 2 is not given layer 1's state, which
    at gamma's zeros moves no result and leaves gamma no gradient."""
    def net(doc, seed):
        model = config.net(doc, seed)
        cell = model.layers[2]
        whole = cell.forward
        cell.forward = lambda x, state=None: whole(x)
        return model
    return {"net": net}


@pytest.mark.parametrize("fault, over", [
    (None, set()),
    (_taps_in_the_wrong_order, {"gradient"}),
    (_state_not_handed_on, {"gradient"}),
], ids=["sound", "mixer-taps-swapped", "router-state-dropped"])
def test_the_cell_rehearsed_end_to_end(config, fault, over):
    """The cell's whole control flow at the toy sizes: a sound bfloat16 step
    is inside every limit of the float32 reference, parameter by parameter
    (52 trained: the tied table; 3 layers of 2 norms, the mixer's 7 and the
    expert layer's 7, a gamma behind the first; the last norm), and the
    three expert layers report their load at one assignment a token. A
    mixer whose taps are swapped is refused by the gradients at least (its
    own taps' first); a router state that is dropped by gamma's gradient
    alone, which reads 1 (the losses and the logits cannot tell: gamma
    starts at zero)."""
    planted = types.SimpleNamespace(**{**vars(config),
                                       **(fault(config) if fault else {})})
    out, notes = _rehearsal(planted)
    verdict = {name: pair for name, pair in notes["reference"].items()
               if name in ("loss1", "loss2", "logits", "gradient", "update")}
    refused = {name for name, (error, limit) in verdict.items()
               if not error < limit}
    assert refused >= over and bool(refused) == bool(over)
    assert out["correct"] is (notes["loss_fell"] and not over)
    by_parameter = notes["reference"]["by_parameter"]
    assert len(by_parameter["gradient"]) == len(by_parameter["update"]) \
        == len(notes["reference"]["trained"]) == 1 + 3 * 16 + 2 + 1
    assert len(notes["moe"]["live_rows"]) == 3
    assert notes["moe"]["rows_held"] == 2 * 128
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["end_to_end"]) == {"samples_per_s", "step_ms_p95"}
    if fault is _state_not_handed_on:
        assert refused == {"gradient"}
        assert sorted(by_parameter["gradient"])[-1] == pytest.approx(1.0)
        assert sorted(by_parameter["gradient"])[-2] < 0.5
