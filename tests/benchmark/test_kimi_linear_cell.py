"""What PR 32 added to the benchmark: the Kimi-Linear configuration's file
against its published source, its operation counts written out by hand, the
two readers of the linear-attention scan, and the cell under `--rehearse`
end to end with the plain reference deciding. Nothing here is a device
number."""
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

CONFIG = "kimi_linear_48b_a3b_ep32"
CELL = "kimi_linear_ep32_train_b1_s8192"
TRAFFIC = "train_b1_s8192_kda_ref"
NEW = ["linear_attention_ms.train", "linear_attention_roofline_pct.train"]
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
    assert (doc["hidden_size"], doc["intermediate_size"],
            doc["moe_intermediate_size"]) == (2304, 9216, 1024)
    assert doc["linear_attn_config"]["num_heads"] == 32
    assert doc["linear_attn_config"]["head_dim"] == 128
    assert doc["linear_attn_config"]["short_conv_kernel_size"] == 4
    assert (doc["num_attention_heads"], doc["qk_nope_head_dim"],
            doc["qk_rope_head_dim"], doc["v_head_dim"],
            doc["kv_lora_rank"]) == (32, 128, 64, 128, 512)
    assert doc["q_lora_rank"] is None and doc["mla_use_nope"] is True
    assert (doc["num_experts"], doc["num_experts_per_token"],
            doc["num_experts_per_tok"], doc["num_shared_experts"]) == (
                256, 8, 8, 1)
    assert doc["moe_router_activation_func"] == "sigmoid"
    assert doc["routed_scaling_factor"] == 2.446 and doc["moe_renormalize"]
    assert doc["rms_norm_eps"] == 1e-5 and doc["head_dim"] == 72
    assert doc["tie_word_embeddings"] is False
    # the layer pattern, derived from the source's own lists (from 1)
    kda = doc["linear_attn_config"]
    assert len(doc["layer_types"]) == len(doc["mlp_layer_types"]) == 27
    for i, (kind, mlp) in enumerate(zip(doc["layer_types"],
                                        doc["mlp_layer_types"])):
        assert (kind == "linear_attention") == (i + 1 in kda["kda_layers"])
        assert (kind == "latent_attention") == (
            i + 1 in kda["full_attn_layers"])
        assert (mlp == "dense") == (i < doc["first_k_dense_replace"])
    assert doc["num_hidden_layers"] == 5
    assert doc["layer_types"][:5].count("linear_attention") == 4
    assert doc["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                "vocab_size": 163840}
    assert doc["num_experts_held"] == {"first": 0, "count": 8}
    assert doc["vocab_rows_held"] * 8 == doc["vocab_size"] == 163840
    assert "32 chips" in doc["deployment"]
    assert len(doc["source"]) <= 200 and "config.json" in doc["source"]
    for key in ("kda_ranks", "kda_bias", "kda_norms", "kda_init", "head_dim",
                "router", "positions", "learning_rate", "expert_load"):
        assert len(doc["assumed"][key]) > 40, key


def test_the_file_is_the_catalogs_config(doc):
    """Every number of the catalog's entry under the same key, but the
    depth (`reduced`); nested groups whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        entry = next(json.loads(line) for line in f
                     if "Kimi-Linear-48B-A3B-Instruct" in line)
    assert doc["source"] == entry["source_url"]
    differs = {key for key, value in entry["config"].items()
               if doc.get(key, "absent") != value}
    assert differs == {"num_hidden_layers"}


def test_manifest_entries(manifest):
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert len(cells) == 4 and all(w["chips"] == 1 for w in cells.values())
    cell = cells[CELL]
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert len(entry["why"]) <= 200
    lists = {m["name"]: m.get("workloads")
             for m in manifest["per_layer"] + manifest["end_to_end"]}
    for name in NEW:
        assert lists[name] == [CELL]
    # every list that holds the Mellum2 cell holds this one behind it
    for name, cells_ in lists.items():
        if cells_ and "mellum2_ep8_train_b1_s8192" in cells_:
            assert cells_[-1] == CELL, name


# -- operation counts, by hand ----------------------------------------------

def test_operation_counts(doc, config):
    """Forward 6.306e12 operations a sequence, 18.92e12 with the backward
    (ISSUE 32 forecast 6.36 and 19.07 from rounder parts); by part as
    written out here."""
    traffic = {"batch": 1, "seq": L}
    d = 2304
    kda_projections = 2 * L * d * (4 * 4096 + 32) + 2 * 2 * L * 128 * (
        d + 4096)
    recurrence = 7 * L * 32 * 128 * 128
    assert config.linear_attention_flops(doc, L) == recurrence
    assert recurrence == pytest.approx(0.0301e12, rel=1e-2)
    pairs = L * (L + 1) // 2
    assert config.attention_flops(doc, L, "latent_attention") == \
        2 * pairs * 32 * (192 + 128)
    assert config.attention_flops(doc, L, "linear_attention") == 0
    mla = (2 * L * d * (32 * 192 + 576) + 2 * L * 512 * 32 * 256
           + 2 * L * 32 * 128 * d + 2 * pairs * 32 * 320)
    dense = 3 * 2 * L * d * 9216
    rows = L * 8 * 8 // 256                     # an even share: 2048
    sparse = 2 * L * d * 256 + 3 * 2 * L * d * 1024 + rows * 6 * d * 1024
    head = 2 * L * d * 20480
    forward = 4 * (kda_projections + recurrence) + mla + dense \
        + 4 * sparse + head
    assert config.flops_per_sample(doc, traffic) == 3 * forward
    # (the issue forecast 6.36e12 from rounder parts)
    assert forward == pytest.approx(6.306e12, rel=1e-3)
    assert 4 * kda_projections == pytest.approx(4 * 0.645e12, rel=5e-3)
    assert 2 * pairs * 32 * 320 == pytest.approx(0.687e12, rel=1e-3)
    assert (dense, head) == (pytest.approx(1.044e12, rel=1e-3),
                             pytest.approx(0.773e12, rel=1e-3))
    # the experts' term follows the rows the program counted, a layer
    more = config.flops_per_sample(doc, traffic, [4096] * 4)
    assert more - 3 * forward == pytest.approx(
        3 * 4 * config.expert_flops(doc, 2048))
    assert config.expert_flops(doc, 2048) == 2048 * 6 * d * 1024
    assert config.expert_bytes(doc, 2048) == 2 * (
        8 * 3 * d * 1024 + 2048 * 2 * d)
    # q, k, v, the log-decay in and o out at 128 a head, and beta
    assert config.linear_attention_bytes(doc, L) == 2 * L * 32 * (
        5 * 128 + 1)


def test_ideal_seconds_of_the_accepted_kernels(doc, config):
    """benchmark/traffic/train_steps_ref.py's `ideal_seconds`, unedited,
    on this configuration: the experts' products by the four EXPERT layers'
    live rows, attention by the latent layer's pairs alone."""
    kind = _module("traffic", "train_steps_ref")
    bench = types.SimpleNamespace(config=doc, traffic={"batch": 1, "seq": L},
                                  device_kind="TPU v5 lite")
    got = kind.ideal_seconds(bench, config, [2048] * 4)
    # 8 experts' weights once dominate 2048 rows: bound by bytes
    assert got["moe_experts"] == pytest.approx(
        4 * 3 * 2 * (8 * 3 * 2304 * 1024 + 2048 * 2 * 2304) / 819e9)
    assert got["attention"] == pytest.approx(
        3 * 2 * (L * (L + 1) // 2) * 32 * 320 / 197e12)


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


def test_readers_sum_the_scopes_and_hold_them_to_the_recurrence(doc):
    net = ["moe_lm_0", "moe_lm_cell_1", "linear_attention_cell_1"]
    events = [
        ("fusion.1", net + ["linear_attention", "conv"], "forward", 2e6),
        ("fusion.2", net + ["linear_attention", "scan", "while"], "forward",
         6e6),
        ("fusion.3", net + ["linear_attention", "scan", "while"], "backward",
         18e6),
        ("fusion.4", net + ["linear_attention", "out_norm"], "backward", 4e6),
        ("fusion.5", net + ["dense_3"], "forward", 50e6),
        ("fusion.6", ["moe_lm_0", "scan"], "forward", 7e6),
    ]
    bench = _traced(doc, events)
    assert _module("layers", NEW[0]).read(bench) == pytest.approx(15.0)
    # four layers, three passes, bound by bytes: 336 MB a pass a layer
    ideal = 3 * 4 * 2 * L * 32 * 641 / 819e9
    assert ideal > 3 * 4 * 7 * L * 32 * 128 * 128 / 197e12
    assert _module("layers", NEW[1]).read(bench) == pytest.approx(
        100 * ideal / 12e-3)


@pytest.mark.parametrize("metric", NEW)
def test_readers_read_nothing_where_there_is_nothing(doc, metric):
    """No trace; a program without the scope (the parent commit's); a
    configuration without such a layer or such a count: None, no error."""
    read = _module("layers", metric).read
    assert read(types.SimpleNamespace(trace_summary=None, outcome={})) is None
    other = [("fusion.1", ["net", "cell_1", "moe", "experts"], "forward",
              5e6)]
    assert read(_traced(doc, other)) is None
    scanned = [("fusion.2", ["net", "linear_attention", "scan"], "forward",
                5e6)]
    mellum = _traced(doc, scanned)
    with open(os.path.join(BENCH, "configs",
                           "mellum2_12b_a2.5b_ep8.json")) as f:
        mellum.config = json.load(f)
    mellum.cell = {"config": "mellum2_12b_a2.5b_ep8"}
    if metric == NEW[1]:
        assert read(mellum) is None
        unknown = _traced(doc, scanned)
        unknown.device_kind = "cpu"
        assert read(unknown) is None


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


def _decay_rate_unmoved(config):
    def net(doc, seed):
        model = config.net(doc, seed)
        model.layers[2].attention.a_log.lr_mult = 0.0
        return model
    return {"net": net}


@pytest.mark.parametrize("fault, over", [(None, set()),
                                         (_decay_rate_unmoved, {"update"})],
                         ids=["sound", "a-decay-rate-unmoved"])
def test_the_cell_rehearsed_end_to_end(config, fault, over):
    """The cell's whole control flow at the toy sizes: a sound bfloat16 step
    is inside every limit of the float32 reference, parameter by parameter
    (111 trained: the table; 4 KDA layers of 17 with their norms, the
    latent one of 7; the dense feed-forward's 3 and 4 expert layers of 7;
    the last norm and the head), the four expert layers report their load,
    and a head's decay rate that the optimizer leaves where it was reads
    update = 1."""
    planted = types.SimpleNamespace(**{**vars(config),
                                       **(fault(config) if fault else {})})
    out, notes = _rehearsal(planted)
    verdict = {name: pair for name, pair in notes["reference"].items()
               if name in ("loss1", "loss2", "logits", "gradient", "update")}
    assert {name for name, (error, limit) in verdict.items()
            if not error < limit} == over
    by_parameter = notes["reference"]["by_parameter"]
    assert len(by_parameter["gradient"]) == len(by_parameter["update"]) \
        == len(notes["reference"]["trained"]) == 1 + 4 * 17 + 7 + 3 + 4 * 7 + 2
    assert len(notes["moe"]["live_rows"]) == 4
    assert notes["moe"]["rows_held"] == 2 * 128 * 4
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["end_to_end"]) == {"samples_per_s", "step_ms_p95"}
    if fault:
        assert max(by_parameter["update"]) == pytest.approx(1.0, abs=1e-6)
        assert sorted(by_parameter["update"])[-2] < 0.01
