"""The benchmark's JoyAI-LLM-Flash cell: the configuration's
file against its published source, its operation counts written out by
hand, the readers of the latent layers' and the MTP module's scopes, and
the cell under `--rehearse` end to end with the plain reference deciding,
sound and with a fault planted in the rotation, in the MTP's input and in
the query's rank. Nothing here is a device number."""
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

CONFIG = "joyai_llm_flash_ep32"
CELL = "joyai_flash_ep32_train_b1_s8192"
TRAFFIC = "train_b1_s8192_mtp_ref"
NEW = ["latent_attention_ms.train", "mtp_ms.train"]
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
    """Every width as published; the cuts stated beside the published
    values, and what the source lacks under `assumed`."""
    assert doc["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (doc["hidden_size"], doc["intermediate_size"],
            doc["moe_intermediate_size"]) == (2048, 7168, 768)
    assert (doc["num_attention_heads"], doc["q_lora_rank"],
            doc["kv_lora_rank"], doc["qk_nope_head_dim"],
            doc["qk_rope_head_dim"], doc["v_head_dim"]) == (
                32, 1536, 512, 128, 64, 128)
    assert doc["qk_head_dim"] == 192 and doc["rope_interleave"] is True
    assert doc["rope_theta"] == 32000000 and doc["rope_scaling"] is None
    assert (doc["n_routed_experts"], doc["num_experts_per_tok"],
            doc["n_shared_experts"], doc["n_group"], doc["topk_group"]) == (
                256, 8, 1, 1, 1)
    assert (doc["scoring_func"], doc["topk_method"]) == ("sigmoid",
                                                         "noaux_tc")
    assert doc["routed_scaling_factor"] == 2.5 and doc["norm_topk_prob"]
    assert doc["num_nextn_predict_layers"] == 1
    assert doc["mtp_loss_weight"] == 0.3
    assert doc["tie_word_embeddings"] is False
    assert doc["layer_types"] == ["latent_attention"] * 40
    assert doc["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert doc["num_hidden_layers"] == 5
    assert doc["published"] == {"num_hidden_layers": 40,
                                "n_routed_experts": 256,
                                "vocab_size": 129280}
    assert doc["num_experts_held"] == {"first": 0, "count": 8}
    assert doc["vocab_rows_held"] * 8 == doc["vocab_size"] == 129280
    assert "32 chips" in doc["deployment"]
    assert len(doc["source"]) <= 200 and "config.json" in doc["source"]
    for key in ("depth", "experts", "vocabulary", "head_dim",
                "latent_attention", "positions", "router", "aux_loss", "mtp",
                "mtp_loss", "init", "learning_rate", "batch", "expert_load"):
        assert len(doc["assumed"][key]) > 40, key


def test_the_file_is_the_catalogs_config(doc):
    """Every number of the catalog's entry under the same key, but the
    depth (`reduced`). The catalog is the JSON-lines file named by
    `MODEL_CONFIG_CATALOG`, else the entry pinned beside this test."""
    catalog = os.environ.get(
        "MODEL_CONFIG_CATALOG",
        os.path.join(HERE, "joyai_llm_flash.catalog.jsonl"))
    with open(catalog) as f:
        entry = next(row for row in map(json.loads, f)
                     if row["name"] == "JoyAI-LLM-Flash")
    assert doc["source"] == entry["source_url"]
    differs = {key for key, value in entry["config"].items()
               if doc.get(key, "absent") != value}
    assert differs == {"num_hidden_layers"}


def test_manifest_entries(manifest):
    """The sixth cell, on one chip, appended last; in every list that names
    cells of the accepted expert cells' kinds but the attention roofline
    (`train_steps_ref.ideal_seconds` counts no MTP block)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert all(w["chips"] == 1 for w in cells.values())
    assert list(cells)[-1] == CELL
    cell = cells[CELL]
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    lists = {m["name"]: m.get("workloads")
             for m in manifest["per_layer"] + manifest["end_to_end"]}
    for name in NEW:
        assert lists[name] == [CELL]
    holding = {name for name, cells_ in lists.items()
               if cells_ and CELL in cells_}
    assert holding == set(NEW) | {
        "step_ms_p95", "dispatch_ms.train", "busy_flops_pct.train",
        "forward_ms.train", "backward_ms.train", "optimizer_ms.train",
        "attention_fwd_ms.train", "attention_bwd_ms.train",
        "programs_per_step.train", "step_enqueue_ms.train", "mfu_pct.train",
        "moe_ms.train", "moe_experts_roofline_pct.train",
        "rms_norm_ms.train"}
    for name in holding - set(NEW):
        assert lists[name][-1] == CELL, name
    assert CELL not in lists["attention_window_roofline_pct.train"]


# -- operation counts, by hand ----------------------------------------------

def test_operation_counts(doc, config):
    """The counts by hand: forward 9182.9e9 operations a sequence of 8192 at
    an even share of 2048 live rows in each of the five expert layers, a
    step 27.549e12; MFU % = samples_per_s x 13.984."""
    traffic = {"batch": 1, "seq": L}
    d, heads = 2048, 32
    pairs = L * (L + 1) // 2
    projections = 2 * L * (d * 1536 + 1536 * heads * 192 + d * 576
                           + 512 * heads * 256 + heads * 128 * d)
    attention = 2 * pairs * heads * (192 + 128)
    dense = 3 * 2 * L * d * 7168
    router = 2 * L * d * 256
    shared = 3 * 2 * L * d * 768
    experts = 2048 * 3 * 2 * d * 768
    head = 2 * L * d * 16160
    eh_proj = 2 * L * 2 * d * d
    assert config.attention_flops(doc, L, "latent_attention") == attention
    assert config.expert_flops(doc, 2048) == experts
    assert (projections, attention, dense, router, shared, experts, head,
            eh_proj) == (
        pytest.approx(431.6e9, rel=1e-3), pytest.approx(687.3e9, rel=1e-3),
        pytest.approx(721.5e9, rel=1e-3), pytest.approx(8.6e9, rel=1e-2),
        pytest.approx(77.3e9, rel=1e-3), pytest.approx(19.3e9, rel=1e-2),
        pytest.approx(542.2e9, rel=1e-3), pytest.approx(137.4e9, rel=1e-3))
    expert_layer = projections + attention + router + shared + experts
    assert expert_layer == pytest.approx(1224.1e9, rel=1e-3)   # the MTP's
    forward = (projections + attention + dense + 4 * expert_layer + head
               + eh_proj + expert_layer + head)
    assert forward == pytest.approx(9182.9e9, rel=1e-4)
    assert config.flops_per_sample(doc, traffic) == 3 * forward
    assert 3 * forward == pytest.approx(27.549e12, rel=1e-4)
    assert 100 * 3 * forward / 197e12 == pytest.approx(13.984, rel=1e-4)
    # the experts' term follows the five counts the program made
    more = config.flops_per_sample(doc, traffic, [2048] * 4 + [4096])
    assert more - 3 * forward == pytest.approx(
        3 * config.expert_flops(doc, 2048))
    with pytest.raises(StopIteration):
        config.flops_per_sample(doc, traffic, [2048] * 4)
    assert config.expert_bytes(doc, 2048) == 2 * (
        8 * 3 * d * 768 + 2048 * 2 * d)


def test_ideal_seconds_of_the_accepted_kernels(doc, config):
    """benchmark/traffic/train_steps_ref.py's `ideal_seconds`, unedited: the
    experts' products by the five expert layers' live rows (the MTP block's
    among them: `moe/experts` holds its time too); attention by the five
    layers of `layer_types`, which leaves the MTP block's out, so the cell
    is not on `attention_window_roofline_pct.train`."""
    kind = _module("traffic", "train_steps_ref")
    bench = types.SimpleNamespace(config=doc, traffic={"batch": 1, "seq": L},
                                  device_kind="TPU v5 lite")
    got = kind.ideal_seconds(bench, config, [2048] * 5)
    by_bytes = 3 * 2 * (8 * 3 * 2048 * 768 + 2048 * 2 * 2048) / 819e9
    assert by_bytes > 3 * 2048 * 6 * 2048 * 768 / 197e12
    assert got["moe_experts"] == pytest.approx(5 * by_bytes)
    assert got["attention"] == pytest.approx(
        5 * 3 * 2 * (L * (L + 1) // 2) * 32 * 320 / 197e12)


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
    layer = ["moe_lm_0", "moe_lm_cell_1", "latent_attention_cell_1"]
    mtp = ["moe_lm_0", "mtp_0", "moe_lm_cell_5", "latent_attention_cell_5"]
    events = [
        ("fusion.1", layer + ["latent_attention", "q_down", "dense_2"],
         "forward", 2e6),
        ("fusion.2", layer + ["latent_attention", "rope"], "backward", 1e6),
        ("flash_attention_bwd.1", layer + ["latent_attention", "attention",
                                           "flash_attention_bwd"],
         "backward", 8e6),
        ("fusion.3", layer + ["dense_5"], "forward", 50e6),
        ("fusion.4", mtp + ["latent_attention", "kv_up"], "forward", 3e6),
        ("fusion.5", mtp + ["dense_30"], "backward", 5e6),
        ("fusion.6", ["moe_lm_0", "mtp_0", "dense_31"], "forward", 6e6),
        ("fusion.7", ["moe_lm_0", "mtp_0", "embedding_0"], "backward", 2e6),
        ("fusion.8", ["moe_lm_0", "dense_29"], "forward", 7e6),
        ("fusion.9", ["loss", "cross_entropy"], "forward", 1e6),
    ]
    bench = _traced(doc, events)
    assert _module("layers", NEW[0]).read(bench) == pytest.approx(7.0)
    assert _module("layers", NEW[1]).read(bench) == pytest.approx(8.0)


@pytest.mark.parametrize("metric", NEW)
def test_readers_read_nothing_where_there_is_nothing(doc, metric):
    """No trace; a program without the scope (the parent commit's, or
    another configuration's): None, no error."""
    read = _module("layers", metric).read
    assert read(types.SimpleNamespace(trace_summary=None, outcome={})) is None
    other = [("fusion.1", ["net", "cell_1", "moe", "experts"], "forward",
              5e6),
             ("fusion.2", ["net", "cell_1", "attention"], "forward", 5e6),
             ("fusion.3", ["net", "mtpx_1", "dense_3"], "forward", 5e6)]
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


def _halves_rotated(config):
    """The rotation pairs channel j with j + 32 (HF's rotate-half) where
    the configuration pairs 2j with 2j + 1, in every latent layer."""
    def net(doc, seed):
        model = config.net(doc, seed)
        for cell in model.layers + [model.mtp.block]:
            cell.attention._interleaved = False
        return model
    return {"net": net}


def _mtp_fed_its_own_token(config):
    """The MTP module embeds t_i where it should embed t_(i+1)."""
    def net(doc, seed):
        model = config.net(doc, seed)
        mtp = model.mtp
        mtp.forward = lambda h, tokens, embedding, head: head(mtp.norm(
            mtp.block(mtp.eh_proj(nd_concat(
                mtp.enorm(embedding(tokens)), mtp.hnorm(h))))))
        return model

    def nd_concat(a, b):
        from incubator_mxnet_tpu import nd
        return nd.concat(a, b, dim=2)
    return {"net": net}


def _query_norm_left_out(config):
    """Layer 2's query goes from its rank to its heads without the norm."""
    def net(doc, seed):
        model = config.net(doc, seed)
        model.layers[2].attention.q_norm.forward = lambda x: x
        return model
    return {"net": net}


@pytest.mark.parametrize("fault", [
    None, _halves_rotated, _mtp_fed_its_own_token, _query_norm_left_out],
    ids=["sound", "halves-rotated", "mtp-fed-its-own-token",
         "query-norm-left-out"])
def test_the_cell_rehearsed_end_to_end(config, fault):
    """The cell's whole control flow at the toy sizes: a sound bfloat16 step
    is inside every limit of the float32 reference, parameter by parameter
    (99 trained: the table; the dense layer's 12 and four expert layers' 16;
    the last norm and the head; the MTP module's 20), the five expert layers
    report their load at four assignments a token; each planted fault is
    refused by one limit at least."""
    planted = types.SimpleNamespace(**{**vars(config),
                                       **(fault(config) if fault else {})})
    out, notes = _rehearsal(planted)
    verdict = {name: pair for name, pair in notes["reference"].items()
               if name in ("loss1", "loss2", "logits", "gradient", "update")}
    refused = {name for name, (error, limit) in verdict.items()
               if not error < limit}
    assert bool(refused) == bool(fault), verdict
    assert out["correct"] is (notes["loss_fell"] and not fault)
    by_parameter = notes["reference"]["by_parameter"]
    assert len(by_parameter["gradient"]) == len(by_parameter["update"]) \
        == len(notes["reference"]["trained"]) == 99
    assert len(notes["moe"]["live_rows"]) == 5
    assert notes["moe"]["rows_held"] == 2 * 128 * 4
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["end_to_end"]) == {"samples_per_s", "step_ms_p95"}
