"""mxtpu.autotune: the knob table's documented precedence (call-site >
BENCH_* > MXTPU_* > cached winner > default) with conflict warnings
pinned, the pallas spelling matrix, mesh-grammar parsing, the pruning
rules firing on the right gap taxonomy, budget exhaustion returning
best-so-far, cache hit skipping the search, corrupt/stale cache entries
rejected and counted, subprocess trial death as a counted skip (never a
crash), and the tooling satellites (trace_check AUTOTUNE_FAMILIES +
check_autotune_extra, perf_regress knob-diff context notes, mxdiag tune
rendering). Search logic runs against
DETERMINISTIC fake measurement fixtures — no real training."""
import importlib.util
import json
import os
import stat

import pytest

import incubator_mxnet_tpu as mx  # noqa: F401 — package init
from incubator_mxnet_tpu import profiler as prof
from incubator_mxnet_tpu.autotune import knobs, space
from incubator_mxnet_tpu.autotune import trial as trial_mod
from incubator_mxnet_tpu.autotune.cache import (TuningCache, SCHEMA,
                                                fingerprint)
from incubator_mxnet_tpu.autotune.knobs import KnobConfig
from incubator_mxnet_tpu.autotune.tuner import search
from incubator_mxnet_tpu.autotune.trial import (TrialResult,
                                                measurement_from_artifact,
                                                score, trial_env)


def _load_tool(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# every env spelling the knob table reads — cleared around each test so
# the suite's own environment can't leak into resolution
_KNOB_ENV_VARS = ("BENCH_LOOP_CHUNK", "MXTPU_LOOP_CHUNK", "BENCH_REMAT",
                  "MXTPU_REMAT", "BENCH_REMAT_POLICY",
                  "MXTPU_REMAT_POLICY", "BENCH_PREFETCH_DEPTH",
                  "MXTPU_PREFETCH_DEPTH", "BENCH_MESH", "MXTPU_MESH",
                  "BENCH_BATCH", "MXTPU_PALLAS", "MXTPU_NO_PALLAS",
                  "MXTPU_FORCE_PALLAS", "MXTPU_AUTOTUNE",
                  "MXTPU_AUTOTUNE_CACHE")


@pytest.fixture(autouse=True)
def _clean_knob_state(monkeypatch):
    for var in _KNOB_ENV_VARS:
        monkeypatch.delenv(var, raising=False)
    knobs.clear_cached_defaults()
    knobs.reset_warned()
    yield
    knobs.clear_cached_defaults()
    knobs.reset_warned()


def _counter(name):
    return prof.counters().get("autotune/" + name) or 0


def _meas(busy=None, step_ms=10.0, mfu=0.1, value=100.0, gaps=None,
          mfu_if_removed=None):
    return {"busy_fraction": busy, "step_ms": step_ms, "mfu": mfu,
            "value": value, "gaps": gaps,
            "mfu_if_removed": mfu_if_removed,
            "provenance": ("measured(profile)" if busy is not None
                           else "host_wall")}


GAPS_INPUT = {"input_starved_ms": 4.0, "dispatch_serialized_ms": 0.5,
              "host_gap_ms": 0.5}
GAPS_DISPATCH = {"input_starved_ms": 0.2, "dispatch_serialized_ms": 3.0,
                 "host_gap_ms": 2.0}


# ---------------------------------------------------------------------------
# KnobConfig: precedence, conflicts, spellings
# ---------------------------------------------------------------------------

class TestKnobPrecedence:
    def test_defaults_and_sources(self):
        cfg = KnobConfig.from_env()
        assert cfg.to_dict() == {"loop_chunk": 0, "remat": False,
                                 "remat_policy": None,
                                 "prefetch_depth": 2, "io_workers": 2,
                                 "pallas": "auto",
                                 "mesh": None, "batch": None}
        assert set(cfg.sources.values()) == {"default"}

    def test_call_site_beats_bench_env(self, monkeypatch):
        monkeypatch.setenv("BENCH_LOOP_CHUNK", "8")
        cfg = KnobConfig.from_env(loop_chunk=2)
        assert cfg.loop_chunk == 2
        assert cfg.sources["loop_chunk"] == "call_site"

    def test_bench_beats_mxtpu_with_conflict_warning(self, monkeypatch):
        monkeypatch.setenv("BENCH_LOOP_CHUNK", "8")
        monkeypatch.setenv("MXTPU_LOOP_CHUNK", "4")
        before = _counter("autotune.env_conflicts")
        with pytest.warns(UserWarning, match="BENCH_LOOP_CHUNK=8.*wins"):
            cfg = KnobConfig.from_env()
        assert cfg.loop_chunk == 8
        assert cfg.sources["loop_chunk"] == "BENCH_LOOP_CHUNK"
        assert _counter("autotune.env_conflicts") == before + 1
        # once per knob per process: the second resolve stays quiet
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error")
            KnobConfig.from_env()

    def test_agreeing_spellings_do_not_warn(self, monkeypatch):
        monkeypatch.setenv("BENCH_LOOP_CHUNK", "4")
        monkeypatch.setenv("MXTPU_LOOP_CHUNK", "4")
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error")
            cfg = KnobConfig.from_env()
        assert cfg.loop_chunk == 4

    def test_mxtpu_beats_cached(self, monkeypatch):
        monkeypatch.setenv("MXTPU_LOOP_CHUNK", "4")
        knobs.set_cached_defaults({"loop_chunk": 8})
        cfg = KnobConfig.from_env()
        assert cfg.loop_chunk == 4
        assert cfg.sources["loop_chunk"] == "MXTPU_LOOP_CHUNK"

    def test_cached_beats_default(self):
        knobs.set_cached_defaults({"loop_chunk": 8, "prefetch_depth": 4,
                                   "unknown_future_field": 1})
        cfg = KnobConfig.from_env()
        assert cfg.loop_chunk == 8
        assert cfg.prefetch_depth == 4
        assert cfg.sources["loop_chunk"] == "cached"
        # unknown keys from a future cache schema are ignored, not fatal
        assert "unknown_future_field" not in knobs.cached_defaults()

    def test_garbage_env_raises(self, monkeypatch):
        monkeypatch.setenv("BENCH_LOOP_CHUNK", "many")
        with pytest.raises(ValueError):
            KnobConfig.from_env()
        monkeypatch.setenv("BENCH_LOOP_CHUNK", "4")
        monkeypatch.setenv("BENCH_REMAT_POLICY", "sometimes")
        with pytest.raises(ValueError, match="remat_policy"):
            KnobConfig.from_env()

    def test_from_dict_unknown_field_raises(self):
        with pytest.raises(ValueError, match="unknown knob"):
            KnobConfig.from_dict({"loop_chunk": 2, "warp_drive": 9})

    def test_unparseable_loser_cannot_crash_a_valid_winner(
            self, monkeypatch):
        # a stale `export MXTPU_PREFETCH_DEPTH=bogus` in a shell profile
        # must not break a run whose valid BENCH_* spelling already won
        monkeypatch.setenv("BENCH_PREFETCH_DEPTH", "4")
        monkeypatch.setenv("MXTPU_PREFETCH_DEPTH", "bogus")
        with pytest.warns(UserWarning, match="ignoring unparseable"):
            cfg = KnobConfig.from_env()
        assert cfg.prefetch_depth == 4
        # with no winner set, the garbage var is the decider: still a
        # loud parse error naming the value, not a silent default
        monkeypatch.delenv("BENCH_PREFETCH_DEPTH")
        with pytest.raises(ValueError):
            KnobConfig.from_env()

    def test_zero_depth_and_batch_same_verdict_everywhere(
            self, monkeypatch):
        # env parse, dict construction, and the TrainLoop constructor
        # must agree: 0 is an error, never a silent unset/default
        monkeypatch.setenv("BENCH_PREFETCH_DEPTH", "0")
        with pytest.raises(ValueError, match="prefetch_depth"):
            KnobConfig.from_env()
        monkeypatch.delenv("BENCH_PREFETCH_DEPTH")
        with pytest.raises(ValueError, match="batch"):
            KnobConfig.from_dict({"batch": 0})
        with pytest.raises(ValueError, match="prefetch_depth"):
            KnobConfig(prefetch_depth=0)


class TestPallasSpellings:
    @pytest.mark.parametrize("env,want", [
        ({}, "auto"),
        ({"MXTPU_PALLAS": "0"}, "off"),
        ({"MXTPU_PALLAS": "off"}, "off"),
        ({"MXTPU_PALLAS": "1"}, "on"),
        ({"MXTPU_PALLAS": "force"}, "force"),
        ({"MXTPU_NO_PALLAS": "1"}, "off"),
        ({"MXTPU_FORCE_PALLAS": "1"}, "force"),
    ])
    def test_spelling_matrix(self, monkeypatch, env, want):
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        assert KnobConfig.from_env().pallas == want

    def test_conflict_off_wins_and_warns(self, monkeypatch):
        # mirrors ops/pallas.enabled()'s if-order: the off spelling wins
        # over force — the knob table must DESCRIBE dispatch, not
        # contradict it
        monkeypatch.setenv("MXTPU_PALLAS", "force")
        monkeypatch.setenv("MXTPU_NO_PALLAS", "1")
        with pytest.warns(UserWarning, match="pallas"):
            cfg = KnobConfig.from_env()
        assert cfg.pallas == "off"
        from incubator_mxnet_tpu.ops import pallas as pallas_mod
        assert pallas_mod.enabled() is False

    def test_to_env_round_trip(self, monkeypatch):
        cfg = KnobConfig(loop_chunk=8, remat=True, remat_policy="dots",
                         prefetch_depth=4, pallas="off", mesh="dp2mp2",
                         batch=64)
        for k, v in cfg.to_env().items():
            monkeypatch.setenv(k, v)
        assert KnobConfig.from_env() == cfg


class TestMeshGrammar:
    def test_valid_specs(self):
        assert knobs.parse_mesh("dp4") == ("dp", {"dp": 4})
        assert knobs.parse_mesh("fsdp4") == ("fsdp", {"dp": 4})
        mode, axes = knobs.parse_mesh("dp2mp2")
        assert mode == "auto" and axes == {"dp": 2, "mp": 2}
        assert knobs.parse_mesh("") == (None, {})

    def test_bad_grammar_raises(self):
        with pytest.raises(ValueError, match="axis-size tokens"):
            knobs.parse_mesh("dp4x")
        with pytest.raises(ValueError, match="more than once"):
            knobs.parse_mesh("dp2dp2")
        with pytest.raises(ValueError, match="model axis"):
            knobs.parse_mesh("fsdp2mp2")


# ---------------------------------------------------------------------------
# consumer resolution: TrainLoop / Trainer ride the same table
# ---------------------------------------------------------------------------

class TestConsumerResolution:
    def test_resolve_chunk_layers(self, monkeypatch):
        from incubator_mxnet_tpu.trainloop import resolve_chunk
        assert resolve_chunk() == 4                      # default
        knobs.set_cached_defaults({"loop_chunk": 8})
        assert resolve_chunk() == 8                      # cached winner
        monkeypatch.setenv("MXTPU_LOOP_CHUNK", "6")
        assert resolve_chunk() == 6                      # MXTPU beats it
        monkeypatch.setenv("BENCH_LOOP_CHUNK", "2")
        assert resolve_chunk() == 2                      # BENCH beats it
        assert resolve_chunk(explicit=3) == 3            # arg beats all

    def test_trainer_loop_chunk_through_knobs(self, monkeypatch):
        from incubator_mxnet_tpu import gluon
        net = gluon.nn.Dense(2, in_units=3)
        net.initialize()
        monkeypatch.setenv("BENCH_LOOP_CHUNK", "5")
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        assert tr.loop_chunk == 5
        tr2 = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, loop_chunk=2)
        assert tr2.loop_chunk == 2


# ---------------------------------------------------------------------------
# space: pruning rules + candidate generation
# ---------------------------------------------------------------------------

class TestPruning:
    def test_input_starved_prunes_remat_not_prefetch(self):
        plan = space.prune_plan(_meas(busy=0.5, gaps=GAPS_INPUT))
        assert plan["diagnosis"] == "input_starved"
        assert plan["allowed"][0] == "prefetch_depth"
        assert "remat_policy" in plan["pruned"]
        assert "pallas" in plan["pruned"]
        assert "prefetch_depth" not in plan["pruned"]

    def test_dispatch_bound_prefers_loop_chunk(self):
        plan = space.prune_plan(_meas(busy=0.41, gaps=GAPS_DISPATCH))
        assert plan["diagnosis"] == "dispatch_bound"
        assert plan["allowed"][0] == "loop_chunk"
        assert "remat_policy" in plan["pruned"]

    def test_device_bound_prunes_dispatch_knobs(self):
        plan = space.prune_plan(_meas(
            busy=0.93, step_ms=10.0,
            gaps={"input_starved_ms": 0.1, "dispatch_serialized_ms": 0.2,
                  "host_gap_ms": 0.1}))
        assert plan["diagnosis"] == "device_bound"
        assert "loop_chunk" in plan["pruned"]
        assert "prefetch_depth" in plan["pruned"]
        assert "pallas" in plan["allowed"]
        assert "remat_policy" in plan["allowed"]

    def test_no_measurement_prunes_nothing_core(self):
        plan = space.prune_plan(None)
        assert plan["diagnosis"] == "unknown"
        for knob in ("loop_chunk", "prefetch_depth", "remat_policy",
                     "pallas"):
            assert knob in plan["allowed"]

    def test_mesh_needs_counterfactual_and_candidates(self):
        m = _meas(busy=0.5, gaps=GAPS_DISPATCH, mfu=0.10,
                  mfu_if_removed={"collective": 0.12})
        # candidates supplied + 20% promised gain -> explored
        plan = space.prune_plan(m, mesh_candidates=("dp4",))
        assert "mesh" in plan["allowed"]
        # weak counterfactual -> pruned even with candidates
        m2 = _meas(busy=0.5, gaps=GAPS_DISPATCH, mfu=0.10,
                   mfu_if_removed={"collective": 0.101})
        plan2 = space.prune_plan(m2, mesh_candidates=("dp4",))
        assert "mesh" in plan2["pruned"]
        # no candidates -> pruned regardless of the counterfactual
        plan3 = space.prune_plan(m)
        assert "mesh" in plan3["pruned"]

    def test_candidates_are_single_coordinate_moves(self):
        base = KnobConfig()
        plan = space.prune_plan(_meas(busy=0.41, gaps=GAPS_DISPATCH))
        cands = space.candidates(base, plan)
        assert cands, "dispatch-bound must propose moves"
        base_d = base.to_dict()
        for knob, value, cfg in cands:
            diff = {k for k, v in cfg.to_dict().items()
                    if v != base_d[k]}
            if knob == "remat_policy":
                assert diff <= {"remat", "remat_policy"}
            else:
                assert diff == {knob}
            assert cfg != base      # the incumbent is never re-proposed


# ---------------------------------------------------------------------------
# trial: measurement extraction, scoring, subprocess isolation
# ---------------------------------------------------------------------------

class TestTrial:
    def test_measurement_from_artifact(self):
        doc = {"value": 123.0, "extra": {
            "mfu": 0.07,
            "devicescope": {"busy_fraction": 0.41,
                            "gaps": {"taxonomy": GAPS_DISPATCH}},
            "perfscope": {"decomposition": {
                "step_ms": 9.5,
                "mfu_if_removed": {"collective": 0.08}}}}}
        m = measurement_from_artifact(doc)
        assert m["busy_fraction"] == 0.41
        assert m["gaps"] == GAPS_DISPATCH
        assert m["step_ms"] == 9.5
        assert m["value"] == 123.0
        assert m["provenance"] == "measured(profile)"

    def test_no_window_degrades_to_host_wall(self):
        m = measurement_from_artifact({"value": 50.0, "extra": {}})
        assert m["busy_fraction"] is None
        assert m["provenance"] == "host_wall"

    def test_score_ordering(self):
        measured_low = _meas(busy=0.40, value=500.0)
        measured_high = _meas(busy=0.70, value=100.0)
        unmeasured_fast = _meas(busy=None, value=9999.0)
        assert score(measured_high) > score(measured_low)
        # any measured trial outranks an unmeasured one
        assert score(measured_low) > score(unmeasured_fast)
        # near-tie on busy defers to throughput (the remat guard)
        a = _meas(busy=0.701, value=100.0)
        b = _meas(busy=0.699, value=200.0)
        assert score(b) > score(a)

    def test_trial_env_scrubs_and_pins(self, monkeypatch):
        monkeypatch.setenv("BENCH_MODEL", "resnet50")
        monkeypatch.setenv("BENCH_STEPS", "999")
        monkeypatch.setenv("MXTPU_AUTOTUNE", "1")
        monkeypatch.setenv("MXTPU_PALLAS", "force")
        env = trial_env(KnobConfig(loop_chunk=8), model="lenet",
                        steps=8, measure=True)
        assert env["BENCH_MODEL"] == "lenet"       # scrubbed, re-pinned
        assert env["BENCH_STEPS"] == "8"
        assert env["MXTPU_AUTOTUNE"] == "0"        # no recursion
        assert "MXTPU_PALLAS" not in env           # config owns pallas
        assert env["BENCH_LOOP_CHUNK"] == "8"
        assert env["BENCH_DEVICESCOPE"] == "1"
        assert env["BENCH_K1_CONTROL"] == "0"

    def _stub(self, tmp_path, body):
        p = tmp_path / "stub_bench.py"
        p.write_text(body)
        os.chmod(p, os.stat(p).st_mode | stat.S_IEXEC)
        return str(p)

    def test_subprocess_death_is_counted_failure(self, tmp_path):
        stub = self._stub(tmp_path, "import sys; sys.exit(1)\n")
        r = trial_mod.run_trial(KnobConfig(), bench_path=stub, timeout=30)
        assert r.status == "failed"
        assert "no JSON" in r.error

    def test_subprocess_timeout_is_failure(self, tmp_path):
        stub = self._stub(tmp_path, "import time; time.sleep(60)\n")
        r = trial_mod.run_trial(KnobConfig(), bench_path=stub, timeout=1)
        assert r.status == "failed"
        assert "timed out" in r.error

    def test_env_failure_artifact_is_failure(self, tmp_path):
        stub = self._stub(tmp_path, (
            'print(\'{"metric": "m", "value": 0.0, '
            '"status": "env_failure", "error": "no backend"}\')\n'))
        r = trial_mod.run_trial(KnobConfig(), bench_path=stub, timeout=30)
        assert r.status == "failed"
        assert "no backend" in r.error

    def test_parent_that_holds_the_chip_cannot_run_a_trial(
            self, tmp_path, monkeypatch):
        """One process per chip: a parent that has opened a TPU backend
        keeps the chip from its trial's child — refused with that
        reason, as a counted failed trial, before anything is spawned."""
        stub = self._stub(tmp_path, "raise SystemExit('must not run')\n")
        from incubator_mxnet_tpu import context
        monkeypatch.setattr(context, "holds_accelerator", lambda: True)
        r = trial_mod.run_trial(KnobConfig(), bench_path=stub, timeout=30)
        assert r.status == "failed"
        assert "one process at a time" in r.error

    def test_ok_stub_yields_measurement(self, tmp_path):
        doc = {"metric": "m", "value": 200.0, "unit": "img/s",
               "extra": {"mfu": 0.1,
                         "devicescope": {"busy_fraction": 0.66}}}
        stub = self._stub(tmp_path,
                          f"print('noise')\nprint('{json.dumps(doc)}')\n")
        r = trial_mod.run_trial(KnobConfig(loop_chunk=4),
                                bench_path=stub, timeout=30)
        assert r.ok
        assert r.measurement["busy_fraction"] == 0.66
        assert r.measurement["provenance"] == "measured(profile)"
        assert r.row()["config"]["loop_chunk"] == 4


# ---------------------------------------------------------------------------
# cache: trust rules
# ---------------------------------------------------------------------------

class TestCache:
    KEY = ("lenet|b64|float32", None, "cpu")

    def _store(self, cache):
        return cache.store(*self.KEY, winner=KnobConfig(loop_chunk=8),
                           score={"busy_fraction": 0.7,
                                  "provenance": "measured(profile)"},
                           default={"busy_fraction": 0.4},
                           diagnosis="dispatch_bound")

    def test_roundtrip(self, tmp_path):
        cache = TuningCache(str(tmp_path))
        self._store(cache)
        entry = cache.lookup(*self.KEY)
        assert entry["winner"]["loop_chunk"] == 8
        assert entry["score"]["busy_fraction"] == 0.7
        assert entry["diagnosis"] == "dispatch_bound"
        assert cache.rejects == 0

    def test_miss_is_none(self, tmp_path):
        assert TuningCache(str(tmp_path)).lookup(*self.KEY) is None

    def test_corrupt_entry_rejected_and_counted(self, tmp_path):
        cache = TuningCache(str(tmp_path))
        self._store(cache)
        with open(cache.path_for(*self.KEY), "w") as f:
            f.write("{torn write")
        before = _counter("autotune.cache_rejects")
        with pytest.warns(UserWarning, match="rejected"):
            assert cache.lookup(*self.KEY) is None
        assert cache.rejects == 1
        assert _counter("autotune.cache_rejects") == before + 1

    def test_schema_bump_rejected(self, tmp_path):
        cache = TuningCache(str(tmp_path))
        self._store(cache)
        path = cache.path_for(*self.KEY)
        doc = json.load(open(path))
        doc["schema"] = "mxtpu.autotune-cache/999"
        json.dump(doc, open(path, "w"))
        with pytest.warns(UserWarning, match="schema"):
            assert cache.lookup(*self.KEY) is None

    def test_device_kind_case_normalized(self, tmp_path):
        # jax reports 'TPU v4' raw; perfscope's peaks table lowercases
        # to 'tpu v4'. Both spellings must land on ONE cache key, or
        # sweep-ingested winners are never found by the driver's lookup
        cache = TuningCache(str(tmp_path))
        cache.store(self.KEY[0], None, "tpu v4",
                    winner=KnobConfig(loop_chunk=8),
                    score={"busy_fraction": 0.7})
        entry = cache.lookup(self.KEY[0], None, "TPU v4")
        assert entry is not None and entry["winner"]["loop_chunk"] == 8

    def test_device_kind_mismatch_rejected(self, tmp_path):
        # a winner tuned on CPU must never configure a TPU run: craft
        # the collision by copying the cpu entry onto the tpu key's path
        cache = TuningCache(str(tmp_path))
        entry = self._store(cache)
        tpu_key = (self.KEY[0], None, "TPU v5e")
        with open(cache.path_for(*tpu_key), "w") as f:
            json.dump(entry, f)
        with pytest.warns(UserWarning, match="device_kind mismatch"):
            assert cache.lookup(*tpu_key) is None

    def test_unparseable_winner_rejected(self, tmp_path):
        cache = TuningCache(str(tmp_path))
        self._store(cache)
        path = cache.path_for(*self.KEY)
        doc = json.load(open(path))
        doc["winner"] = {"warp_drive": 9}
        json.dump(doc, open(path, "w"))
        with pytest.warns(UserWarning, match="winner"):
            assert cache.lookup(*self.KEY) is None

    def test_ingest_picks_best(self, tmp_path):
        cache = TuningCache(str(tmp_path))
        rows = [TrialResult(KnobConfig(), "ok",
                            measurement=_meas(busy=0.4)),
                TrialResult(KnobConfig(loop_chunk=8), "ok",
                            measurement=_meas(busy=0.7)),
                TrialResult(KnobConfig(loop_chunk=4), "failed",
                            error="died"),
                TrialResult(None, "ok", measurement=_meas(busy=0.99))]
        entry = cache.ingest(rows, *self.KEY)
        assert entry["winner"]["loop_chunk"] == 8   # config-less &
        assert len(entry["trials"]) == 4            # failed rows skipped
        assert cache.lookup(*self.KEY)["winner"]["loop_chunk"] == 8

    def test_fingerprint_structural(self):
        from incubator_mxnet_tpu import gluon
        net = gluon.nn.Dense(4, in_units=3)
        net.initialize()
        fp1 = fingerprint(model=net, batch=32, dtype="float32")
        net2 = gluon.nn.Dense(4, in_units=3)
        net2.initialize()
        fp2 = fingerprint(model=net2, batch=32, dtype="float32")
        assert fp1 == fp2                  # same structure, same key
        net3 = gluon.nn.Dense(5, in_units=3)
        net3.initialize()
        assert fingerprint(model=net3, batch=32,
                           dtype="float32") != fp1


# ---------------------------------------------------------------------------
# search: deterministic fake measurement fixtures
# ---------------------------------------------------------------------------

def _fake_runner(busy_by_chunk=None, fail_configs=(), gaps=None,
                 calls=None):
    """A deterministic runner: busy fraction keyed by loop_chunk, gaps
    fixed, named configs fail."""
    busy_by_chunk = busy_by_chunk or {0: 0.41, 4: 0.60, 8: 0.75}
    gaps = gaps or GAPS_DISPATCH

    def run(cfg, knob=None, value=None):
        if calls is not None:
            calls.append(cfg)
        if cfg.describe() in fail_configs:
            return TrialResult(cfg, "failed", knob=knob, value=value,
                               error="injected trial death")
        busy = busy_by_chunk.get(cfg.loop_chunk, 0.5)
        m = _meas(busy=busy, step_ms=10.0, value=100 + busy * 100,
                  gaps=gaps)
        return TrialResult(cfg, "ok", measurement=m, knob=knob,
                           value=value)
    return run


class TestSearch:
    def test_budget_exhaustion_returns_best_so_far(self, tmp_path):
        calls = []
        r = search(model="lenet", runner=_fake_runner(calls=calls),
                   cache_dir=str(tmp_path), budget=2)
        assert len(calls) == 2                 # baseline + ONE move
        assert r.exhausted is True
        assert r.winner is not None            # best-so-far, not None
        assert r.to_extra()["budget_exhausted"] is True

    def test_pruning_restricts_moves_and_counts(self, tmp_path):
        before = _counter("autotune.trials_pruned")
        calls = []
        r = search(model="lenet", runner=_fake_runner(calls=calls),
                   cache_dir=str(tmp_path), budget=10)
        # dispatch-bound baseline: no remat/pallas move may ever run
        for cfg in calls:
            assert cfg.remat is False and cfg.pallas == "auto"
        assert "remat_policy" in r.pruned
        assert "pallas" in r.pruned
        assert _counter("autotune.trials_pruned") > before

    def test_winner_beats_or_ties_default_by_construction(self, tmp_path):
        r = search(model="lenet", runner=_fake_runner(),
                   cache_dir=str(tmp_path), budget=6)
        assert r.score["busy_fraction"] >= r.default["busy_fraction"]
        assert r.winner.loop_chunk == 8

    def test_cache_hit_skips_search(self, tmp_path):
        search(model="lenet", runner=_fake_runner(),
               cache_dir=str(tmp_path), budget=6)
        calls = []
        before_hits = _counter("autotune.cache_hits")
        r = search(model="lenet", runner=_fake_runner(calls=calls),
                   cache_dir=str(tmp_path), budget=6)
        assert r.cache_hit is True
        assert calls == []                     # runner never invoked
        assert r.trials_attempted == 0
        assert r.winner.loop_chunk == 8
        assert _counter("autotune.cache_hits") == before_hits + 1

    def test_different_key_misses(self, tmp_path):
        search(model="lenet", runner=_fake_runner(),
               cache_dir=str(tmp_path), budget=4)
        r = search(model="lenet", batch=256, runner=_fake_runner(),
                   cache_dir=str(tmp_path), budget=4)
        assert r.cache_hit is False

    def test_failed_trial_is_counted_skip(self, tmp_path):
        before = _counter("autotune.trials_failed")
        r = search(model="lenet",
                   runner=_fake_runner(fail_configs=("loop_chunk=4",)),
                   cache_dir=str(tmp_path), budget=6)
        assert r.trials_failed == 1
        assert _counter("autotune.trials_failed") == before + 1
        assert r.winner is not None            # search survived
        rows = r.to_extra()["trial_table"]
        assert any(row["status"] == "failed"
                   and "injected" in row["error"] for row in rows)

    def test_runner_exception_is_counted_skip(self, tmp_path):
        def exploding(cfg, knob=None, value=None):
            if cfg.loop_chunk == 4:
                raise RuntimeError("runner blew up")
            return _fake_runner()(cfg, knob=knob, value=value)
        r = search(model="lenet", runner=exploding,
                   cache_dir=str(tmp_path), budget=6)
        assert r.winner is not None
        assert r.trials_failed == 1

    def test_all_trials_fail_returns_error_result(self, tmp_path):
        def dead(cfg, knob=None, value=None):
            return TrialResult(cfg, "failed", knob=knob, value=value,
                               error="always dead")
        r = search(model="lenet", runner=dead, cache_dir=str(tmp_path),
                   budget=3)
        assert r.winner is None
        assert r.error == "every trial failed"
        # nothing cached: the next search re-runs
        r2 = search(model="lenet", runner=_fake_runner(),
                    cache_dir=str(tmp_path), budget=3)
        assert r2.cache_hit is False and r2.winner is not None

    def test_extra_validates_under_trace_check(self, tmp_path):
        tc = _load_tool("trace_check")
        r = search(model="lenet", runner=_fake_runner(),
                   cache_dir=str(tmp_path), budget=4)
        assert tc.check_autotune_extra(r.to_extra()) == []
        r_hit = search(model="lenet", runner=_fake_runner(),
                       cache_dir=str(tmp_path), budget=4)
        assert tc.check_autotune_extra(r_hit.to_extra()) == []

    def test_ensure_tuned_installs_cached_defaults(self, tmp_path,
                                                   monkeypatch):
        from incubator_mxnet_tpu import autotune as at
        monkeypatch.setattr(
            "incubator_mxnet_tpu.autotune.tuner.run_trial",
            lambda cfg, **kw: _fake_runner()(cfg, knob=kw.get("knob"),
                                             value=kw.get("value")))
        res = at.ensure_tuned(model="lenet", budget=4,
                              cache_dir=str(tmp_path))
        assert res.winner.loop_chunk == 8
        assert knobs.cached_defaults()["loop_chunk"] == 8
        # the installed winner feeds every consumer through the table
        from incubator_mxnet_tpu.trainloop import resolve_chunk
        assert resolve_chunk() == 8


# ---------------------------------------------------------------------------
# tooling satellites
# ---------------------------------------------------------------------------

class TestTraceCheck:
    def test_autotune_families_enforced(self):
        tc = _load_tool("trace_check")
        assert tc.check_healthmon_kinds(
            {"autotune/autotune.trials": "counter",
             "autotune/autotune.best_busy_fraction": "gauge"}) == []
        errs = tc.check_healthmon_kinds(
            {"autotune/autotune.made_up": "counter"})
        assert errs and "AUTOTUNE_FAMILIES" in errs[0]
        errs = tc.check_healthmon_kinds(
            {"autotune/autotune.trials": "gauge"})
        assert errs and "kind" in errs[0]

    def _good_extra(self):
        return {"enabled": True, "cache_hit": False, "trials": 3,
                "trials_failed": 0, "trials_pruned": 2, "budget": 6,
                "budget_exhausted": False, "diagnosis": "dispatch_bound",
                "winner": KnobConfig(loop_chunk=8).to_dict(),
                "resolved": KnobConfig(loop_chunk=8).to_dict(),
                "score": {"busy_fraction": 0.7, "step_ms": 9.0,
                          "mfu": 0.1, "value": 100.0,
                          "provenance": "measured(profile)"},
                "default": {"busy_fraction": 0.4, "step_ms": 12.0,
                            "mfu": 0.08, "value": 80.0,
                            "provenance": "measured(profile)"},
                "pruned": {"remat_policy": "dispatch-bound"},
                "trial_table": [
                    {"knob": None, "value": None, "status": "ok",
                     "config": KnobConfig().to_dict()},
                    {"knob": "loop_chunk", "value": 8, "status": "ok",
                     "config": KnobConfig(loop_chunk=8).to_dict()}],
                "cache": {"fingerprint": "lenet|b64", "mesh": None,
                          "device_kind": "cpu"},
                "error": None}

    def test_check_autotune_extra_matrix(self):
        tc = _load_tool("trace_check")
        assert tc.check_autotune_extra(None) == []
        assert tc.check_autotune_extra({"enabled": False}) == []
        assert tc.check_autotune_extra(self._good_extra()) == []
        # cache hit with nonzero trials violates the contract
        bad = dict(self._good_extra(), cache_hit=True)
        assert any("trials=0" in e
                   for e in tc.check_autotune_extra(bad))
        # unknown knob field in the winner
        bad = self._good_extra()
        bad["winner"] = dict(bad["winner"], warp_drive=9)
        assert any("unknown knob" in e
                   for e in tc.check_autotune_extra(bad))
        # provenance outside the closed taxonomy
        bad = self._good_extra()
        bad["score"] = dict(bad["score"], provenance="vibes")
        assert any("provenance" in e
                   for e in tc.check_autotune_extra(bad))
        # busy fraction outside [0, 1]
        bad = self._good_extra()
        bad["score"] = dict(bad["score"], busy_fraction=1.5)
        assert any("busy_fraction" in e
                   for e in tc.check_autotune_extra(bad))
        # a failed trial row must carry its reason
        bad = self._good_extra()
        bad["trial_table"] = [{"status": "failed", "config": None}]
        assert any("error" in e for e in tc.check_autotune_extra(bad))
        # enabled + error-free needs a winner
        bad = dict(self._good_extra(), winner=None)
        assert any("winner" in e for e in tc.check_autotune_extra(bad))

    def test_check_bench_json_accepts_autotune(self, tmp_path):
        tc = _load_tool("trace_check")
        doc = {"metric": "m", "value": 1.0, "unit": "u",
               "extra": {"mfu": 0.1, "autotune": self._good_extra()}}
        p = tmp_path / "BENCH_at.json"
        p.write_text(json.dumps(doc))
        assert tc.check_bench_json(str(p)) == []
        doc["extra"]["autotune"]["trials"] = -1
        p.write_text(json.dumps(doc))
        assert any("extra.autotune" in e
                   for e in tc.check_bench_json(str(p)))


class TestPerfRegress:
    def _artifact(self, tmp_path, name, value, knobs_dict):
        doc = {"metric": "m", "value": value, "unit": "img/s",
               "extra": {"mfu": 0.1,
                         "autotune": {"enabled": True, "cache_hit": True,
                                      "trials": 0,
                                      "resolved": knobs_dict}}}
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        return str(p)

    def test_knob_diff_is_context_note_not_verdict(self, tmp_path):
        pr = _load_tool("perf_regress")
        a = self._artifact(tmp_path, "a.json", 100.0,
                           KnobConfig().to_dict())
        b = self._artifact(tmp_path, "b.json", 100.0,
                           KnobConfig(loop_chunk=8).to_dict())
        ra, _ = pr.load_artifact(a)
        rb, _ = pr.load_artifact(b)
        regs, notes = pr.compare(ra, rb)
        assert regs == []                  # a knob diff alone never fails
        assert any("CONTEXT: knob config differs" in n
                   and "loop_chunk: 0 -> 8" in n for n in notes)

    def test_knob_diff_rides_alongside_real_regression(self, tmp_path):
        pr = _load_tool("perf_regress")
        a = self._artifact(tmp_path, "a.json", 100.0,
                           KnobConfig().to_dict())
        b = self._artifact(tmp_path, "b.json", 50.0,
                           KnobConfig(loop_chunk=8).to_dict())
        ra, _ = pr.load_artifact(a)
        rb, _ = pr.load_artifact(b)
        regs, notes = pr.compare(ra, rb)
        assert regs                        # the 50% drop still fires...
        assert any("CONTEXT: knob config differs" in n
                   for n in notes)         # ...WITH the context attached

    def test_one_sided_knobs_skipped(self, tmp_path):
        pr = _load_tool("perf_regress")
        a = self._artifact(tmp_path, "a.json", 100.0,
                           KnobConfig().to_dict())
        doc = {"metric": "m", "value": 100.0, "unit": "img/s",
               "extra": {"mfu": 0.1}}
        b = tmp_path / "b.json"
        b.write_text(json.dumps(doc))
        ra, _ = pr.load_artifact(a)
        rb, _ = pr.load_artifact(str(b))
        regs, notes = pr.compare(ra, rb)
        assert regs == []
        assert any("knob context skipped" in n for n in notes)


class TestMxdiagTune:
    def test_renders_search_and_hit_shapes(self, tmp_path, capsys):
        md = _load_tool("mxdiag")
        tc = _load_tool("trace_check")
        extra = TestTraceCheck()._good_extra()
        assert tc.check_autotune_extra(extra) == []
        doc = {"metric": "m", "value": 100.0, "unit": "img/s",
               "extra": {"model": "lenet", "batch": 64,
                         "dtype": "float32", "mfu": 0.1,
                         "autotune": extra}}
        assert md.print_tune(doc) == 0
        out = capsys.readouterr().out
        assert "MISS" in out and "<< WINNER" in out
        assert "dispatch-bound" in out     # pruning reason rendered
        assert "vs default" in out
        doc["extra"]["autotune"] = dict(extra, cache_hit=True, trials=0)
        assert md.print_tune(doc) == 0
        assert "HIT (0 trials" in capsys.readouterr().out

    def test_renders_disabled_and_missing(self, capsys):
        md = _load_tool("mxdiag")
        doc = {"metric": "m", "value": 1.0, "unit": "u",
               "extra": {"autotune": {"enabled": False}}}
        assert md.print_tune(doc) == 0
        assert "DISABLED" in capsys.readouterr().out
        assert md.print_tune({"metric": "m", "value": 1.0,
                              "extra": {}}) == 1

    def test_override_note(self, capsys):
        md = _load_tool("mxdiag")
        extra = TestTraceCheck()._good_extra()
        extra["resolved"] = dict(extra["winner"], loop_chunk=2)
        doc = {"metric": "m", "value": 1.0, "unit": "u",
               "extra": {"autotune": extra}}
        md.print_tune(doc)
        assert "OVERRODE" in capsys.readouterr().out
