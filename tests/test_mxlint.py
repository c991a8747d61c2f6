"""mxtpu.mxlint — static analyzer + strict-mode runtime auditor.

Covers the PR 14 acceptance matrix: every rule fires on its bad fixture
and stays quiet on its good one (tests/fixtures/mxlint/),
suppression-with-reason is honored while a reasonless directive is
itself a finding, the counter-family tables have ONE home (the
trace_check drift test), the secondary-knob accessors resolve
call-site > env > default, the repo tree lints CLEAN end-to-end, and
the runtime auditor detects an injected host sync / a forced re-jit /
a donated-buffer read while the off path pays one predicate.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.mxlint import engine, families, rules, runtime
from incubator_mxnet_tpu.profiler.counters import (counters as
                                                   counters_snapshot,
                                                   reset_counters)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "mxlint")


def _load_tool(name):
    path = os.path.join(REPO, "tools", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fixture(name):
    with open(os.path.join(FIXTURES, name), encoding="utf-8") as f:
        return f.read()


def _lint_fixture(name, relpath, rule):
    """Run ONE rule over one fixture as if it lived at ``relpath``."""
    return engine.lint_sources([(relpath, _fixture(name))], [rule])


# the (bad fixture, good fixture, pretend package path, rule id) matrix
RULE_MATRIX = [
    ("raw_env_read_bad.py", "raw_env_read_good.py",
     "incubator_mxnet_tpu/somemod.py", "raw-env-read"),
    ("unregistered_counter_bad.py", "unregistered_counter_good.py",
     "incubator_mxnet_tpu/somemod.py", "unregistered-counter"),
    ("raise_in_never_raise_bad.py", "raise_in_never_raise_good.py",
     "incubator_mxnet_tpu/devicescope/ingest.py",
     "raise-in-never-raise"),
    ("unnormalized_device_kind_bad.py",
     "unnormalized_device_kind_good.py",
     "incubator_mxnet_tpu/somemod.py", "unnormalized-device-kind"),
    ("thread_shared_mutation_bad.py", "thread_shared_mutation_good.py",
     "incubator_mxnet_tpu/serving/batcher.py", "thread-shared-mutation"),
]


class TestRuleMatrix:
    @pytest.mark.parametrize("bad,good,relpath,rule_id", RULE_MATRIX,
                             ids=[m[3] for m in RULE_MATRIX])
    def test_bad_fires_good_quiet(self, bad, good, relpath, rule_id):
        rule = rules.rule_by_id(rule_id)
        found = _lint_fixture(bad, relpath, rule)
        assert found, f"{rule_id} must fire on {bad}"
        assert all(f.rule == rule_id for f in found)
        assert all(f.hint for f in found), "every finding carries a hint"
        rule = rules.rule_by_id(rule_id)     # fresh (stateful rules)
        quiet = _lint_fixture(good, relpath, rule)
        assert quiet == [], \
            f"{rule_id} must stay quiet on {good}: {quiet}"

    def test_raw_env_read_catches_every_spelling(self):
        found = _lint_fixture("raw_env_read_bad.py",
                              "incubator_mxnet_tpu/somemod.py",
                              rules.rule_by_id("raw-env-read"))
        # .get / os.getenv / bare getenv / subscript / membership /
        # dynamic-name helper
        assert len(found) == 6

    def test_raw_env_read_skips_driver_layer(self):
        # tools/ parse their own command lines — out of scope
        rule = rules.rule_by_id("raw-env-read")
        assert engine.lint_sources(
            [("tools/serve_load.py", _fixture("raw_env_read_bad.py"))],
            [rule]) == []

    def test_raw_env_read_exempts_settings_home(self):
        rule = rules.rule_by_id("raw-env-read")
        assert engine.lint_sources(
            [("incubator_mxnet_tpu/settings.py",
              _fixture("raw_env_read_bad.py"))], [rule]) == []

    def test_raw_env_read_allowlist_is_file_scoped(self):
        src = 'import os\nv = os.environ.get("MXTPU_HEALTHMON", "0")\n'
        rule = rules.rule_by_id("raw-env-read")
        ok = engine.lint_sources(
            [("incubator_mxnet_tpu/healthmon/__init__.py", src)], [rule])
        assert ok == []          # allowlisted THERE
        elsewhere = engine.lint_sources(
            [("incubator_mxnet_tpu/somemod.py", src)], [rule])
        assert len(elsewhere) == 1   # but only there

    def test_every_allowlist_entry_has_reason_and_files(self):
        for name, entry in rules.RAW_ENV_ALLOWLIST.items():
            assert entry["reason"].strip(), name
            assert entry["files"] is None or entry["files"], name

    def test_unregistered_counter_names_the_metric(self):
        found = _lint_fixture("unregistered_counter_bad.py",
                              "incubator_mxnet_tpu/somemod.py",
                              rules.rule_by_id("unregistered-counter"))
        msgs = " ".join(f.message for f in found)
        assert "healthmon/healthmon.not_a_real_metric" in msgs
        assert "memscope/memscope.invented_histogram" in msgs
        # kind mismatches: a gauge observed as histogram, a counter
        # written as gauge
        assert "perfscope/perfscope.mfu" in msgs
        assert "resilience/resilience.rollbacks" in msgs
        assert len(found) == 4

    def test_duplicated_table_pair(self):
        rule = rules.rule_by_id("duplicated-default-table")
        found = engine.lint_sources(
            [("incubator_mxnet_tpu/bench_tables.py",
              _fixture("duplicated_default_table_bad_a.py")),
             ("tools/sweep_tables.py",
              _fixture("duplicated_default_table_bad_b.py"))], [rule])
        assert len(found) == 1
        # the non-package copy is the flagged one; the package copy is
        # named as the canonical home
        assert found[0].path == "tools/sweep_tables.py"
        assert "DEFAULT_BATCH" in found[0].message
        rule = rules.rule_by_id("duplicated-default-table")
        assert engine.lint_sources(
            [("incubator_mxnet_tpu/a.py",
              _fixture("duplicated_default_table_good.py")),
             ("tools/b.py",
              _fixture("duplicated_default_table_bad_a.py"))],
            [rule]) == []


class TestSuppression:
    def test_with_reason_honored(self):
        rule = rules.rule_by_id("raw-env-read")
        assert engine.lint_sources(
            [("incubator_mxnet_tpu/somemod.py",
              _fixture("suppression_with_reason.py"))], [rule]) == []

    def test_without_reason_rejected(self):
        rule = rules.rule_by_id("raw-env-read")
        found = engine.lint_sources(
            [("incubator_mxnet_tpu/somemod.py",
              _fixture("suppression_without_reason.py"))], [rule])
        by_rule = {f.rule for f in found}
        # the directive suppresses NOTHING (the read still fires) and is
        # itself a finding
        assert "raw-env-read" in by_rule
        assert engine.SUPPRESSION_RULE_ID in by_rule

    def test_multiline_reason_covers_next_code_line(self):
        src = ("import os\n"
               "# mxlint: disable=raw-env-read -- reason line one\n"
               "# continues over a second comment line\n"
               'v = os.environ.get("MXTPU_K", "1")\n')
        assert engine.lint_sources(
            [("incubator_mxnet_tpu/m.py", src)],
            [rules.rule_by_id("raw-env-read")]) == []

    def test_disable_file_scope(self):
        src = ('"""mod."""\n'
               "# mxlint: disable-file=raw-env-read -- fixture-wide "
               "waiver\n"
               "import os\n"
               'a = os.environ.get("MXTPU_A", "1")\n'
               'b = os.environ.get("MXTPU_B", "1")\n')
        assert engine.lint_sources(
            [("incubator_mxnet_tpu/m.py", src)],
            [rules.rule_by_id("raw-env-read")]) == []

    def test_cross_file_rule_honors_suppression(self):
        # duplicated-default-table reports from finish(), AFTER the
        # engine's per-file filter — the directive must still work
        rule = rules.rule_by_id("duplicated-default-table")
        copy_src = _fixture("duplicated_default_table_bad_b.py").replace(
            "MY_BATCH_TABLE = {",
            "# mxlint: disable=duplicated-default-table -- deliberately "
            "independent copy\nMY_BATCH_TABLE = {")
        assert engine.lint_sources(
            [("incubator_mxnet_tpu/a.py",
              _fixture("duplicated_default_table_bad_a.py")),
             ("tools/b.py", copy_src)], [rule]) == []

    def test_suppression_only_covers_its_rule(self):
        src = ("import os\n"
               "# mxlint: disable=unregistered-counter -- wrong rule\n"
               'v = os.environ.get("MXTPU_K", "1")\n')
        found = engine.lint_sources(
            [("incubator_mxnet_tpu/m.py", src)],
            [rules.rule_by_id("raw-env-read")])
        assert [f.rule for f in found] == ["raw-env-read"]


class TestFamiliesSingleHome:
    def test_trace_check_derives_from_families(self):
        """THE drift test: trace_check's exported tables must BE the
        family-home tables (someone re-inlining a literal dict fails
        here)."""
        tc = _load_tool("trace_check")
        assert tc.HEALTHMON_FAMILIES == families.family_table("healthmon")
        assert tc.IO_TRAINLOOP_FAMILIES == families.family_table(
            "io", "trainloop")
        assert tc.SHARDING_FAMILIES == families.family_table("sharding")
        assert tc.PERFSCOPE_FAMILIES == families.family_table("perfscope")
        assert tc.COMMSCOPE_FAMILIES == families.family_table("commscope")
        assert tc.DEVICESCOPE_FAMILIES == families.family_table(
            "devicescope")
        assert tc.SERVESCOPE_FAMILIES == families.family_table(
            "servescope")
        assert tc.RESILIENCE_FAMILIES == families.family_table(
            "resilience")
        assert tc.MEMSCOPE_FAMILIES == families.family_table("memscope")
        assert tc.MXLINT_FAMILIES == families.family_table("mxlint")

    def test_table_shape(self):
        for domain, table in families.FAMILY_TABLES.items():
            for full, kind in table.items():
                assert full.startswith(f"{domain}/{domain}."), full
                assert kind in ("counter", "gauge", "histogram"), full

    def test_mxlint_family_accepted_by_kind_checker(self):
        tc = _load_tool("trace_check")
        kinds = {k: v for k, v in families.family_table("mxlint").items()}
        assert tc.check_healthmon_kinds(kinds) == []
        bad = dict(kinds)
        bad["mxlint/mxlint.invented"] = "counter"
        assert tc.check_healthmon_kinds(bad)

    def test_known_metric_helpers(self):
        assert families.known_metric("healthmon/healthmon.nan_alerts")
        assert not families.known_metric("healthmon/healthmon.nope")
        assert families.known_metric("bulk/anything")   # ungoverned
        assert families.metric_kind(
            "perfscope/perfscope.device_step_ms") == "histogram"


class TestEnvAccessors:
    def setup_method(self):
        for k in ("MXTPU_T_INT", "MXTPU_T_FLAG", "MXTPU_T_STR"):
            os.environ.pop(k, None)

    teardown_method = setup_method

    def test_precedence_call_site_beats_env(self):
        from incubator_mxnet_tpu import settings
        os.environ["MXTPU_T_INT"] = "5"
        assert settings.env_int("MXTPU_T_INT", 1) == 5
        assert settings.env_int("MXTPU_T_INT", 1, call_site=9) == 9
        assert settings.env_int("MXTPU_T_INT_UNSET", 7) == 7

    def test_empty_env_is_unset(self):
        from incubator_mxnet_tpu import settings
        os.environ["MXTPU_T_STR"] = "   "
        assert settings.env_str("MXTPU_T_STR", "d") == "d"
        assert settings.env_raw("MXTPU_T_STR") is None

    def test_int_garbage_raises_naming_the_knob(self):
        from incubator_mxnet_tpu import settings
        os.environ["MXTPU_T_INT"] = "banana"
        with pytest.raises(ValueError, match="MXTPU_T_INT"):
            settings.env_int("MXTPU_T_INT", 1)

    def test_int_garbage_degrades_for_never_raise_consumers(self):
        from incubator_mxnet_tpu import settings
        settings.reset_warned()
        os.environ["MXTPU_T_INT"] = "banana"
        with pytest.warns(UserWarning, match="MXTPU_T_INT"):
            assert settings.env_int("MXTPU_T_INT", 3,
                                 on_error="default") == 3

    def test_flag_spelling_table(self):
        from incubator_mxnet_tpu import settings
        for raw, want in (("1", True), ("true", True), ("on", True),
                          ("yes", True), ("0", False), ("false", False),
                          ("off", False), ("no", False)):
            os.environ["MXTPU_T_FLAG"] = raw
            assert settings.env_flag("MXTPU_T_FLAG", not want) is want, raw

    def test_flag_garbage_warns_and_defaults(self):
        from incubator_mxnet_tpu import settings
        settings.reset_warned()
        os.environ["MXTPU_T_FLAG"] = "maybe"
        with pytest.warns(UserWarning, match="MXTPU_T_FLAG"):
            assert settings.env_flag("MXTPU_T_FLAG", True) is True

    def test_pallas_switch_rides_the_settings_home(self):
        """ops/pallas.enabled() decides from settings.resolve("pallas"),
        not from a raw env read of its own."""
        from incubator_mxnet_tpu import settings
        from incubator_mxnet_tpu.ops import pallas
        for k in ("MXTPU_PALLAS", "MXTPU_NO_PALLAS",
                  "MXTPU_FORCE_PALLAS"):
            os.environ.pop(k, None)
        try:
            assert pallas.enabled() is False       # cpu default: auto
            os.environ["MXTPU_PALLAS"] = "force"
            assert settings.resolve("pallas")[0] == "force"
            assert pallas.enabled() is True
            os.environ["MXTPU_PALLAS"] = "0"
            assert pallas.enabled() is False
        finally:
            os.environ.pop("MXTPU_PALLAS", None)


class TestTreeClean:
    def test_repo_lints_clean_end_to_end(self):
        """The acceptance gate, as a tier-1 test: tools/mxlint.py
        --check over the real tree finds nothing."""
        cli = _load_tool("mxlint")
        findings, _ = cli.run_lint()
        assert findings == [], "\n".join(
            f.render(root=REPO) for f in findings)

    def test_lint_tree_on_package_dir_keeps_rule_scope(self, tmp_path):
        # linting the package dir DIRECTLY (commonpath strips the
        # prefix) must still put files in raw-env-read's jurisdiction
        import incubator_mxnet_tpu.mxlint as mxl
        pkg = tmp_path / "incubator_mxnet_tpu"
        pkg.mkdir()
        (pkg / "victim.py").write_text(
            'import os\nv = os.environ.get("MXTPU_FOO")\n')
        found = mxl.lint_tree([str(pkg)])
        assert [f.rule for f in found] == ["raw-env-read"]

    def test_cli_check_exit_codes(self, tmp_path):
        cli = _load_tool("mxlint")
        assert cli.main(["--check"]) == 0
        bad = tmp_path / "incubator_mxnet_tpu" / "m.py"
        bad.parent.mkdir()
        bad.write_text('import os\nv = os.environ.get("MXTPU_X", "")\n')
        assert cli.main(["--check", str(tmp_path)]) == 1

    def test_cli_errors_on_nonexistent_path(self, tmp_path):
        # a typo'd gate invocation must FAIL, never report a clean
        # empty lint set
        cli = _load_tool("mxlint")
        assert cli.main(["--check", str(tmp_path / "nope")]) == 2

    def test_allowlist_and_scopes_are_component_anchored(self):
        src = 'import os\nv = os.environ.get("MXTPU_HEALTHMON", "0")\n'
        rule = rules.rule_by_id("raw-env-read")
        # a suffix-colliding module must NOT inherit healthmon's waiver
        hit = engine.lint_sources(
            [("incubator_mxnet_tpu/myhealthmon/__init__.py", src)],
            [rule])
        assert len(hit) == 1
        # nor may a fake mxlint-suffixed path escape the rule wholesale
        hit2 = engine.lint_sources(
            [("incubator_mxnet_tpu/foo_mxlint/rules.py",
              'import os\nv = os.environ.get("MXTPU_X", "")\n')],
            [rules.rule_by_id("raw-env-read")])
        assert len(hit2) == 1

    def test_list_rules_covers_every_rule(self, capsys):
        cli = _load_tool("mxlint")
        assert cli.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in rules.RULES:
            assert rid in out

    def test_json_output(self, tmp_path, capsys):
        cli = _load_tool("mxlint")
        bad = tmp_path / "incubator_mxnet_tpu" / "m.py"
        bad.parent.mkdir()
        bad.write_text('import os\nv = os.environ.get("MXTPU_X", "")\n')
        assert cli.main(["--check", "--json", str(tmp_path)]) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["count"] == 1
        assert doc["findings"][0]["rule"] == "raw-env-read"

    def test_mxdiag_lint_renders_report(self, tmp_path):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "mxdiag.py"),
             "lint", os.path.join(FIXTURES, "raw_env_read_bad.py")],
            capture_output=True, text=True)
        # fixtures lack the package prefix, so raw-env-read stays
        # quiet — but the report must render and exit clean
        assert "mxlint findings" in out.stdout


class TestRuntimeAuditor:
    def setup_method(self):
        runtime.disable()
        reset_counters()

    def teardown_method(self):
        runtime.disable()
        reset_counters()

    def _counters(self):
        return counters_snapshot()

    def test_injected_host_sync_fires_detection(self):
        """An NDArray materialization inside a guarded dispatch is a
        counted host-sync trip (the CPU-provable channel of the
        transfer-guard detector) — and the dispatch still completes."""
        aud = runtime.enable()
        x = nd.ones((4, 4))

        def leaky_step():
            return float(x.asnumpy().sum())      # injected host sync

        v = aud.guarded(leaky_step)
        assert v == 16.0                          # detection, not death
        c = self._counters()
        assert c["mxlint/mxlint.transfer_guard_trips"] == 1
        assert c["mxlint/mxlint.guarded_dispatches"] == 1

    def test_sync_outside_guard_not_counted(self):
        runtime.enable()
        x = nd.ones((2,))
        x.asnumpy()                               # legit boundary fetch
        assert self._counters().get(
            "mxlint/mxlint.transfer_guard_trips", 0) == 0

    def test_allowed_sync_counted_separately(self):
        aud = runtime.enable()
        x = nd.ones((2,))

        def step():
            with runtime.allowed_sync("boundary barrier"):
                x.asnumpy()
            return 1

        assert aud.guarded(step) == 1
        c = self._counters()
        assert c["mxlint/mxlint.transfer_guard_trips"] == 0
        assert c["mxlint/mxlint.allowed_syncs"] == 1

    def test_accelerator_guard_trip_counts_once_and_reraises(self):
        """On a real accelerator the jax guard raises mid-dispatch —
        the XLA execution already ran and may have donated its inputs,
        so there is NO side-effect-safe re-run: strict mode counts ONE
        trip and re-raises loudly (a re-run would double-apply the
        update — the CPU sentinel path is the detect-and-continue
        channel)."""
        aud = runtime.enable()
        calls = []

        def accelerator_like_step():
            calls.append(1)
            # what jax raises under transfer_guard("disallow")
            raise RuntimeError(
                "Disallowed device-to-host transfer: ...")

        with pytest.raises(RuntimeError, match="[Dd]isallowed"):
            aud.guarded(accelerator_like_step)
        assert len(calls) == 1                    # never re-run
        assert self._counters()[
            "mxlint/mxlint.transfer_guard_trips"] == 1

    def test_forced_rejit_fires_recompile_counter(self):
        """A perfscope capture of a known program name after warmup is
        a steady-state recompile: counted AND named."""
        aud = runtime.enable()
        aud.note_program("fused_step")            # warmup compile
        aud.mark_warmup_done()
        aud.note_program("fused_step")            # the storm
        aud.note_program("fused_step")
        aud.note_program("fresh_program")         # first sight: fine
        c = self._counters()
        assert c["mxlint/mxlint.recompiles"] == 2
        extra = runtime.bench_extra()
        assert extra["recompiles"] == 2
        assert extra["recompiled_programs"] == ["fused_step"]

    def test_recompile_hook_rides_record_program(self):
        """End-to-end through perfscope: record_program pushes into the
        armed auditor."""
        from incubator_mxnet_tpu.perfscope import cost
        runtime.enable()
        cost.record_program("prog_a", 1e9, 1e6)
        runtime.mark_warmup_done()
        cost.record_program("prog_a", 1e9, 1e6)
        assert self._counters()["mxlint/mxlint.recompiles"] == 1

    def test_donated_buffer_read_counted_and_reraised(self):
        import jax.numpy as jnp
        aud = runtime.enable()
        arr = jnp.ones((4,)) * 2

        def read_deleted():
            arr.delete()                          # stand-in for donation
            return float(arr[0])

        with pytest.raises(RuntimeError, match="[Dd]eleted"):
            aud.guarded(read_deleted)
        assert self._counters()[
            "mxlint/mxlint.donation_violations"] == 1

    def test_off_path_pays_one_predicate(self):
        """Strict off: no auditor, no mxlint counters, the ndarray/
        perfscope hooks are None (ONE predicate each)."""
        assert runtime.enabled() is False
        assert nd._STRICT_SYNC is None
        from incubator_mxnet_tpu.perfscope import cost
        assert cost._STRICT_HOOK is None
        x = nd.ones((8,))
        x.asnumpy()
        assert runtime.guarded(lambda: 41 + 1) == 42
        assert not [k for k in self._counters() if k.startswith("mxlint/")]

    def test_enable_installs_and_disable_removes_hooks(self):
        runtime.enable()
        from incubator_mxnet_tpu.perfscope import cost
        assert nd._STRICT_SYNC is not None
        assert cost._STRICT_HOOK is not None
        assert self._counters()["mxlint/mxlint.strict"] == 1
        runtime.disable()
        assert nd._STRICT_SYNC is None
        assert cost._STRICT_HOOK is None
        assert self._counters()["mxlint/mxlint.strict"] == 0

    def test_bench_extra_shapes_validate(self):
        tc = _load_tool("trace_check")
        assert runtime.bench_extra() == {"strict": False}
        assert tc.check_mxlint_extra({"strict": False}) == []
        aud = runtime.enable()
        x = nd.ones((2,))
        aud.guarded(lambda: x.asnumpy())          # one trip
        extra = runtime.bench_extra()
        assert extra["strict"] is True
        assert extra["findings"] == 1 == extra["transfer_guard_trips"]
        assert tc.check_mxlint_extra(extra) == []
        # findings gauge settles for the counters surface
        assert self._counters()["mxlint/mxlint.findings"] == 1

    def test_check_mxlint_extra_bad_shapes(self):
        tc = _load_tool("trace_check")
        assert tc.check_mxlint_extra(None) == []
        assert tc.check_mxlint_extra([]) != []
        assert tc.check_mxlint_extra({}) != []
        good = {"strict": True, "findings": 1,
                "transfer_guard_trips": 1, "allowed_syncs": 0,
                "recompiles": 0, "recompiled_programs": [],
                "donation_violations": 0, "guarded_dispatches": 5}
        assert tc.check_mxlint_extra(good) == []
        bad_sum = dict(good, findings=3)
        assert any("findings" in e
                   for e in tc.check_mxlint_extra(bad_sum))
        bad_named = dict(good, recompiled_programs=["x"])
        assert any("recompiled_programs" in e
                   for e in tc.check_mxlint_extra(bad_named))
        bad_neg = dict(good, recompiles=-1)
        assert tc.check_mxlint_extra(bad_neg) != []

    def test_strict_steady_loop_is_clean(self):
        """A real FusedTrainStep steady loop under the guard: zero
        trips, zero recompiles — the invariant the strict lenet smoke
        pins on the full bench path."""
        from incubator_mxnet_tpu import gluon
        from incubator_mxnet_tpu.parallel import FusedTrainStep
        net = gluon.nn.Dense(4)
        net.initialize()
        L = gluon.loss.L2Loss()
        opt = mx.optimizer.create("sgd", learning_rate=0.05)
        step = FusedTrainStep(net, L, opt)
        x = nd.ones((8, 6))
        y = nd.zeros((8, 4))
        float(step(x, y))                         # compile + warmup
        aud = runtime.enable()
        aud.mark_warmup_done()
        for _ in range(5):
            loss = aud.guarded(lambda: step(x, y))
        float(loss)                               # boundary: outside
        c = self._counters()
        assert c["mxlint/mxlint.transfer_guard_trips"] == 0
        assert c["mxlint/mxlint.recompiles"] == 0
        assert c["mxlint/mxlint.guarded_dispatches"] == 5
