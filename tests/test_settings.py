"""mxtpu.settings: the one home of the package's environment reads.

One resolution order (call-site argument > MXTPU_* > default), the
pallas master switch's three spellings, and the consumers (TrainLoop,
Trainer) resolving through it. Moved from tests/test_autotune.py when
the tuner left (PR 30): with only MXTPU_* names set, every surviving
setting resolves to the value it had there.
"""
from __future__ import annotations

import pytest

import incubator_mxnet_tpu as mx  # noqa: F401 — package init
from incubator_mxnet_tpu import settings

# every env spelling resolve() reads — cleared around each test so the
# suite's own environment can't leak into resolution
_ENV_VARS = ("MXTPU_LOOP_CHUNK", "MXTPU_PREFETCH_DEPTH",
             "MXTPU_IO_WORKERS", "MXTPU_PALLAS", "MXTPU_NO_PALLAS",
             "MXTPU_FORCE_PALLAS")


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    for var in _ENV_VARS:
        monkeypatch.delenv(var, raising=False)
    settings.reset_warned()
    yield
    settings.reset_warned()


def _resolved():
    return {f: settings.resolve(f) for f in settings.FIELDS}


class TestPrecedence:
    def test_defaults_and_sources(self):
        got = _resolved()
        assert {f: v for f, (v, _) in got.items()} == {
            "loop_chunk": 0, "prefetch_depth": 2, "io_workers": 2,
            "pallas": "auto"}
        assert {src for _, src in got.values()} == {"default"}

    def test_call_site_beats_env(self, monkeypatch):
        monkeypatch.setenv("MXTPU_LOOP_CHUNK", "8")
        assert settings.resolve("loop_chunk") == (8, "MXTPU_LOOP_CHUNK")
        assert settings.resolve("loop_chunk", 2) == (2, "call_site")

    def test_garbage_env_raises(self, monkeypatch):
        monkeypatch.setenv("MXTPU_LOOP_CHUNK", "many")
        with pytest.raises(ValueError):
            settings.resolve("loop_chunk")
        with pytest.raises(ValueError, match="unknown setting"):
            settings.resolve("warp_drive")

    def test_zero_depth_and_workers_same_verdict_everywhere(
            self, monkeypatch):
        # env parse and the TrainLoop constructor must agree: 0 is an
        # error, never a silent unset/default
        from incubator_mxnet_tpu import gluon
        from incubator_mxnet_tpu.trainloop import TrainLoop
        net = gluon.nn.Dense(2, in_units=3)
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        for field, env in (("prefetch_depth", "MXTPU_PREFETCH_DEPTH"),
                           ("io_workers", "MXTPU_IO_WORKERS")):
            monkeypatch.setenv(env, "0")
            with pytest.raises(ValueError, match=field):
                settings.resolve(field)
            monkeypatch.delenv(env)
            with pytest.raises(ValueError, match=field):
                TrainLoop(net, gluon.loss.L2Loss(), tr, **{field: 0})
        monkeypatch.setenv("MXTPU_LOOP_CHUNK", "0")        # stepwise
        assert settings.resolve("loop_chunk")[0] == 0


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
        assert settings.resolve("pallas")[0] == want

    def test_conflict_off_wins_and_warns(self, monkeypatch):
        # ops/pallas.enabled()'s order: the off spelling wins over force
        monkeypatch.setenv("MXTPU_PALLAS", "force")
        monkeypatch.setenv("MXTPU_NO_PALLAS", "1")
        with pytest.warns(UserWarning, match="pallas"):
            mode, src = settings.resolve("pallas")
        assert (mode, src) == ("off", "MXTPU_NO_PALLAS")
        from incubator_mxnet_tpu.ops import pallas as pallas_mod
        assert pallas_mod.enabled() is False
        # once per process: the second resolve stays quiet
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error")
            settings.resolve("pallas")


class TestConsumerResolution:
    def test_resolve_chunk_layers(self, monkeypatch):
        from incubator_mxnet_tpu.trainloop import resolve_chunk
        assert resolve_chunk() == 4                      # default
        monkeypatch.setenv("MXTPU_LOOP_CHUNK", "6")
        assert resolve_chunk() == 6                      # MXTPU_* beats it
        assert resolve_chunk(explicit=3) == 3            # arg beats all

    def test_trainer_loop_chunk_through_settings(self, monkeypatch):
        from incubator_mxnet_tpu import gluon
        net = gluon.nn.Dense(2, in_units=3)
        net.initialize()
        monkeypatch.setenv("MXTPU_LOOP_CHUNK", "5")
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        assert tr.loop_chunk == 5
        tr2 = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, loop_chunk=2)
        assert tr2.loop_chunk == 2
