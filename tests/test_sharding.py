"""mxtpu.sharding tier-1 (ISSUE 8): mesh registry + logical axis rules,
Block.shard annotations, resolution fallbacks, the sharded one-jit
executor's parity matrix (dp / dp×mp / fsdp vs the single-device
trainer, to a few float32 ulps), FSDP per-device memory reduction, and the subprocess CPU-mesh
matrix on 4 REAL fake devices (shard_matrix_worker.py)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.gluon.trainer import Trainer
from incubator_mxnet_tpu.parallel import (FusedTrainStep, fsdp, make_mesh,
                                          sharding)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test starts and ends without a process-global mesh."""
    sharding.clear_mesh()
    yield
    sharding.clear_mesh()


@pytest.fixture(autouse=True)
def _no_persistent_compile_cache():
    """Same hazard as tests/test_sharded_checkpoint.py: this jaxlib's CPU
    backend has mis-deserialized persistent-cache entries for donated
    sharded fused-step executables. Compile fresh in this module."""
    from jax._src import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _net():
    mx.random.seed(0)
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"),
            nn.Dense(16, activation="relu"),
            nn.Dense(4))
    net.initialize(init=mx.init.Xavier())
    return net


def _data(seed, batch=16):
    rng = np.random.RandomState(seed)
    return (nd.array(rng.randn(batch, 8).astype(np.float32)),
            nd.array(rng.randint(0, 4, batch)))


def assert_same_losses(got, ref, maxulp=8):
    """A sharded step is a DIFFERENT XLA program from the one-device
    step: the compiler owns the order of its reductions, so what it can
    promise is a few float32 ulps per loss, not the same bits (seen on
    jax 0.9.0: one ulp on the first loss)."""
    np.testing.assert_array_max_ulp(np.asarray(got, np.float32),
                                    np.asarray(ref, np.float32),
                                    maxulp=maxulp)


def _run(mode=None, mesh=None, n=4, annotate=None, momentum=0.0, **kw):
    net = _net()
    if annotate is not None:
        annotate(net)
    step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mx.optimizer.create("sgd", learning_rate=0.1,
                                              momentum=momentum),
                          mesh=mesh, sharding=mode, **kw)
    return [float(step(*_data(100 + i))) for i in range(n)], step


@pytest.fixture(scope="module")
def ref_losses():
    """Single-device reference, computed once for the parity matrix."""
    sharding.clear_mesh()
    losses, _ = _run()
    return losses


# ---------------------------------------------------------------------------
# make_mesh edge cases
# ---------------------------------------------------------------------------

class TestMakeMesh:
    def test_minus1_absorbs_remainder(self):
        mesh = make_mesh({"dp": -1, "mp": 2})
        assert mesh.shape == {"dp": len(jax.devices()) // 2, "mp": 2}

    def test_multiple_minus1_rejected(self):
        with pytest.raises(ValueError, match="more than one -1"):
            make_mesh({"dp": -1, "mp": -1})

    def test_oversubscribed_message_names_counts(self):
        with pytest.raises(ValueError, match=r"needs 16 devices.*have 8"):
            make_mesh({"dp": 4, "mp": 4})

    def test_minus1_nondividing_rejected(self):
        with pytest.raises(ValueError, match="do not divide evenly"):
            make_mesh({"dp": -1, "mp": 3})

    def test_zero_and_negative_sizes_rejected(self):
        with pytest.raises(ValueError, match="must be positive"):
            make_mesh({"dp": 0})
        with pytest.raises(ValueError, match="must be positive"):
            make_mesh({"dp": -2})

    def test_single_device_mesh_is_a_noop(self, ref_losses):
        """A 1-device mesh must train bit-identically to no mesh at all
        (laptop-to-pod: same construction code everywhere)."""
        mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
        losses, step = _run(mode="auto", mesh=mesh)
        assert losses == ref_losses
        assert all(p.data()._data.sharding.spec == P()
                   for p in step.params)


# ---------------------------------------------------------------------------
# mesh registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_set_get_clear(self):
        assert sharding.get_mesh() is None
        mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
        assert sharding.set_mesh(mesh) is mesh
        assert sharding.get_mesh() is mesh
        sharding.clear_mesh()
        assert sharding.get_mesh() is None

    def test_required_raises_without_mesh(self):
        with pytest.raises(RuntimeError, match="no global mesh"):
            sharding.get_mesh(required=True)

    def test_use_mesh_scopes_and_restores(self):
        outer = make_mesh({"dp": 2}, devices=jax.devices()[:2])
        inner = make_mesh({"dp": 4}, devices=jax.devices()[:4])
        sharding.set_mesh(outer)
        with sharding.use_mesh(inner):
            assert sharding.get_mesh() is inner
        assert sharding.get_mesh() is outer

    def test_axis_detection(self):
        mesh = make_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
        assert sharding.data_axis(mesh) == "dp"
        assert sharding.model_axis(mesh) == "mp"
        tp_mesh = make_mesh({"dp": 4, "tp": 2})
        assert sharding.model_axis(tp_mesh) == "tp"   # seed helper alias
        assert sharding.data_axis(make_mesh({"sp": 8})) is None


# ---------------------------------------------------------------------------
# logical axis rules + resolution
# ---------------------------------------------------------------------------

class TestRules:
    def test_mesh_axes_pass_through(self):
        mesh = make_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
        assert sharding.resolve_axis("mp", mesh) == "mp"
        assert sharding.resolve_axis(None, mesh) is None

    def test_logical_names_map_by_rule_priority(self):
        mp_mesh = make_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
        tp_mesh = make_mesh({"dp": 4, "tp": 2})
        assert sharding.resolve_axis("model", mp_mesh) == "mp"
        assert sharding.resolve_axis("model", tp_mesh) == "tp"
        assert sharding.resolve_axis("batch", mp_mesh) == "dp"

    def test_unknown_logical_replicates(self):
        mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
        assert sharding.resolve_axis("model", mesh) is None   # no mp/tp
        assert sharding.resolve_axis("garbage", mesh) is None

    def test_axis_rules_prepend_and_restore(self):
        mesh = make_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
        with sharding.axis_rules(("model", None)):
            assert sharding.resolve_axis("model", mesh) is None
            with sharding.axis_rules(("model", "dp")):
                assert sharding.resolve_axis("model", mesh) == "dp"
            assert sharding.resolve_axis("model", mesh) is None
        assert sharding.resolve_axis("model", mesh) == "mp"

    def test_axis_rules_validates_pairs(self):
        with pytest.raises(ValueError, match="2-tuples"):
            with sharding.axis_rules("model"):
                pass

    def test_resolve_spec_tuples_and_trailing_none(self):
        mesh = make_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
        assert sharding.resolve_spec(P(("dp", "mp"), None), mesh) \
            == P(("dp", "mp"))
        assert sharding.resolve_spec(P("vocab", None), mesh) == P("mp")
        assert sharding.resolve_spec(None, mesh) == P()

    def test_resolve_param_divisibility_fallback(self):
        mesh = make_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
        from incubator_mxnet_tpu.gluon.parameter import Parameter
        good = Parameter("w", shape=(8, 4))
        good._sharding = P("mp", None)
        assert sharding.resolve_param(good, mesh).spec == P("mp")
        odd = Parameter("w2", shape=(7, 4))          # 7 % 2 != 0
        odd._sharding = P("mp", None)
        from incubator_mxnet_tpu import profiler as prof
        before = prof.counters().get(
            "sharding/sharding.fallback_replicated", 0)
        assert sharding.resolve_param(odd, mesh).spec == P()
        assert prof.counters()["sharding/sharding.fallback_replicated"] \
            == before + 1


# ---------------------------------------------------------------------------
# Block.shard + auto_shard
# ---------------------------------------------------------------------------

class TestBlockShard:
    def test_spec_applies_to_matching_rank_recursively(self):
        net = _net()
        net.shard(P("model", None))
        for blk in net._children.values():
            assert blk.weight._sharding == P("model", None)
            assert blk.bias._sharding is None          # 1-D: untouched

    def test_by_name_kwargs(self):
        net = _net()
        dense = list(net._children.values())[0]
        dense.shard(weight=P(None, "mp"), bias=P())
        assert dense.weight._sharding == P(None, "mp")
        assert dense.bias._sharding == P()

    def test_none_clears_subtree(self):
        net = _net()
        net.shard(P("model", None))
        net.shard(None)
        assert all(p._sharding is None
                   for p in net.collect_params().values())

    def test_rejects_non_partitionspec(self):
        net = _net()
        with pytest.raises(TypeError, match="PartitionSpec"):
            net.shard(("model", None))
        with pytest.raises(TypeError, match="PartitionSpec"):
            net.shard(weight="mp")

    def test_unmatched_keyword_raises(self):
        """A typo'd keyword must not leave the model silently
        replicated while the user believes it is sharded."""
        dense = list(_net()._children.values())[0]
        with pytest.raises(ValueError, match="wieght"):
            dense.shard(wieght=P("model", None))

    def test_auto_shard_defaults(self):
        net = nn.HybridSequential()
        net.add(nn.Dense(16), nn.BatchNorm(), nn.Embedding(12, 8))
        sharding.auto_shard(net)
        dense, bn, emb = net._children.values()
        assert dense.weight._sharding == P("model", None)
        assert dense.bias._sharding is None
        assert emb.weight._sharding == P("model", None)
        assert bn.gamma._sharding is None and bn.beta._sharding is None

    def test_auto_shard_keeps_existing_annotations(self):
        net = nn.HybridSequential()
        net.add(nn.Dense(16))
        dense = list(net._children.values())[0]
        dense.weight._sharding = P(None, "mp")
        sharding.auto_shard(net)
        assert dense.weight._sharding == P(None, "mp")


# ---------------------------------------------------------------------------
# the sharded executor: parity matrix + layouts (in-process, 4 of
# the suite's 8 virtual devices)
# ---------------------------------------------------------------------------

class TestShardedExecutor:
    def test_dp4_parity(self, ref_losses):
        sharding.set_mesh(make_mesh({"dp": 4}, devices=jax.devices()[:4]))
        losses, step = _run(mode="dp")
        assert_same_losses(losses, ref_losses)
        assert step.mesh is sharding.get_mesh()   # registry pickup

    def test_2x2_auto_parity_and_mp_sharded(self, ref_losses):
        sharding.set_mesh(make_mesh({"dp": 2, "mp": 2},
                                    devices=jax.devices()[:4]))
        losses, step = _run(mode="auto")
        assert_same_losses(losses, ref_losses)
        # 'auto' resolves ephemerally: the net's own annotations stay
        # untouched, so a later 'dp' build is not silently model-sharded
        assert all(p._sharding is None for p in step.params)
        specs = {p.name: p.data()._data.sharding.spec for p in step.params}
        weights = {k: v for k, v in specs.items() if "weight" in k}
        biases = {k: v for k, v in specs.items() if "bias" in k}
        assert weights and all("mp" in str(s) for s in weights.values())
        assert all(s == P() for s in biases.values())
        # shard shapes: units dim really split in half on device 0
        w0 = next(p for p in step.params if "weight" in p.name)
        shard0 = next(iter(w0.data()._data.addressable_shards)).data
        assert shard0.shape[0] * 2 == w0.shape[0]

    def test_explicit_logical_annotation_parity(self, ref_losses):
        sharding.set_mesh(make_mesh({"dp": 2, "mp": 2},
                                    devices=jax.devices()[:4]))
        losses, step = _run(mode="dp",
                            annotate=lambda n: n.shard(P("model", None)))
        assert_same_losses(losses, ref_losses)
        assert any("mp" in str(p.data()._data.sharding.spec)
                   for p in step.params)

    def test_axis_rules_pin_replicated(self, ref_losses):
        sharding.set_mesh(make_mesh({"dp": 2, "mp": 2},
                                    devices=jax.devices()[:4]))
        with sharding.axis_rules(("model", None)):
            losses, step = _run(mode="auto")
        assert_same_losses(losses, ref_losses)
        assert all(p.data()._data.sharding.spec == P()
                   for p in step.params)

    def test_fsdp_parity_memory_and_states(self):
        """FSDP: same math up to the collective's reduction order (~1 ulp
        per step on XLA:CPU), params AND momentum sharded over dp, and
        per-device bytes reduced by ~the dp degree."""
        sharding.clear_mesh()
        ref, _ = _run(momentum=0.9)
        sharding.set_mesh(make_mesh({"dp": 4}, devices=jax.devices()[:4]))
        losses, step = _run(mode="fsdp", momentum=0.9)
        np.testing.assert_allclose(losses, ref, rtol=1e-5, atol=1e-6)
        specs = [p.data()._data.sharding.spec for p in step.params]
        assert any("dp" in str(s) for s in specs)
        state_specs = [getattr(s, "sharding", None).spec
                       for s in jax.tree_util.tree_leaves(step._states)]
        assert any("dp" in str(s) for s in state_specs)
        report = fsdp.memory_report(step)
        assert report["param_bytes_per_device"] \
            < report["param_bytes_logical"]
        assert report["reduction"] >= 2.0
        assert report["state_bytes_per_device"] > 0
        summ = sharding.summary()
        assert summ["fsdp"] and summ["params_data_sharded"] > 0

    def test_fsdp_honors_explicit_replicate_pin(self):
        """An explicit replicate annotation (shard(weight=P())) is the
        user saying "no per-step all-gathers for this one" — FSDP must
        not dp-shard it anyway (the every-mode annotation contract)."""
        sharding.set_mesh(make_mesh({"dp": 4}, devices=jax.devices()[:4]))

        def pin_first(net):
            list(net._children.values())[0].shard(weight=P())

        losses, step = _run(mode="fsdp", annotate=pin_first)
        pinned = next(p for p in step.params if p._sharding == P())
        assert pinned.data()._data.sharding.spec == P()
        # the rest still FSDP-shard
        assert any("dp" in str(p.data()._data.sharding.spec)
                   for p in step.params)

    def test_fsdp_shards_dissolved_annotations(self):
        """An auto_shard'ed net (P('model', None) annotations) on a
        dp-ONLY mesh: 'model' dissolves, and FSDP must still shard the
        weights over dp — a dissolved hint must not silently cost the
        mode its entire memory saving."""
        sharding.set_mesh(make_mesh({"dp": 4}, devices=jax.devices()[:4]))
        losses, step = _run(mode="fsdp", annotate=sharding.auto_shard)
        weights = [p for p in step.params if "weight" in p.name]
        assert weights and all(
            "dp" in str(p.data()._data.sharding.spec) for p in weights)

    def test_dissolved_annotation_counts_fallback(self):
        """'counted, never silent': an annotation whose axes don't exist
        on this mesh must tick sharding.fallback_replicated."""
        from incubator_mxnet_tpu import profiler as prof
        from incubator_mxnet_tpu.gluon.parameter import Parameter
        mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
        p = Parameter("w", shape=(8, 4))
        p._sharding = P("model", None)     # no mp/tp on this mesh
        before = prof.counters().get(
            "sharding/sharding.fallback_replicated", 0)
        assert sharding.resolve_param(p, mesh).spec == P()
        assert prof.counters()["sharding/sharding.fallback_replicated"] \
            == before + 1
        # an explicit pin is NOT a fallback — requested and delivered
        p2 = Parameter("w2", shape=(8, 4))
        p2._sharding = P()
        assert sharding.resolve_param(p2, mesh).spec == P()
        assert prof.counters()["sharding/sharding.fallback_replicated"] \
            == before + 1

    def test_mesh_gauges_zeroed_on_clear(self):
        from incubator_mxnet_tpu import profiler as prof
        sharding.set_mesh(make_mesh({"dp": 4}, devices=jax.devices()[:4]))
        assert prof.counters()["sharding/sharding.mesh_devices"] == 4
        sharding.clear_mesh()
        assert prof.counters()["sharding/sharding.mesh_devices"] == 0

    def test_fsdp_spec_edge_cases(self):
        mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
        assert fsdp.fsdp_spec((8, 3), mesh) == P("dp", None)
        assert fsdp.fsdp_spec((7, 3), mesh) is None     # 7 % 4
        assert fsdp.fsdp_spec((), mesh) is None         # scalar
        one = make_mesh({"dp": 1}, devices=jax.devices()[:1])
        assert fsdp.fsdp_spec((8,), one) is None        # dp degree 1

    def test_invalid_mode_rejected(self):
        with pytest.raises(ValueError, match="unknown sharding mode"):
            _run(mode="zap")

    def test_trainer_flag_and_env_plumb_through(self, monkeypatch):
        net = _net()
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.1}, sharding="fsdp")
        assert tr.sharding == "fsdp"
        sharding.set_mesh(make_mesh({"dp": 4}, devices=jax.devices()[:4]))
        step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
        assert step.sharding == "fsdp"
        assert step.shard_optimizer_states
        monkeypatch.setenv("MXTPU_SHARDING", "auto")
        tr2 = Trainer(_net().collect_params(), "sgd")
        assert tr2.sharding == "auto"
        monkeypatch.setenv("MXTPU_SHARDING", "bogus")
        with pytest.raises(ValueError, match="unknown sharding mode"):
            Trainer(_net().collect_params(), "sgd")

    def test_trainloop_sharded_chunk_parity(self, ref_losses):
        """The whole-loop executor under a mesh: one donated program per
        2-step chunk, dp-sharded stacked batches, constant lr — losses
        must equal the single-device sequential run to a few ulps."""
        from incubator_mxnet_tpu.trainloop import TrainLoop
        import jax.numpy as jnp
        sharding.set_mesh(make_mesh({"dp": 4}, devices=jax.devices()[:4]))
        net = _net()
        tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                     sharding="dp", loop_chunk=2)
        loop = TrainLoop(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
        out = []
        for c in range(2):
            xs = jnp.stack([_data(100 + 2 * c + i)[0]._data
                            for i in range(2)])
            ys = jnp.stack([_data(100 + 2 * c + i)[1]._data
                            for i in range(2)])
            out.extend(float(v) for v in loop.run_chunk(xs, ys).asnumpy())
        assert_same_losses(out, ref_losses)
        assert loop.step.mesh is sharding.get_mesh()


# ---------------------------------------------------------------------------
# integrations: kvstore mesh reuse, diagnostics per-device census,
# seed helpers over the registry
# ---------------------------------------------------------------------------

class TestIntegrations:
    def test_kvstore_reuses_registry_mesh(self):
        from incubator_mxnet_tpu.kvstore import _BucketedAllReduce
        from incubator_mxnet_tpu import profiler as prof
        devs = tuple(jax.devices())
        gm = sharding.set_mesh(make_mesh({"dp": -1}))
        before = prof.counters().get("mxtpu/kvstore.mesh_reuse", 0)
        mesh = _BucketedAllReduce._collective_mesh(devs)
        assert mesh is gm                 # IDENTITY reuse, not a copy
        assert prof.counters()["mxtpu/kvstore.mesh_reuse"] == before + 1
        # subset of the registry devices: falls back to a private mesh
        sub = _BucketedAllReduce._collective_mesh(devs[:4])
        assert prof.counters()["mxtpu/kvstore.mesh_reuse"] == before + 1
        assert sub.devices.shape == (4,) and sub.axis_names == ("kv",)
        # a multi-axis registry mesh can't flatten to the reduce's one
        # axis — private mesh, not counted
        sharding.set_mesh(make_mesh({"dp": 4, "mp": 2}))
        multi = _BucketedAllReduce._collective_mesh(devs)
        assert multi.axis_names == ("kv",)
        assert prof.counters()["mxtpu/kvstore.mesh_reuse"] == before + 1

    def test_kvstore_aggregation_rides_reused_mesh(self):
        """End to end: device aggregation with the registry mesh reused
        still sums correctly (the reduce must use the mesh's own axis
        name — 'dp' here — not a hardcoded 'kv')."""
        import jax.numpy as jnp
        gm = sharding.set_mesh(make_mesh({"dp": -1}))
        kv = mx.kv.create("dist_sync_device")
        devs = jax.devices()
        shards_np = [np.full((3, 5), i + 1.0, np.float32)
                     for i in range(len(devs))]
        shards = [nd.NDArray(jax.device_put(jnp.asarray(s), d))
                  for s, d in zip(shards_np, devs)]
        out = [nd.array(np.zeros((3, 5), np.float32))]
        kv.pushpull(["g0"], [shards], out=out)
        np.testing.assert_allclose(out[0].asnumpy(),
                                   np.sum(shards_np, axis=0))
        (_, mesh), = kv._allreduce._reduce_cache.values()
        assert mesh is gm                  # the reduce compiled ON it

    def test_reconcile_reports_per_device_bytes(self):
        import jax.numpy as jnp
        from incubator_mxnet_tpu.diagnostics import memory as dmem
        from jax.sharding import NamedSharding
        mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
        base = dmem.reconcile()["per_device_live_bytes"]
        big = jnp.zeros((1024, 256), jnp.float32)          # 1 MiB
        repl = jax.device_put(big, NamedSharding(mesh, P()))
        shrd = jax.device_put(big, NamedSharding(mesh, P("dp")))
        after = dmem.reconcile()["per_device_live_bytes"]
        d0 = str(jax.devices()[0])
        delta = after.get(d0, 0) - base.get(d0, 0)
        # replicated costs 1 MiB on device 0, the dp shard 1/4 MiB
        assert delta >= big.nbytes + big.nbytes // 4
        del repl, shrd

    def test_tensor_parallel_defaults_via_registry(self):
        from incubator_mxnet_tpu.parallel import column_parallel, row_parallel
        sharding.set_mesh(make_mesh({"dp": 4, "tp": 2}))
        d = nn.Dense(8, in_units=4)
        column_parallel(d)                       # axis=None → registry tp
        assert d.weight._sharding == P("tp", None)
        sharding.clear_mesh()
        d2 = nn.Dense(8, in_units=4)
        row_parallel(d2)                         # no mesh → logical name
        assert d2.weight._sharding == P(None, "model")

    def test_moe_resolve_shardings_via_registry(self):
        from incubator_mxnet_tpu.parallel import MoEFFN
        layer = MoEFFN(8, 16, 32)
        sharding.set_mesh(make_mesh({"ep": 8}))
        resolved = layer.resolve_shardings()
        assert resolved["w1"].spec == P("ep")
        assert resolved["gate_w"].spec == P()
        # an ep the expert count doesn't divide → replicated, not an error
        bad = MoEFFN(6, 16, 32)
        assert bad.resolve_shardings()["w1"].spec == P()
        sharding.clear_mesh()
        with pytest.raises(RuntimeError, match="no global mesh"):
            layer.resolve_shardings()


# ---------------------------------------------------------------------------
# the subprocess CPU-mesh matrix: 4 REAL fake devices per layout
# (what the in-process tests can't prove: the layouts on a genuine
# 4-device process, plus the FSDP checkpoint round trip — the
# migrated zero1 coverage lives in test_sharded_checkpoint.py)
# ---------------------------------------------------------------------------

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "shard_matrix_worker.py")


def _run_worker(layout, *extra):
    env = dict(os.environ)
    # the worker pins its own XLA_FLAGS/JAX_PLATFORMS before importing jax
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, _WORKER, layout, *extra],
                          capture_output=True, text=True, timeout=240,
                          env=env)
    assert proc.returncode == 0, \
        f"worker {layout} rc={proc.returncode}:\n{proc.stderr[-2000:]}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


class TestSubprocessMatrix:
    @pytest.fixture(scope="class")
    def matrix(self):
        return {layout: _run_worker(layout)
                for layout in ("single", "dp2mp2", "fsdp4")}

    def test_2x2_parity_with_single_device(self, matrix):
        assert matrix["dp2mp2"]["devices"] == 4
        assert_same_losses(
            [float.fromhex(h) for h in matrix["dp2mp2"]["losses_hex"]],
            [float.fromhex(h) for h in matrix["single"]["losses_hex"]])

    def test_2x2_weights_on_mp_with_halved_shards(self, matrix):
        specs = matrix["dp2mp2"]["specs"]
        shard0 = matrix["dp2mp2"]["shard0_shapes"]
        weights = [k for k in specs if "weight" in k]
        assert weights
        for k in weights:
            assert "mp" in specs[k], f"{k}: {specs[k]}"
        # dense_0: (32, 8) weight → (16, 8) per mp shard
        w0 = weights[0]
        assert shard0[w0][0] * 2 == 32

    def test_fsdp_parity_and_per_device_reduction(self, matrix):
        single, fs = matrix["single"], matrix["fsdp4"]
        np.testing.assert_allclose(fs["losses"], single["losses"],
                                   rtol=1e-5, atol=1e-6)
        rep = fs["report"]
        assert rep["reduction"] >= 2.0
        # the diagnostics ledger census agrees: device 0 holds fewer
        # live bytes than the logical param total would cost replicated
        per_dev = fs["per_device_live_bytes"]
        assert per_dev and all(v > 0 for v in per_dev.values())
