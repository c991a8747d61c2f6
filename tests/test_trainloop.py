"""Whole-loop train executor (mxtpu.trainloop) + satellites:

* run_k per-micro-step lr: bit-exact vs a sequential loop with constant
  lr, within-tolerance with a decaying schedule (the k-granularity
  scheduler-coarsening regression test);
* in-program lr (lr_scheduler.as_jax closed forms) matches the host
  schedulers step-for-step, including warmup and mid-run handoff;
* TrainLoop: chunk resolution (arg > Trainer.loop_chunk > env), fit
  drives the prefetcher, losses decrease, donation safety after chunks;
* DevicePrefetcher: ordering, chunk stacking, drain/early-stop without
  leaking the device buffer, io.* counters;
* Pallas selection (ops/select) parity on CPU (interpret-mode kernels):
  conv_bn_relu / scale_shift_act / BatchNormReLU, the MXTPU_PALLAS=0
  escape hatch, and the capture log;
* persistent-compile-cache guard (runtime/cache_guard): pass and trip
  paths.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import TrainLoop, gluon, nd
from incubator_mxnet_tpu import profiler as prof
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.io import DevicePrefetcher
from incubator_mxnet_tpu.parallel import FusedTrainStep


def _net(seed=0):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(init=mx.init.Xavier())
    return net


L = gluon.loss.SoftmaxCrossEntropyLoss()


def _data(seed=0, batch=8, n=1):
    rng = np.random.RandomState(seed)
    out = [(nd.array(rng.randn(batch, 8).astype(np.float32)),
            nd.array(rng.randint(0, 4, batch))) for _ in range(n)]
    return out[0] if n == 1 else out


def _stacked(k, seed=0, batch=8):
    pairs = _data(seed=seed, batch=batch, n=k)
    xs = jnp.stack([p[0]._data for p in pairs])
    ys = jnp.stack([p[1]._data for p in pairs])
    return xs, ys


# ---------------------------------------------------------------------------
# satellite: run_k scheduler coarsening fix
# ---------------------------------------------------------------------------

class TestRunKScheduleExact:
    def test_constant_lr_bit_exact(self):
        s1 = FusedTrainStep(_net(), L, mx.optimizer.create(
            "sgd", learning_rate=0.1, momentum=0.9))
        xs, ys = _stacked(4)
        seq = np.asarray([float(s1(nd.array(np.asarray(xs[i])),
                                   nd.array(np.asarray(ys[i]))))
                          for i in range(4)], np.float32)
        s2 = FusedTrainStep(_net(), L, mx.optimizer.create(
            "sgd", learning_rate=0.1, momentum=0.9))
        kl = s2.run_k(xs, ys).asnumpy().astype(np.float32)
        assert np.array_equal(kl, seq), (kl, seq)
        assert s2.optimizer.num_update == 4

    def test_decaying_schedule_matches_sequential(self):
        def mk():
            return mx.optimizer.create(
                "sgd", learning_rate=0.2,
                lr_scheduler=mx.lr_scheduler.FactorScheduler(
                    step=2, factor=0.5, base_lr=0.2))
        s1 = FusedTrainStep(_net(), L, mk())
        xs, ys = _stacked(6)
        seq = [float(s1(nd.array(np.asarray(xs[i])),
                        nd.array(np.asarray(ys[i])))) for i in range(6)]
        s2 = FusedTrainStep(_net(), L, mk())
        kl = s2.run_k(xs, ys).asnumpy()
        np.testing.assert_allclose(kl, seq, rtol=1e-6)
        # the scheduler advanced exactly like the sequential loop
        assert s2.optimizer.learning_rate == s1.optimizer.learning_rate

    def test_mixing_run_k_and_single_steps_keeps_schedule(self):
        def mk():
            return mx.optimizer.create(
                "sgd", learning_rate=0.2,
                lr_scheduler=mx.lr_scheduler.FactorScheduler(
                    step=3, factor=0.1, base_lr=0.2))
        s1 = FusedTrainStep(_net(), L, mk())
        xs, ys = _stacked(4)
        seq = [float(s1(nd.array(np.asarray(xs[i])),
                        nd.array(np.asarray(ys[i])))) for i in range(4)]
        x4, y4 = _data(seed=77)
        seq.append(float(s1(x4, y4)))
        s2 = FusedTrainStep(_net(), L, mk())
        got = list(s2.run_k(xs, ys).asnumpy())
        got.append(float(s2(x4, y4)))
        np.testing.assert_allclose(got, seq, rtol=1e-6)


class TestAsJaxSchedules:
    @pytest.mark.parametrize("mk", [
        lambda: mx.lr_scheduler.FactorScheduler(step=5, factor=0.5,
                                                base_lr=0.4),
        lambda: mx.lr_scheduler.FactorScheduler(step=3, factor=0.1,
                                                base_lr=1.0,
                                                stop_factor_lr=1e-3),
        lambda: mx.lr_scheduler.FactorScheduler(step=4, factor=0.7,
                                                base_lr=0.2, warmup_steps=6,
                                                warmup_begin_lr=0.01),
        lambda: mx.lr_scheduler.MultiFactorScheduler(step=[4, 9, 15],
                                                     factor=0.3,
                                                     base_lr=0.5),
        lambda: mx.lr_scheduler.MultiFactorScheduler(step=[3, 7], factor=0.5,
                                                     base_lr=0.5,
                                                     warmup_steps=2),
        lambda: mx.lr_scheduler.PolyScheduler(max_update=20, base_lr=0.3,
                                              pwr=2, final_lr=0.01),
        lambda: mx.lr_scheduler.CosineScheduler(max_update=25, base_lr=0.3,
                                                final_lr=0.02,
                                                warmup_steps=5),
        lambda: mx.lr_scheduler.LinearScheduler(max_update=18, base_lr=0.25),
    ])
    def test_matches_host(self, mk):
        host, traced = mk(), mk()
        fn = traced.as_jax()
        hv = [float(host(t)) for t in range(1, 30)]
        jv = [float(fn(t)) for t in range(1, 30)]
        np.testing.assert_allclose(jv, hv, rtol=1e-6, atol=1e-7)

    def test_midrun_handoff_stateful(self):
        h = mx.lr_scheduler.FactorScheduler(step=4, factor=0.5, base_lr=0.8)
        for t in range(1, 11):
            h(t)
        fn = h.as_jax()                 # closed form FROM current state
        ref = mx.lr_scheduler.FactorScheduler(step=4, factor=0.5,
                                              base_lr=0.8)
        want = [ref(t) for t in range(1, 25)][10:]
        got = [float(fn(t)) for t in range(11, 25)]
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_custom_scheduler_has_no_closed_form(self):
        class Weird(mx.lr_scheduler.LRScheduler):
            def __call__(self, num_update):
                return 0.1 / (1 + num_update % 7)
        assert Weird().as_jax() is None
        # ...and the executor still matches sequentially (host lr table)
        def mk():
            return mx.optimizer.create("sgd", learning_rate=0.1,
                                       lr_scheduler=Weird())
        s1 = FusedTrainStep(_net(), L, mk())
        xs, ys = _stacked(5)
        seq = [float(s1(nd.array(np.asarray(xs[i])),
                        nd.array(np.asarray(ys[i])))) for i in range(5)]
        s2 = FusedTrainStep(_net(), L, mk(), schedule_in_program=True)
        kl = s2.run_k(xs, ys).asnumpy()
        np.testing.assert_allclose(kl, seq, rtol=1e-6)
        assert s2._lr_program is None   # fell back to the host table


# ---------------------------------------------------------------------------
# TrainLoop executor
# ---------------------------------------------------------------------------

class TestTrainLoop:
    def test_bit_exact_vs_sequential_fused_path(self):
        s1 = FusedTrainStep(_net(), L, mx.optimizer.create(
            "sgd", learning_rate=0.1))
        xs, ys = _stacked(4)
        seq = np.asarray([float(s1(nd.array(np.asarray(xs[i])),
                                   nd.array(np.asarray(ys[i]))))
                          for i in range(4)], np.float32)
        loop = TrainLoop(_net(), L, mx.optimizer.create(
            "sgd", learning_rate=0.1), chunk=4)
        got = loop.run_chunk(xs, ys).asnumpy().astype(np.float32)
        assert np.array_equal(got, seq)

    def test_in_program_lr_matches_sequential(self):
        def mk():
            return mx.optimizer.create(
                "sgd", learning_rate=0.3,
                lr_scheduler=mx.lr_scheduler.CosineScheduler(
                    max_update=12, base_lr=0.3, final_lr=0.01))
        s1 = FusedTrainStep(_net(), L, mk())
        xs, ys = _stacked(8)
        seq = [float(s1(nd.array(np.asarray(xs[i])),
                        nd.array(np.asarray(ys[i])))) for i in range(8)]
        loop = TrainLoop(_net(), L, mk(), chunk=8)
        got = loop.run_chunk(xs, ys).asnumpy()
        assert loop.in_program_lr          # the schedule compiled on device
        np.testing.assert_allclose(got, seq, rtol=1e-5, atol=1e-6)

    def test_chunk_resolution(self, monkeypatch):
        net = _net()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1}, loop_chunk=6)
        assert TrainLoop(net, L, tr).chunk == 6
        assert TrainLoop(net, L, tr, chunk=3).chunk == 3
        monkeypatch.setenv("MXTPU_LOOP_CHUNK", "5")
        tr2 = gluon.Trainer(_net().collect_params(), "sgd",
                            {"learning_rate": 0.1})
        assert tr2.loop_chunk == 5
        assert TrainLoop(net, L, mx.optimizer.create("sgd")).chunk == 5
        monkeypatch.delenv("MXTPU_LOOP_CHUNK")
        assert TrainLoop(net, L, mx.optimizer.create("sgd")).chunk == 4

    def test_fit_trains_and_counts(self):
        loop = TrainLoop(_net(), L, mx.optimizer.create(
            "sgd", learning_rate=0.5), chunk=4)
        data = _data(seed=3, n=4) * 10          # 40 batches, recycled shapes
        losses = loop.fit(data, steps=40)
        assert losses.shape == (40,)
        assert losses[-4:].mean() < losses[:4].mean()
        assert loop.num_update == 40
        c = prof.counters()
        assert c["io/io.batches_prefetched"] >= 40
        assert "io/io.wait_ms" in c
        assert c["trainloop/trainloop.steps"] >= 40
        assert c["mxtpu/trainer.dispatches_per_step"] == 0.25

    def test_fit_epochs_drops_partial_chunk(self):
        loop = TrainLoop(_net(), L, mx.optimizer.create("sgd"), chunk=4)
        losses = loop.fit(_data(seed=3, n=10), epochs=1)  # 10 → 2 chunks
        assert losses.shape == (8,)

    def test_fit_epochs_resets_data_iter_each_epoch(self):
        """A DataIter source must rewind at every epoch start — epoch 2+
        of an exhausted iterator would otherwise silently contribute
        nothing."""
        import incubator_mxnet_tpu.io as mio
        rng = np.random.RandomState(0)
        X = rng.randn(32, 8).astype(np.float32)
        Y = rng.randint(0, 4, 32).astype(np.float32)
        it = mio.NDArrayIter(X, Y, batch_size=8)    # 4 batches/epoch
        loop = TrainLoop(_net(), L, mx.optimizer.create("sgd"), chunk=4)
        losses = loop.fit(it, epochs=3)
        assert losses.shape == (12,)                # 1 chunk x 3 epochs

    def test_fit_steps_exhausted_source_raises_clearly(self):
        loop = TrainLoop(_net(), L, mx.optimizer.create("sgd"), chunk=4)
        gen = (b for b in _data(seed=3, n=8))       # 8 batches, no rewind
        with pytest.raises(ValueError, match="exhausted after 8 of 16"):
            loop.fit(gen, steps=16)

    def test_fit_labelless_source_rejected(self):
        loop = TrainLoop(_net(), L, mx.optimizer.create("sgd"), chunk=2)
        bare = [np.zeros((4, 8), np.float32) for _ in range(4)]
        with pytest.raises(ValueError, match="labeled batches"):
            loop.fit(bare, steps=2)

    def test_fit_epochs_oneshot_iterator_raises(self):
        loop = TrainLoop(_net(), L, mx.optimizer.create("sgd"), chunk=4)
        gen = (b for b in _data(seed=3, n=8))       # can't rewind
        with pytest.raises(ValueError, match="epoch 2 produced no"):
            loop.fit(gen, epochs=2)

    def test_donation_safety_between_chunks(self):
        """Params stay readable between chunks (rebound to the donated
        program's outputs), and a reader between chunks doesn't poison
        the next dispatch."""
        net = _net()
        loop = TrainLoop(net, L, mx.optimizer.create(
            "sgd", learning_rate=0.1), chunk=3)
        xs, ys = _stacked(3)
        loop.run_chunk(xs, ys)
        snap1 = {k: v.data().asnumpy().copy()
                 for k, v in net.collect_params().items()}
        loop.run_chunk(xs, ys)
        snap2 = {k: v.data().asnumpy().copy()
                 for k, v in net.collect_params().items()}
        changed = any(not np.array_equal(snap1[k], snap2[k]) for k in snap1)
        assert changed, "second chunk did not update parameters"
        # and the params still drive an eager forward
        x, _ = _data()
        assert np.isfinite(net(x).asnumpy()).all()

    def test_steps_smaller_than_chunk_rejected(self):
        loop = TrainLoop(_net(), L, mx.optimizer.create("sgd"), chunk=8)
        with pytest.raises(ValueError, match="less than one chunk"):
            loop.fit(_data(n=4), steps=4)

    @pytest.mark.parametrize("policy", ["dots", "nothing", "everything"])
    def test_remat_policies_match_plain(self, policy):
        x, y = _data()
        s1 = FusedTrainStep(_net(), L, mx.optimizer.create(
            "sgd", learning_rate=0.1))
        a = float(s1(x, y))
        s2 = FusedTrainStep(_net(), L, mx.optimizer.create(
            "sgd", learning_rate=0.1), remat=True, remat_policy=policy)
        np.testing.assert_allclose(float(s2(x, y)), a, rtol=1e-6)

    def test_states_show_the_master_weight_and_the_optimizers_own(self):
        net = _net()
        net.cast("bfloat16")
        step = FusedTrainStep(net, L, mx.optimizer.create(
            "adam", learning_rate=0.01, multi_precision=True))
        assert step.states() == []
        x, y = _data()
        x = x.astype("bfloat16")
        step.ensure_built(x, y)
        before = [np.asarray(p.data().jax(), np.float32)
                  for p in net.collect_params().values()]
        step(x, y)
        states = step.states()
        assert len(states) == len(step.train_idx) == 4
        for i, (master, mean, variance) in zip(step.train_idx, states):
            assert master.dtype == mean.dtype == jnp.float32
            assert master.shape == before[i].shape
            # Adam's first update moves a weight by the rate against its
            # gradient's sign, and keeps a tenth of the gradient as the mean
            moved = np.asarray(master) - before[i]
            sign = np.sign(np.asarray(mean))
            np.testing.assert_allclose(moved[sign != 0],
                                       -0.01 * sign[sign != 0], rtol=1e-3)
            np.testing.assert_allclose(np.asarray(variance),
                                       0.1 * np.asarray(mean) ** 2,
                                       rtol=1e-4, atol=1e-30)

    def test_bad_remat_policy_raises(self):
        step = FusedTrainStep(_net(), L, "sgd", remat=True,
                              remat_policy="bogus")
        with pytest.raises(ValueError, match="remat_policy"):
            step(*_data())


# ---------------------------------------------------------------------------
# DevicePrefetcher
# ---------------------------------------------------------------------------

class TestDevicePrefetcher:
    def test_order_and_values(self):
        data = _data(seed=5, n=6)
        with DevicePrefetcher(data, depth=2) as pf:
            got = list(pf)
        assert len(got) == 6
        for (x, y), (gx, gy) in zip(data, got):
            np.testing.assert_array_equal(x.asnumpy(), np.asarray(gx))
            np.testing.assert_array_equal(y.asnumpy(), np.asarray(gy))

    def test_chunk_stacking(self):
        data = _data(seed=5, n=7)
        with DevicePrefetcher(data, depth=2, chunk=3) as pf:
            got = list(pf)
        assert len(got) == 2                  # 7 → two chunks, tail dropped
        assert got[0][0].shape == (3, 8, 8)
        np.testing.assert_array_equal(
            np.asarray(got[1][0])[0], data[3][0].asnumpy())

    def test_early_stop_drains_without_leak(self):
        data = _data(seed=5, n=50)
        pf = DevicePrefetcher(data, depth=3)
        next(pf)                              # consume one, buffer fills
        pf.close()                            # early stop mid-stream
        assert not pf._thread.is_alive()
        assert pf._buf.qsize() == 0           # no device refs parked
        with pytest.raises(StopIteration):
            next(pf)
        pf.close()                            # idempotent

    def test_source_error_surfaces_at_next(self):
        def bad():
            yield _data()
            raise RuntimeError("decode exploded")
        pf = DevicePrefetcher(bad(), depth=2)
        next(pf)
        with pytest.raises(RuntimeError, match="decode exploded"):
            next(pf)
        pf.close()

    def test_cycle_restarts_data_iter(self):
        import incubator_mxnet_tpu.io as mio
        X = np.random.RandomState(0).rand(8, 4).astype(np.float32)
        it = mio.NDArrayIter(X, X[:, 0], batch_size=4)
        with DevicePrefetcher(it, depth=2, cycle=True) as pf:
            got = [next(pf) for _ in range(5)]    # 2 per epoch, cycles
        assert len(got) == 5

    def test_close_abandons_worker_blocked_in_source(self, monkeypatch):
        """A worker parked inside the source's next() can't be
        interrupted; close() must return after its deadline instead of
        hanging the training process."""
        import threading
        import time as _time
        from incubator_mxnet_tpu.io import prefetch as _pfmod
        monkeypatch.setattr(_pfmod, "_CLOSE_DEADLINE_S", 0.3)
        release = threading.Event()

        def blocking():
            yield _data(seed=0)
            release.wait(30)          # park until the test releases us

        pf = DevicePrefetcher(blocking(), depth=2)
        next(pf)
        t0 = _time.monotonic()
        pf.close()                    # worker is stuck inside wait(30)
        assert _time.monotonic() - t0 < 2.0
        assert pf._buf.qsize() == 0
        release.set()

    def test_mixed_labels_in_chunk_rejected(self):
        x = np.zeros((4, 8), np.float32)
        src = [(x, np.zeros(4, np.float32)), (x, None)]
        pf = DevicePrefetcher(src, depth=2, chunk=2)
        with pytest.raises(ValueError, match="mixed labeled"):
            next(pf)
        pf.close()

    def test_wait_counter_advances_on_slow_source(self):
        import time as _time
        base = prof.counters().get("io/io.wait_ms", 0)

        def slow():
            for i in range(3):
                _time.sleep(0.05)
                yield _data(seed=i)
        with DevicePrefetcher(slow(), depth=2) as pf:
            list(pf)
        assert prof.counters()["io/io.wait_ms"] > base


# ---------------------------------------------------------------------------
# Pallas selection + interpret-mode kernel parity (CPU)
# ---------------------------------------------------------------------------

@pytest.fixture
def force_pallas(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS", "force")
    yield


class TestPallasSelection:
    def test_escape_hatch_master_switch(self, monkeypatch):
        from incubator_mxnet_tpu.ops import pallas as P
        monkeypatch.setenv("MXTPU_PALLAS", "0")
        assert not P.enabled()
        monkeypatch.setenv("MXTPU_PALLAS", "force")
        assert P.enabled()
        # the natural MXTPU_*=1 spelling is explicit-on, not a no-op
        # (off-TPU: interpret-mode kernels)
        monkeypatch.setenv("MXTPU_PALLAS", "1")
        assert P.enabled() or P.is_tpu()
        monkeypatch.delenv("MXTPU_PALLAS")
        monkeypatch.setenv("MXTPU_NO_PALLAS", "1")
        assert not P.enabled()

    def test_selection_counters_and_capture(self, force_pallas):
        from incubator_mxnet_tpu.ops import select as S
        x = jnp.ones((4, 32))
        g = jnp.ones((32,))
        with S.capture() as log:
            assert S.layer_norm(x, g, -1)
            assert not S.flash_attention(mask=jnp.ones((4, 4)),
                                         dropout_active=False)
        assert log == [
            {"kernel": "layer_norm", "selected": True, "reason": "ok"},
            {"kernel": "flash_attention", "selected": False,
             "reason": "explicit mask (causal and window stay in the kernel)"}]
        c = prof.counters()
        assert c["ops/pallas.selected.layer_norm"] >= 1
        assert c["ops/pallas.rejected.flash_attention"] >= 1

    def test_scale_shift_act_parity_fwd_bwd(self, force_pallas):
        from incubator_mxnet_tpu.ops import pallas as P
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(6, 7, 32).astype(np.float32))
        s = jnp.asarray(rng.rand(32).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(32).astype(np.float32))

        def ref(x, s, b):
            return jnp.maximum(x * s + b, 0.0)

        got, vg = jax.vjp(lambda *a: P.scale_shift_act(*a, act="relu"),
                          x, s, b)
        want, vr = jax.vjp(ref, x, s, b)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)
        ct = jnp.ones_like(want)
        for g1, g2, nm in zip(vg(ct), vr(ct), "xsb"):
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       atol=1e-5, err_msg=nm)

    @pytest.mark.parametrize("geometry", ["1x1", "3x3"])
    def test_conv_bn_relu_parity(self, force_pallas, geometry):
        from incubator_mxnet_tpu.ops import pallas as P, _raw
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 5, 5, 16).astype(np.float32))
        kh = 1 if geometry == "1x1" else 3
        pad = (0, 0) if geometry == "1x1" else (1, 1)
        w = jnp.asarray(rng.randn(kh, kh, 16, 24).astype(np.float32) * 0.2)
        g = jnp.asarray(rng.rand(24).astype(np.float32) + 0.5)
        be = jnp.asarray(rng.randn(24).astype(np.float32))
        mm = jnp.asarray(rng.randn(24).astype(np.float32) * 0.1)
        mv = jnp.asarray(rng.rand(24).astype(np.float32) + 0.5)

        def ref(x, w):
            y = jax.lax.conv_general_dilated(
                x, w, (1, 1), [(p, p) for p in pad],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            yy, _, _ = _raw.batch_norm(y, g, be, mm, mv, axis=-1,
                                       training=False)
            return jnp.maximum(yy, 0)

        got, vg = jax.vjp(
            lambda x, w: P.conv_bn_relu(x, w, g, be, mm, mv, pad=pad), x, w)
        want, vr = jax.vjp(ref, x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        ct = jnp.ones_like(want)
        for g1, g2, nm in zip(vg(ct), vr(ct), ["x", "w"]):
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       atol=2e-4, rtol=1e-4, err_msg=nm)

    def test_batch_norm_relu_block_fused_parity(self, force_pallas):
        """nn.BatchNormReLU (fused epilogue) vs nn.BatchNorm + relu —
        training AND inference mode, channels-last."""
        def mk(cls):
            mx.random.seed(0)
            np.random.seed(0)
            b = cls(axis=-1, in_channels=16)
            b.initialize()
            return b
        x = nd.array(np.random.RandomState(1)
                     .randn(4, 6, 16).astype(np.float32))
        for train in (True, False):
            fused, plain = mk(nn.BatchNormReLU), mk(nn.BatchNorm)
            with mx.autograd.record(train_mode=train):
                yf = fused(x)
                yp = plain(x).relu()
            np.testing.assert_allclose(yf.asnumpy(), yp.asnumpy(),
                                       atol=1e-5,
                                       err_msg=f"train={train}")
            np.testing.assert_allclose(
                fused.running_mean.data().asnumpy(),
                plain.running_mean.data().asnumpy(), atol=1e-6)

    def test_unsupported_act_falls_back_to_xla(self, force_pallas):
        """Activations outside the epilogue kernel's table (relu/relu6)
        must route to the XLA chain, not raise from the pallas kernel."""
        from incubator_mxnet_tpu.ops import _raw, select as S
        x = jnp.ones((4, 32))
        assert not S.scale_shift_act(x, -1, act="sigmoid")
        y, _, _ = _raw.batch_norm(
            x, jnp.ones(32), jnp.zeros(32), jnp.zeros(32), jnp.ones(32),
            axis=-1, training=False, act="sigmoid")
        np.testing.assert_allclose(np.asarray(y),
                                   1 / (1 + np.exp(-1.0)), atol=1e-6)

    def test_conv_bn_relu_op_training_fallback(self, force_pallas):
        """The NDArray-level ConvBNReLU op in training mode falls back to
        the exact conv→BN(batch stats)→relu chain."""
        from incubator_mxnet_tpu import ops
        rng = np.random.RandomState(0)
        x = nd.array(rng.randn(2, 5, 5, 8).astype(np.float32))
        w = nd.array(rng.randn(1, 1, 8, 12).astype(np.float32))
        g = nd.array(np.ones(12, np.float32))
        b = nd.array(np.zeros(12, np.float32))
        mm = nd.array(np.zeros(12, np.float32))
        mv = nd.array(np.ones(12, np.float32))
        with mx.autograd.record():
            y = ops.ConvBNReLU(x, w, g, b, mm, mv)
        from incubator_mxnet_tpu.ops import _raw
        ref = jax.lax.conv_general_dilated(
            x._data, w._data, (1, 1), [(0, 0), (0, 0)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        ry, _, _ = _raw.batch_norm(ref, g._data, b._data, mm._data,
                                   mv._data, axis=-1, training=True)
        np.testing.assert_allclose(y.asnumpy(),
                                   np.maximum(np.asarray(ry), 0), atol=1e-5)

    def test_hybridize_records_selection(self, force_pallas, tmp_path):
        """hybridize() tracing routes through the selection layer: the
        trace's decisions show in the counters and in the flight ring
        (_build_cache captures them into a pallas.selection record)."""
        from incubator_mxnet_tpu import diagnostics as diag
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(32), nn.LayerNorm(in_channels=32))
        net.initialize()
        net.hybridize()
        x = nd.array(np.random.RandomState(0)
                     .randn(4, 8).astype(np.float32))
        before = prof.counters().get("ops/pallas.selected.layer_norm", 0)
        diag.enable_flight_recorder(dump_dir=str(tmp_path))
        try:
            net(x)                       # first call = the CachedOp trace
        finally:
            from incubator_mxnet_tpu.diagnostics import flight as _flight
            events = (list(_flight._REC.events)
                      if _flight._REC is not None else [])
            diag.disable_flight_recorder()
        assert prof.counters()["ops/pallas.selected.layer_norm"] > before
        sel = [e for e in events
               if e.get("name", "").startswith("pallas.selection:")]
        assert sel, f"no pallas.selection record in flight ring: " \
                    f"{[e.get('name') for e in events][:10]}"
        decisions = sel[-1]["args"]["decisions"]
        assert any(d["kernel"] == "layer_norm" and d["selected"]
                   for d in decisions)


# ---------------------------------------------------------------------------
# persistent-compile-cache guard
# ---------------------------------------------------------------------------

class TestCacheGuard:
    def test_canary_passes_and_caches_verdict(self):
        from incubator_mxnet_tpu.runtime import cache_guard as cg
        cg._reset_for_tests()
        try:
            assert cg.check() is True
            assert cg.verdict() is True
        finally:
            cg._reset_for_tests()

    def test_corrupt_read_trips_and_disables_cache(self, monkeypatch):
        from incubator_mxnet_tpu.runtime import cache_guard as cg
        cg._reset_for_tests()
        old_enabled = jax.config.jax_enable_compilation_cache
        monkeypatch.setattr(
            cg, "_canary_values",
            lambda: (np.zeros((8, 128), np.float32),
                     np.full((4,), 1e19, np.float32)))
        monkeypatch.setattr(cg, "_cache_active", lambda: True)
        try:
            with pytest.warns(RuntimeWarning, match="integrity canary"):
                assert cg.check() is False
            assert jax.config.jax_enable_compilation_cache is False
            assert prof.counters()["mxtpu/compile_cache.guard_tripped"] >= 1
        finally:
            jax.config.update("jax_enable_compilation_cache", old_enabled)
            cg._reset_for_tests()

    def test_env_opt_out(self, monkeypatch):
        from incubator_mxnet_tpu.runtime import cache_guard as cg
        cg._reset_for_tests()
        monkeypatch.setenv("MXTPU_CACHE_GUARD", "0")
        called = []
        monkeypatch.setattr(cg, "_canary_values",
                            lambda: called.append(1) or (None, None))
        try:
            assert cg.check() is True
            assert not called
        finally:
            cg._reset_for_tests()
