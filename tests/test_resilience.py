"""mxtpu.resilience — the recovery-policy matrix (ISSUE 12).

Covers: manifest integrity + torn-checkpoint fallback, atomic-save
invisibility, bounded rotation, save-is-async (the training thread
never blocks past the boundary copy), bit-exact resume at constant lr,
data-cursor resume not replaying consumed batches, NaN -> rollback ->
retries-exhausted -> escalate, stall -> supervised restart routing,
elastic evict/leave/re-join, disabled-mode zero overhead, and the
tooling contracts (trace_check families + extra, perf_regress
recovered-run notes, mxdiag recover rendering). The chaos harness
(tools/chaos_cluster.py) runs as a subprocess acceptance test.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd, resilience
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.io.prefetch import DevicePrefetcher
from incubator_mxnet_tpu.parallel import (CorruptCheckpointError,
                                          latest_step, list_steps,
                                          read_manifest,
                                          restore_train_step,
                                          save_train_step,
                                          verify_checkpoint)
from incubator_mxnet_tpu.parallel import checkpoint as ckpt_mod
from incubator_mxnet_tpu.profiler.counters import counters
from incubator_mxnet_tpu.resilience import (CheckpointManager,
                                            ElasticGroup,
                                            RecoveryEscalated, Supervisor)
from incubator_mxnet_tpu.trainloop import TrainLoop

_HERE = os.path.dirname(os.path.abspath(__file__))
_TOOLS = os.path.join(os.path.dirname(_HERE), "tools")


def _load_tool(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_rtool_" + name, os.path.join(_TOOLS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# toy fixtures
# ---------------------------------------------------------------------------

_W = np.random.RandomState(7).randn(8, 1).astype(np.float32)


@pytest.fixture
def _fresh_compile_session():
    """Disable the persistent XLA compile cache for a bit-exactness
    test: the cache can hand the resumed executor an executable
    compiled by a PREVIOUS process, and XLA:CPU codegen is not
    bit-stable across compile sessions — last-float-bit divergence
    that is compiler noise, not a resume bug. Restored state itself is
    exact (the other Supervisor tests pin that); bit-exact loss
    comparison is only meaningful between executables born in one
    compiler session, so this test compiles everything fresh."""
    import jax
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def _batch(i, poison=False):
    r = np.random.RandomState(1000 + i)
    x = r.randn(16, 8).astype(np.float32)
    if poison:
        x[0, 0] = np.nan
    return (x, (x @ _W).astype(np.float32))


def _loop(seed=0, chunk=2):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(1))
    net.initialize(init=mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, kvstore=None)
    return TrainLoop(net, gluon.loss.L2Loss(), tr, chunk=chunk)


def _snap():
    return {k: v for k, v in counters().items()
            if k.startswith("resilience/") and not isinstance(v, dict)}


# ---------------------------------------------------------------------------
# checkpoint layer: manifest, atomicity, fallback, rotation, async
# ---------------------------------------------------------------------------

class TestCheckpointIntegrity:
    def _built_step(self, n_steps=2):
        loop = _loop()
        data = [_batch(i) for i in range(20)]
        loop.fit(data, steps=n_steps, cycle=False)
        return loop.step

    def test_manifest_written_and_verifies(self, tmp_path):
        step = self._built_step()
        p = save_train_step(str(tmp_path), step, cursor=5)
        status, errs = verify_checkpoint(p)
        assert (status, errs) == ("ok", [])
        man = read_manifest(p)
        assert man["schema"].startswith("mxtpu.ckpt-manifest/")
        assert man["meta"] == {"num_update": 2, "cursor": 5}
        assert man["files"]          # per-shard digests present
        for rec in man["files"].values():
            assert rec["bytes"] >= 0 and len(rec["sha256"]) == 64

    def test_torn_checkpoint_detected_and_fallback(self, tmp_path):
        step = self._built_step()
        save_train_step(str(tmp_path), step)        # good @ 2
        data = [_batch(i) for i in range(20, 26)]
        for xy in [data[i:i + 2] for i in range(0, 4, 2)]:
            step.run_k(np.stack([b[0] for b in xy]),
                       np.stack([b[1] for b in xy]))
        p2 = save_train_step(str(tmp_path), step)   # newest @ 6
        # tear the newest: bit-flip its largest payload file
        victim, size = None, -1
        for root, _d, files in os.walk(p2):
            for f in files:
                if f == "manifest.json":
                    continue
                fp = os.path.join(root, f)
                if os.path.getsize(fp) > size:
                    victim, size = fp, os.path.getsize(fp)
        with open(victim, "r+b") as f:
            b = f.read(1)
            f.seek(0)
            f.write(bytes([b[0] ^ 0xFF]))
        assert verify_checkpoint(p2)[0] == "corrupt"
        before = counters().get(
            "resilience/resilience.corrupt_checkpoints", 0)
        fresh = self._built_step(n_steps=2)
        # latest-good path: falls back past the torn one, counted
        n = restore_train_step(str(tmp_path), fresh)
        assert n == 2
        assert counters()["resilience/resilience.corrupt_checkpoints"] \
            == before + 1
        # explicit request for the torn step: refuses loudly
        with pytest.raises(CorruptCheckpointError):
            restore_train_step(str(tmp_path), fresh, step_num=6)

    def test_all_corrupt_raises(self, tmp_path):
        step = self._built_step()
        p = save_train_step(str(tmp_path), step)
        man = os.path.join(p, "manifest.json")
        doc = json.load(open(man))
        first = next(iter(doc["files"]))
        doc["files"][first]["sha256"] = "0" * 64
        json.dump(doc, open(man, "w"))
        fresh = self._built_step()
        with pytest.raises(CorruptCheckpointError, match="every"):
            restore_train_step(str(tmp_path), fresh)

    def test_inflight_temp_dir_never_visible(self, tmp_path):
        """A crashed mid-save leaves only a dot-prefixed temp dir —
        latest_step/list_steps must never surface it."""
        step = self._built_step()
        save_train_step(str(tmp_path), step)
        os.makedirs(tmp_path / ".tmp_step_00000099.1234.5678")
        (tmp_path / ".tmp_step_00000099.1234.5678" / "junk").write_bytes(
            b"torn")
        assert latest_step(str(tmp_path)) == 2
        assert list_steps(str(tmp_path)) == [2]

    def test_rotation_bounded(self, tmp_path):
        step = self._built_step()
        mgr = CheckpointManager(str(tmp_path), step, every=1, keep=2)
        try:
            for i in range(5):
                mgr.save_now(step_num=10 + i, block=True)
            mgr.wait()
            time.sleep(0.05)       # let the last prune land
            assert len(list_steps(str(tmp_path))) <= 2
            assert list_steps(str(tmp_path))[-1] == 14
            assert counters()[
                "resilience/resilience.checkpoints_pruned"] >= 3
        finally:
            mgr.close()

    def test_cadence_not_stretched_by_chunk_misalignment(self, tmp_path):
        """every=3 with a chunk advancing num_update by 2 must still
        checkpoint roughly every 3 steps (crossing the boundary), not
        every lcm(3,2)=6 (landing exactly on it)."""
        step = self._built_step()
        mgr = CheckpointManager(str(tmp_path), step, every=3, keep=10)
        try:
            saved = [n for n in range(2, 14, 2)
                     if mgr.maybe_save(step_num=n) and mgr.wait(5)]
            assert saved == [4, 6, 10, 12]
        finally:
            mgr.close()

    def test_cadence_reanchors_after_rollback(self, tmp_path):
        """A restore moves num_update below the save high-water mark;
        replayed steps must checkpoint on cadence again instead of
        waiting to re-cross the old mark."""
        step = self._built_step()
        mgr = CheckpointManager(str(tmp_path), step, every=2, keep=10)
        try:
            assert mgr.maybe_save(step_num=2) and mgr.wait(5)
            assert mgr.maybe_save(step_num=8) and mgr.wait(5)
            # tear the newest so the restore lands BELOW the high-water
            man = tmp_path / "step_00000008" / "manifest.json"
            doc = json.loads(man.read_text())
            first = next(iter(doc["files"]))
            doc["files"][first]["sha256"] = "0" * 64
            man.write_text(json.dumps(doc))
            n, _cur = mgr.restore_last_good()
            assert n == 2
            assert mgr.maybe_save(step_num=4)   # replay checkpoints
        finally:
            mgr.close()

    def test_save_is_async_never_blocks_past_copy(self, tmp_path,
                                                  monkeypatch):
        """The training thread pays the boundary copy only: with a slow
        serializer, maybe_save returns fast and an in-flight save turns
        the next boundary into a counted skip, not a wait."""
        step = self._built_step()
        real_save = ckpt_mod.save_tree

        def slow_save(directory, n, tree, meta=None):
            time.sleep(0.6)
            return real_save(directory, n, tree, meta=meta)

        monkeypatch.setattr(ckpt_mod, "save_tree", slow_save)
        mgr = CheckpointManager(str(tmp_path), step, every=1, keep=3)
        try:
            skipped0 = counters().get(
                "resilience/resilience.saves_skipped", 0)
            t0 = time.perf_counter()
            assert mgr.maybe_save(step_num=1)
            first = time.perf_counter() - t0
            assert first < 0.4, \
                f"maybe_save blocked {first:.3f}s on serialization"
            t0 = time.perf_counter()
            assert not mgr.maybe_save(step_num=2)   # in flight -> skip
            assert time.perf_counter() - t0 < 0.2
            assert counters()["resilience/resilience.saves_skipped"] \
                == skipped0 + 1
            mgr.wait()
            assert mgr.last_saved_step == 1
        finally:
            mgr.close()


# ---------------------------------------------------------------------------
# supervisor: resume exactness, cursor, rollback, escalation, stall
# ---------------------------------------------------------------------------

class TestSupervisor:
    def test_bit_exact_resume_constant_lr(self, tmp_path,
                                          _fresh_compile_session):
        data = [_batch(i) for i in range(40)]
        gold = _loop().fit(data, steps=12, cycle=False)

        d = str(tmp_path / "ck")
        loop1 = _loop()
        first = loop1.fit(data, steps=6,
                          resilience=Supervisor(d, every=100))

        # the resume contract, asserted where it is guaranteed: the
        # restored state is BIT-identical to the live state the first
        # run ended with (params + optimizer + rng + update counter)
        import jax
        live = jax.tree_util.tree_leaves(ckpt_mod._host_tree(loop1.step))
        loop2 = _loop()
        x, y = _batch(0)
        loop2.step.ensure_built(nd.array(x), nd.array(y))
        restore_train_step(d, loop2.step)
        assert loop2.step._num_update == 6
        restored = jax.tree_util.tree_leaves(
            ckpt_mod._host_tree(loop2.step))
        assert len(live) == len(restored)
        for a, b in zip(live, restored):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        resumed = _loop().fit(data, steps=12,
                              resilience=Supervisor(d, every=100))
        got = np.concatenate([first, resumed])
        # the continued trajectory matches the uninterrupted one. NOT
        # assert_array_equal: gold and the resumed run execute
        # separately-compiled XLA:CPU programs, and the compiler's
        # per-compile choices (measured: a ~2^-8 dot-precision variant
        # under load) are not bit-stable across compiles — compiler
        # variance, not resume state drift (pinned bit-exactly above).
        # A reset/diverged trajectory differs by >100%; 1e-2 is far
        # below that and above the measured compiler noise.
        np.testing.assert_allclose(got, gold, rtol=1e-2)

    def test_ambient_arming_degrades_not_crashes(self, tmp_path,
                                                 monkeypatch):
        # MXTPU_RESILIENCE_DIR arms every Trainer ambiently; an
        # epochs-driven fit that predates resilience must keep working
        # (unsupervised + warning), and resilience=False opts a single
        # call out of the ambient default
        amb = str(tmp_path / "amb")
        monkeypatch.setenv("MXTPU_RESILIENCE_DIR", amb)
        data = [_batch(i) for i in range(8)]
        with pytest.warns(UserWarning, match="UNSUPERVISED"):
            losses = _loop().fit(data, epochs=1)
        assert len(losses) == 8
        losses = _loop().fit(data, steps=4, resilience=False)
        assert len(losses) == 4
        assert not os.path.isdir(amb)   # nothing ever armed
        # explicit misuse still raises
        with pytest.raises(ValueError, match="steps-driven"):
            _loop().fit(data, epochs=1,
                        resilience=Supervisor(str(tmp_path / "x")))

    def test_cursor_resume_skips_consumed_batches(self, tmp_path):
        data = [_batch(i) for i in range(40)]
        d = str(tmp_path / "ck")
        _loop().fit(data, steps=6, resilience=Supervisor(d, every=100))
        man = read_manifest(
            os.path.join(d, f"step_{6:08d}"))
        assert man["meta"]["cursor"] == 6   # 3 chunks x 2 batches
        skipped0 = counters().get("io/io.batches_skipped", 0)
        _loop().fit(data, steps=12, resilience=Supervisor(d, every=100))
        assert counters()["io/io.batches_skipped"] == skipped0 + 6

    def test_nan_rollback_skips_poison_and_converges(self, tmp_path):
        data = [_batch(i, poison=(i == 7)) for i in range(60)]
        rb0 = counters().get("resilience/resilience.rollbacks", 0)
        loop = _loop()
        losses = loop.fit(data, steps=12,
                          resilience=Supervisor(str(tmp_path), every=2))
        assert len(losses) == 12
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
        c = counters()
        assert c["resilience/resilience.rollbacks"] == rb0 + 1
        assert c["resilience/resilience.recoveries_total"] >= 1
        assert loop.step._num_update == 12

    def test_retries_exhausted_escalates(self, tmp_path):
        data = [_batch(i, poison=True) for i in range(60)]
        esc0 = counters().get(
            "resilience/resilience.retries_exhausted", 0)
        with pytest.raises(RecoveryEscalated, match="consecutive"):
            _loop().fit(data, steps=12,
                        resilience=Supervisor(str(tmp_path), every=2,
                                              max_retries=2,
                                              backoff_s=0.0))
        assert counters()[
            "resilience/resilience.retries_exhausted"] == esc0 + 1

    def test_reread_mode_retries_same_chunk(self, tmp_path):
        """skip_poison=False re-reads the faulting chunk — with a
        persistent poison batch that means escalation after exactly
        max_retries re-reads (the transient-fault policy)."""
        data = [_batch(i, poison=(i == 3)) for i in range(60)]
        with pytest.raises(RecoveryEscalated):
            _loop().fit(data, steps=12,
                        resilience=Supervisor(str(tmp_path), every=2,
                                              max_retries=1,
                                              backoff_s=0.0,
                                              skip_poison=False))

    def test_stall_routes_to_registered_supervisor(self, tmp_path):
        sup = Supervisor(str(tmp_path), on_stall="none")
        resilience._register(sup)
        try:
            mon = mx.healthmon.enable(
                hm_dir=str(tmp_path), stall_timeout_s=0,
                events_path=str(tmp_path / "ev.jsonl"))
            r0 = counters().get(
                "resilience/resilience.restarts_requested", 0)
            mon._alert("stall", {"age_s": 12.0})
            assert counters()[
                "resilience/resilience.restarts_requested"] == r0 + 1
            # non-stall verdicts are the drive loop's problem, not the
            # alert hook's
            mon._alert("nan_loss", {"value": "nan"})
            assert counters()[
                "resilience/resilience.restarts_requested"] == r0 + 1
            ev = (tmp_path / "ev.jsonl").read_text()
            assert "resilience.restart_requested" in ev
        finally:
            mx.healthmon.disable()
            resilience._unregister(sup)

    def test_invalid_on_stall_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="on_stall"):
            Supervisor(str(tmp_path), on_stall="reboot")

    def test_epochs_mode_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="steps-driven"):
            _loop().fit([_batch(i) for i in range(8)], epochs=1,
                        resilience=str(tmp_path))

    def test_healthmon_status_carries_resilience(self):
        st = mx.healthmon.status()
        assert "resilience" in st
        rs = st["resilience"]
        for key in ("supervised", "last_checkpoint_step",
                    "recoveries_total", "rollback_in_progress"):
            assert key in rs
        assert rs["supervised"] is False


class TestDisabledOverhead:
    def test_plain_fit_touches_no_resilience_state(self):
        """The disabled-cost contract: an unsupervised fit leaves every
        resilience counter untouched and registers no supervisor."""
        before = _snap()
        data = [_batch(i) for i in range(20)]
        _loop().fit(data, steps=4, cycle=False)
        assert _snap() == before
        assert resilience.current() is None
        assert not resilience.supervised()


# ---------------------------------------------------------------------------
# prefetcher cursor skip
# ---------------------------------------------------------------------------

class TestPrefetcherSkip:
    def test_skip_drops_exactly_n(self):
        items = [(np.full((2, 2), i, np.float32),
                  np.full((2, 1), i, np.float32)) for i in range(10)]
        skipped0 = counters().get("io/io.batches_skipped", 0)
        with DevicePrefetcher(items, depth=2, skip=3) as pf:
            got = [float(np.asarray(x)[0, 0]) for x, _ in pf]
        assert got == [3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0]
        assert counters()["io/io.batches_skipped"] == skipped0 + 3

    def test_negative_skip_rejected(self):
        with pytest.raises(ValueError, match="skip"):
            DevicePrefetcher([], skip=-1)

    def test_cycling_skip_folds_to_epoch_position(self):
        # a long run's absolute cursor through a cycling source resumes
        # at cursor % epoch — after ONE learning pass, whole epochs of
        # the skip fold away instead of being read and discarded
        items = [(np.full((2, 2), i, np.float32),
                  np.full((2, 1), i, np.float32)) for i in range(4)]
        skipped0 = counters().get("io/io.batches_skipped", 0)
        with DevicePrefetcher(items, depth=2, skip=10, cycle=True) as pf:
            got = [float(np.asarray(next(pf)[0])[0, 0]) for _ in range(3)]
        assert got == [2.0, 3.0, 0.0]           # 10 % 4 = 2
        # one full learning pass (4) + in-epoch remainder (2), not 10
        assert counters()["io/io.batches_skipped"] == skipped0 + 6

    def test_sharded_rejoin_replays_zero_batches(self, tmp_path):
        # the PR 17 resume matrix: sharded record reader x skip cursor
        # x an evicted rank re-joining. The re-joined rank must resume
        # ITS shard exactly where the cursor says — zero replayed
        # batches, zero holes, order bit-identical to a serial rank
        # that never left, at any decode-pool width.
        from incubator_mxnet_tpu import recordio
        from incubator_mxnet_tpu.io.pipeline import ShardedRecordReader
        idx = str(tmp_path / "s.idx")
        rec = str(tmp_path / "s.rec")
        w = recordio.MXIndexedRecordIO(idx, rec, "w")
        for i in range(21):
            w.write_idx(i, recordio.pack(
                recordio.IRHeader(0, float(i), i, 0),
                np.full((2, 2), i, np.float32).tobytes()))
        w.close()

        def decode(payload):
            _h, s = recordio.unpack(payload)
            x = np.frombuffer(s, np.float32).reshape(2, 2).copy()
            return x, x[:, :1]

        def rank_reader():
            return ShardedRecordReader(idx, rec, rank=1, num_ranks=3,
                                       decode_fn=decode)

        def trace(pf, n=None):
            out = []
            for x, _ in pf:
                out.append(int(np.asarray(x)[0, 0]))
                if n is not None and len(out) == n:
                    break
            return out

        # the never-evicted serial reference for this rank's shard
        with DevicePrefetcher(rank_reader(), depth=1,
                              workers=1) as pf:
            gold = trace(pf)
        assert gold == list(range(1, 21, 3))      # keys[1::3]

        # rank trains 3 batches through the 4-worker pool, is evicted
        # (close), re-joins with skip=cursor: the tail must butt-join
        cursor = 3
        with DevicePrefetcher(rank_reader(), depth=2, workers=4) as pf:
            head = trace(pf, n=cursor)
        with DevicePrefetcher(rank_reader(), depth=2, workers=4,
                              skip=cursor) as pf:
            tail = trace(pf)
        assert head + tail == gold                # zero replay, no holes
        assert len(set(head + tail)) == len(gold)


# ---------------------------------------------------------------------------
# elastic membership
# ---------------------------------------------------------------------------

class TestElastic:
    def _pair(self, timeout=1.0):
        g0 = ElasticGroup(rank=0, sync_timeout_s=timeout)
        g1 = ElasticGroup(rank=1, addr=g0.addr, sync_timeout_s=timeout)
        g0.join()
        g1.join()
        return g0, g1

    def test_evict_on_deadline_and_survivor_continues(self):
        g0, g1 = self._pair()
        try:
            out = {}

            def run(g, steps, die_at=None):
                v = np.full(3, float(g.rank + 1), np.float32)
                hist = []
                for s in range(1, steps + 1):
                    if die_at == s:
                        return
                    mean, info = g.sync(s, v)
                    hist.append((s, float(mean[0]), info["generation"],
                                 tuple(info["departed"])))
                out[g.rank] = hist

            t0 = threading.Thread(target=run, args=(g0, 3))
            t1 = threading.Thread(target=run, args=(g1, 3, 2))
            t0.start(); t1.start(); t0.join(); t1.join()
            hist = out[0]
            assert hist[0] == (1, 1.5, 1, ())       # both contributed
            assert hist[1][3] == (1,)               # eviction observed
            assert hist[2] == (3, 1.0, 2, ())       # solo, new gen
        finally:
            g0.leave()

    def test_graceful_leave_is_not_a_departure(self):
        g0, g1 = self._pair()
        try:
            done = threading.Event()

            def r1():
                g1.sync(1, np.zeros(2, np.float32))
                g1.leave()
                done.set()

            t = threading.Thread(target=r1)
            t.start()
            g0.sync(1, np.zeros(2, np.float32))
            t.join()
            assert done.wait(5)
            _, info = g0.sync(2, np.zeros(2, np.float32))
            assert info["membership_changed"]
            assert info["left"] == [1]
            assert info["departed"] == []           # no rollback cue
        finally:
            g0.leave()

    def test_rejoin_waits_for_checkpoint_boundary(self):
        g0 = ElasticGroup(rank=0, sync_timeout_s=1.0)
        try:
            g0.join()
            g0.sync(1, np.zeros(2, np.float32))     # group started
            g1 = ElasticGroup(rank=1, addr=g0.addr, sync_timeout_s=1.0)
            # no checkpoint yet: not admitted
            with pytest.raises(TimeoutError):
                g1.join(poll_s=0.05, timeout_s=0.4)
            g0.report_checkpoint(1, "/tmp/ck/step_1")
            j = g1.join(poll_s=0.05, timeout_s=5)
            assert j["admitted"] and j["last_good"]["step"] == 1
            assert j["next_step"] == 2
        finally:
            g0.leave()

    def test_ahead_member_never_evicted_from_stale_round(self):
        g0 = ElasticGroup(rank=0, sync_timeout_s=1.0)
        try:
            g0.join()
            for s in (1, 2, 3):
                g0.sync(s, np.full(2, 10.0, np.float32))
            g0.report_checkpoint(3, "/tmp/ck/step_3")
            g1 = ElasticGroup(rank=1, addr=g0.addr, sync_timeout_s=1.0)
            g1.join()
            # a lagging joiner replaying round 2 (stale): rank 0 already
            # synced past it — the round must complete WITHOUT waiting
            # out the deadline and WITHOUT evicting rank 0
            t0 = time.perf_counter()
            mean, info = g1.sync(2, np.full(2, 20.0, np.float32))
            assert time.perf_counter() - t0 < 0.9
            assert 0 in info["members"]
            assert float(mean[0]) == 15.0   # rank 0's round-2 vec kept
        finally:
            g0.leave()

    def test_evicted_rank_must_rejoin(self):
        g0, g1 = self._pair(timeout=0.5)
        try:
            g0.sync(1, np.zeros(2, np.float32))     # evicts silent g1
            with pytest.raises(RuntimeError, match="not a member"):
                g1.sync(2, np.zeros(2, np.float32))
        finally:
            g0.leave()


# ---------------------------------------------------------------------------
# tooling: trace_check, perf_regress, mxdiag recover
# ---------------------------------------------------------------------------

class TestTooling:
    def test_resilience_families_enforced(self):
        tc = _load_tool("trace_check")
        ok = {"resilience/resilience.rollbacks": "counter",
              "resilience/resilience.save_ms": "histogram",
              "resilience/resilience.last_checkpoint_step": "gauge"}
        assert tc.check_healthmon_kinds(ok) == []
        bad_name = {"resilience/resilience.invented": "counter"}
        assert tc.check_healthmon_kinds(bad_name)
        bad_kind = {"resilience/resilience.rollbacks": "gauge"}
        assert tc.check_healthmon_kinds(bad_kind)

    def test_check_resilience_extra_matrix(self):
        tc = _load_tool("trace_check")
        good = {"enabled": True, "checkpoints_saved": 3,
                "last_checkpoint_step": 30, "recoveries_total": 1,
                "rollbacks": 1, "steps_lost_last": 2,
                "steps_lost_total": 2,
                "save": {"count": 3, "p50_ms": 50.0, "p95_ms": 80.0},
                "copy": {"count": 3, "p50_ms": 1.0, "p95_ms": 2.0},
                "every": 10, "keep": 3}
        assert tc.check_resilience_extra(good) == []
        assert tc.check_resilience_extra(None) == []
        assert tc.check_resilience_extra(
            dict(good, rollbacks=-1))
        assert tc.check_resilience_extra(
            dict(good, save={"count": 3, "p50_ms": 90.0,
                             "p95_ms": 80.0}))
        assert tc.check_resilience_extra(
            dict(good, recoveries_total=2, rollbacks=0,
                 resumes=0))      # recovery with no trail
        assert tc.check_resilience_extra(dict(good, keep=0))

    def test_perf_regress_notes_recovery_and_accepts(self, tmp_path):
        pr = _load_tool("perf_regress")
        base = {"metric": "train_throughput", "value": 100.0,
                "unit": "img/s", "extra": {"mfu": 0.1}}
        cand = dict(base, extra={
            "mfu": 0.1,
            "resilience": {"enabled": True, "checkpoints_saved": 2,
                           "recoveries_total": 1, "rollbacks": 1,
                           "steps_lost_last": 4, "steps_lost_total": 4,
                           "save": None, "copy": None}})
        bp, cp = tmp_path / "b.json", tmp_path / "c.json"
        bp.write_text(json.dumps(base))
        cp.write_text(json.dumps(cand))
        b, err = pr.load_artifact(str(bp))
        assert err is None
        c, err = pr.load_artifact(str(cp))
        assert err is None and c["recoveries"] == 1 \
            and c["steps_lost"] == 4
        regs, notes = pr.compare(b, c)
        assert not regs             # a recovered run is USABLE
        assert any("RECOVERED 1 time(s), 4 step(s) lost" in n
                   for n in notes)

    def test_mxdiag_recover_renders_and_flags(self, tmp_path, capsys):
        md = _load_tool("mxdiag")
        ev = tmp_path / "ev.jsonl"

        def rec(ts, kind, name, step=None, args=None):
            d = {"schema": "mxtpu.events/1", "ts": ts, "run_id": "r1",
                 "rank": 0, "step": step, "kind": kind, "name": name}
            if args:
                d["args"] = args
            return json.dumps(d)

        lines = [
            rec(1.0, "lifecycle", "events.open"),
            rec(2.0, "resilience", "resilience.checkpoint_saved",
                step=4, args={"save_ms": 50}),
            rec(3.0, "alert", "healthmon.nan_loss", step=7,
                args={"value": "nan"}),
            rec(3.1, "resilience", "resilience.rollback", step=7,
                args={"from_step": 7, "to_step": 4, "steps_lost": 3,
                      "attempt": 1, "reason": "nan_loss"}),
            rec(4.0, "trainer", "step", step=12),
        ]
        ev.write_text("\n".join(lines) + "\n")
        merged = md.merge_timelines([str(ev)])
        assert md.print_recover(merged) == 0
        out = capsys.readouterr().out
        assert "FAULT" in out and "rollback" in out
        assert "steps_replayed=3" in out
        # an unhandled fault (no action after it) must flag
        ev2 = tmp_path / "ev2.jsonl"
        ev2.write_text("\n".join(lines[:3]) + "\n")
        assert md.print_recover(md.merge_timelines([str(ev2)])) == 1


# ---------------------------------------------------------------------------
# chaos acceptance (subprocess; the ISSUE's tier-1 bar)
# ---------------------------------------------------------------------------

@pytest.mark.serial
def test_chaos_harness_self_heals_through_all_faults(tmp_path):
    """NaN injection, torn checkpoint, frozen rank (stall -> restart),
    and a mid-step rank SIGKILL with elastic re-join: training must run
    to completion with loss DECREASING and >= 1 recovery per fault on
    all three surfaces (counters, flight, events) — asserted by the
    harness itself; re-asserted on the headline here so a weakened
    driver can't silently pass."""
    env = dict(os.environ)
    env["MXTPU_CHAOS_OUT"] = str(tmp_path / "chaos")
    env["MXTPU_CHAOS_STEPS"] = "16"
    env["MXTPU_CHAOS_NAN_BATCH"] = "7"
    env["MXTPU_CHAOS_KILL_STEP"] = "6"
    env["MXTPU_CHAOS_FREEZE_BATCH"] = "6"
    env["MXTPU_CHAOS_CKPT_EVERY"] = "3"
    r = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "chaos_cluster.py")],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, \
        f"chaos failed\nstdout:{r.stdout[-4000:]}\nstderr:{r.stderr[-3000:]}"
    verdicts = [ln for ln in r.stdout.splitlines()
                if ln.startswith("CHAOS_OK ")]
    assert verdicts, f"no CHAOS_OK in {r.stdout[-2000:]}"
    doc = json.loads(verdicts[0][len("CHAOS_OK "):])
    for scenario in ("nan", "torn", "freeze", "kill"):
        assert scenario in doc, f"scenario {scenario} missing: {doc}"
        assert doc[scenario]["losses"]["decreased"], \
            f"{scenario}: loss did not decrease: {doc[scenario]}"
    assert doc["nan"]["rollbacks"] >= 1
    assert doc["torn"]["corrupt_detected"] >= 1
    assert doc["torn"]["resumes"] >= 1
    assert doc["freeze"]["resumes"] >= 1
    assert doc["kill"]["departures"] >= 1
    assert doc["kill"]["joins"] >= 1
    assert os.path.exists(doc["merged_file"])
