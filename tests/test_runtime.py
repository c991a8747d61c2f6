"""Native runtime tests: dependency engine semantics, storage pool,
token queue, DataLoader prefetch pipeline (SURVEY.md §2.4, §2.27)."""
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, runtime
from incubator_mxnet_tpu.gluon.data import ArrayDataset, DataLoader


def test_native_builds():
    assert runtime.native_available(), "C++ runtime failed to build"


def test_failed_native_build_raises_with_compiler_stderr(tmp_path):
    """A compiler that is there and fails is a broken build: loud, with
    g++'s own words — never a quiet switch to the Python engine."""
    with pytest.raises(RuntimeError, match=r"g\+\+ failed.*no_such_source"):
        runtime._build_so("no_such_source.cc", str(tmp_path / "x.so"))
    assert not list(tmp_path.iterdir())          # no temp file left behind


def test_no_compiler_means_python_engine(tmp_path, monkeypatch):
    """Only a machine without g++ gets the pure-Python equivalents."""
    monkeypatch.setenv("PATH", str(tmp_path))
    assert runtime._build_so("runtime.cc", str(tmp_path / "x.so")) is None


class TestEngine:
    def test_write_write_ordering(self):
        eng = runtime.Engine(4)
        v = eng.new_var()
        out = []
        for i in range(50):
            eng.push(lambda i=i: out.append(i), mutable_vars=[v])
        eng.wait_for_var(v)
        assert out == list(range(50))   # writes serialize in program order

    def test_reads_run_concurrently(self):
        eng = runtime.Engine(4)
        v = eng.new_var()
        active = []
        peak = []
        lock = threading.Lock()

        def reader():
            with lock:
                active.append(1)
                peak.append(len(active))
            time.sleep(0.05)
            with lock:
                active.pop()

        for _ in range(4):
            eng.push(reader, const_vars=[v])
        eng.wait_all()
        assert max(peak) > 1            # overlapping readers

    def test_write_waits_for_reads(self):
        eng = runtime.Engine(4)
        v = eng.new_var()
        events = []
        lock = threading.Lock()

        def slow_read():
            time.sleep(0.05)
            with lock:
                events.append("r")

        def write():
            with lock:
                events.append("w")

        eng.push(slow_read, const_vars=[v])
        eng.push(slow_read, const_vars=[v])
        eng.push(write, mutable_vars=[v])
        eng.wait_for_var(v)
        assert events == ["r", "r", "w"]

    def test_independent_vars_parallel(self):
        eng = runtime.Engine(4)
        v1, v2 = eng.new_var(), eng.new_var()
        t0 = time.perf_counter()
        for v in (v1, v2):
            eng.push(lambda: time.sleep(0.1), mutable_vars=[v])
        eng.wait_all()
        assert time.perf_counter() - t0 < 0.19   # ran in parallel

    def test_read_after_write_sees_result(self):
        eng = runtime.Engine(2)
        v = eng.new_var()
        box = {}
        eng.push(lambda: box.__setitem__("x", 42), mutable_vars=[v])
        got = []
        eng.push(lambda: got.append(box.get("x")), const_vars=[v])
        eng.wait_all()
        assert got == [42]

    def test_python_fallback_semantics(self):
        eng = runtime.Engine(4, force_python=True)
        v = eng.new_var()
        out = []
        for i in range(20):
            eng.push(lambda i=i: out.append(i), mutable_vars=[v])
        eng.wait_for_var(v)
        eng.wait_all()
        assert out == list(range(20))

    def test_python_fallback_write_waits_for_reads(self):
        """Regression: a write pushed after reads must wait for them."""
        eng = runtime.Engine(4, force_python=True)
        v = eng.new_var()
        events = []
        lock = threading.Lock()

        def slow_read():
            time.sleep(0.05)
            with lock:
                events.append("r")

        def write():
            with lock:
                events.append("w")

        eng.push(slow_read, const_vars=[v])
        eng.push(slow_read, const_vars=[v])
        eng.push(write, mutable_vars=[v])
        eng.wait_for_var(v)
        assert events == ["r", "r", "w"]

    def test_wait_for_unknown_var_returns(self):
        eng = runtime.Engine(2)
        eng.wait_for_var(999999)   # must not abort/hang

    def test_many_ops_stress(self):
        """Thunk lifetime: thousands of callbacks through the persistent
        dispatcher must not corrupt the process."""
        eng = runtime.Engine(8)
        v = [eng.new_var() for _ in range(8)]
        counter = {"n": 0}
        lock = threading.Lock()

        def bump():
            with lock:
                counter["n"] += 1

        for i in range(4000):
            eng.push(bump, mutable_vars=[v[i % 8]])
        eng.wait_all()
        assert counter["n"] == 4000


class TestStoragePool:
    def test_alloc_free_reuse(self):
        pool = runtime.StoragePool()
        p1 = pool.alloc(1000)
        assert p1
        stats = pool.stats()
        assert stats["bytes_in_use"] == 1024      # rounded to bucket
        pool.free(p1)
        stats = pool.stats()
        assert stats["bytes_in_use"] == 0
        assert stats["bytes_pooled"] == 1024
        p2 = pool.alloc(900)                       # same bucket -> reused
        assert p2 == p1
        assert pool.stats()["bytes_pooled"] == 0
        pool.free(p2)

    def test_double_free_ignored(self):
        pool = runtime.StoragePool()
        p = pool.alloc(64)
        pool.free(p)
        pool.free(p)                               # no crash, no double count
        assert pool.stats()["bytes_pooled"] == 256


class TestTokenQueue:
    def test_fifo_and_len(self):
        q = runtime.TokenQueue(8)
        for i in range(5):
            assert q.push(i)
        assert len(q) == 5
        assert [q.pop() for _ in range(5)] == list(range(5))

    def test_bounded_blocking_push(self):
        q = runtime.TokenQueue(2)
        q.push(0)
        q.push(1)
        state = {"pushed": False}

        def producer():
            q.push(2)                              # blocks until a pop
            state["pushed"] = True

        t = threading.Thread(target=producer)
        t.start()
        time.sleep(0.05)
        assert not state["pushed"]                 # still blocked (full)
        assert q.pop() == 0
        t.join(timeout=2)
        assert state["pushed"]

    def test_close_unblocks(self):
        q = runtime.TokenQueue(1)
        got = []

        def consumer():
            got.append(q.pop())

        t = threading.Thread(target=consumer)
        t.start()
        time.sleep(0.02)
        q.close()
        t.join(timeout=2)
        assert got == [None]
        assert q.push(7) is False                  # closed


class TestDataLoaderPrefetch:
    def _ds(self, n=64):
        x = np.arange(n * 4, dtype=np.float32).reshape(n, 4)
        y = np.arange(n, dtype=np.int32)
        return ArrayDataset(x, y)

    def test_ordered_and_complete(self):
        dl = DataLoader(self._ds(), batch_size=8, num_workers=3)
        seen = [b[1].asnumpy() for b in dl]
        np.testing.assert_array_equal(np.concatenate(seen), np.arange(64))

    def test_matches_sequential(self):
        ds = self._ds(40)
        seq = [b[0].asnumpy() for b in DataLoader(ds, batch_size=8)]
        par = [b[0].asnumpy() for b in
               DataLoader(ds, batch_size=8, num_workers=4)]
        for a, b in zip(seq, par):
            np.testing.assert_array_equal(a, b)

    def test_early_break_does_not_hang(self):
        dl = DataLoader(self._ds(), batch_size=4, num_workers=2, prefetch=2)
        it = iter(dl)
        next(it)
        it.close()                                  # generator close path

    def test_worker_exception_propagates(self):
        class Bad:
            def __len__(self):
                return 8

            def __getitem__(self, i):
                if i == 5:
                    raise RuntimeError("boom")
                return np.zeros(2, np.float32)

        dl = DataLoader(Bad(), batch_size=4, num_workers=2)
        with pytest.raises(RuntimeError, match="boom"):
            list(dl)


def test_engine_push_error_propagates_to_wait():
    """An exception inside a pushed op must not vanish in the callback
    trampoline: it re-raises from wait_for_var(var) and wait_all()."""
    eng = runtime.Engine(num_threads=2)
    v = eng.new_var()

    def bad():
        raise ValueError("engine-op-boom")

    eng.push(bad, mutable_vars=[v])
    with pytest.raises(ValueError, match="engine-op-boom"):
        eng.wait_for_var(v)

    eng.push(bad, mutable_vars=[v])
    with pytest.raises(ValueError, match="engine-op-boom"):
        eng.wait_all()
    # errors are consumed once raised; subsequent waits are clean
    eng.wait_all()

    # unrelated vars don't see the error
    eng.push(bad, mutable_vars=[v])
    other = eng.new_var()
    eng.push(lambda: None, mutable_vars=[other])
    eng.wait_for_var(other)
    with pytest.raises(ValueError):
        eng.wait_all()


def test_features_pallas_flag_reflects_ops():
    from incubator_mxnet_tpu.ops import pallas
    feats = runtime.Features()
    assert feats.is_enabled("PALLAS") == bool(pallas.enabled())


def test_prefetch_window_is_bounded():
    """A straggler first batch must not let completed batches pile up past
    the prefetch window."""
    import time as _t
    peak = {"inflight": 0, "n": 0}
    lock = threading.Lock()

    class SlowFirst:
        def __len__(self):
            return 64

        def __getitem__(self, i):
            with lock:
                peak["n"] += 1
                peak["inflight"] = max(peak["inflight"], peak["n"])
            if i == 0:
                _t.sleep(0.3)
            with lock:
                peak["n"] -= 1
            return np.zeros(2, np.float32)

    dl = DataLoader(SlowFirst(), batch_size=4, num_workers=4, prefetch=3)
    list(dl)
    # in-flight batches bounded by prefetch window (x batch items)
    assert peak["inflight"] <= 3 * 4 + 4, peak


def test_engine_module_surface():
    from incubator_mxnet_tpu import engine
    assert engine.engine_type() in ("native", "python")
    v = engine.new_var()
    out = []
    engine.push(lambda: out.append(1), mutable_vars=[v])
    engine.wait_for_var(v)
    assert out == [1]
    engine.wait_all()
