"""mxtpu.serving — AOT-compiled inference with dynamic batching.

Covers the acceptance surface of the serving subsystem: FrozenModel
bit-exactness and bucket policy, the batcher's admission-control edge
cases (deadline expiry is a REJECTION not a silent drop, oversized /
mistyped inputs are clean client errors, queue-full backpressure fails
fast, graceful drain completes accepted work), the HTTP front end with
concurrent clients demonstrably coalescing, and the telemetry contract
(counters + latency histograms visible to the exporters and the flight
recorder with zero extra wiring).
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd, serving
from incubator_mxnet_tpu import profiler as prof
from incubator_mxnet_tpu.serving import (DeadlineExceededError,
                                         DynamicBatcher, FrozenModel,
                                         InvalidInputError, ModelServer,
                                         QueueFullError, ServerClosedError)


def _mlp(in_units=6, out=3, seed=0):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, in_units=in_units, activation="relu"),
            gluon.nn.Dense(out, in_units=16))
    net.initialize(init=mx.init.Xavier())
    rng = np.random.RandomState(seed)
    for p in net.collect_params().values():
        p.set_data(nd.array(rng.randn(*p.shape).astype(np.float32) * 0.1))
    return net


@pytest.fixture
def frozen():
    return FrozenModel(_mlp(), input_shape=(6,), batch_buckets=(1, 2, 4, 8))


# ---------------------------------------------------------------------------
# FrozenModel
# ---------------------------------------------------------------------------

def assert_same_program_output(got, ref):
    """Two DIFFERENT XLA programs over the same weights (another batch
    size, another fusion): what the compiler can promise is float32
    resolution, not the same bits (seen on jax 0.9.0: 1.5e-8 absolute)."""
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)


def test_frozen_precompiles_every_bucket_and_matches_eager(frozen):
    net = _mlp()          # same seeded params as the fixture's source
    net_h = _mlp()
    net_h.hybridize()
    assert set(frozen._exec) == {1, 2, 4, 8}
    for n in (1, 3, 5, 8):
        x = np.random.RandomState(n).randn(n, 6).astype(np.float32)
        out = frozen(x).asnumpy()
        # the bucket's program pads the batch, the hybridized forward and
        # per-op eager do not: three programs, float32 resolution
        assert_same_program_output(out, net_h(nd.array(x)).asnumpy())
        assert_same_program_output(out, net(nd.array(x)).asnumpy())


def test_frozen_padding_rows_do_not_leak_into_real_rows(frozen):
    x = np.random.RandomState(1).randn(3, 6).astype(np.float32)
    padded = frozen.predict_batch(x)[0]              # bucket 4, 1 pad row
    exact = frozen.predict_batch(
        np.concatenate([x, np.random.RandomState(9).randn(1, 6)
                        .astype(np.float32)]))[0][:3]  # same bucket, junk row
    np.testing.assert_array_equal(padded, exact)


def test_frozen_is_immutable_after_training(frozen):
    x = np.random.RandomState(2).randn(2, 6).astype(np.float32)
    before = frozen(x).asnumpy()
    net = _mlp(seed=0)
    for p in net.collect_params().values():          # "train" the source
        p.set_data(p.data() * 0 + 1)
    np.testing.assert_array_equal(frozen(x).asnumpy(), before)


def test_frozen_bucket_policy(frozen):
    assert frozen.bucket_for(1) == 1
    assert frozen.bucket_for(3) == 4
    assert frozen.bucket_for(8) == 8
    with pytest.raises(InvalidInputError):
        frozen.bucket_for(9)


def test_freeze_handoff_and_env_buckets(monkeypatch):
    monkeypatch.setenv("MXTPU_SERVING_BUCKETS", "1,4")
    fm = _mlp().freeze(input_shape=(6,))
    assert fm.buckets == (1, 4)


def test_frozen_from_exported_checkpoint(tmp_path):
    net = _mlp()
    net.hybridize()
    x = nd.array(np.random.RandomState(3).randn(2, 6).astype(np.float32))
    ref = net(x).asnumpy()
    prefix = str(tmp_path / "served")
    net.export(prefix)
    fm = FrozenModel.from_exported(prefix, input_shape=(6,),
                                   input_name="data",
                                   batch_buckets=(1, 2))
    np.testing.assert_allclose(fm(x).asnumpy(), ref, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# DynamicBatcher admission control
# ---------------------------------------------------------------------------

def test_batcher_coalesces_concurrent_requests(frozen):
    b = DynamicBatcher(frozen, max_delay_ms=50, queue_limit=64).start()
    prof.reset_counters()
    xs = np.random.RandomState(4).randn(12, 6).astype(np.float32)
    results = [None] * 12

    def client(i):
        results[i] = b.predict(xs[i])

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    b.stop()
    stats = b.stats()
    assert stats["serving.responses"] == 12
    assert stats["serving.batches"] < 12          # demonstrably coalesced
    assert stats["batch_fill"] > 1.5
    net = _mlp()
    for i in range(12):
        ref = net(nd.array(xs[i:i + 1])).asnumpy()[0]
        assert_same_program_output(results[i][0], ref)


def test_deadline_expired_requests_rejected_not_dropped(frozen):
    b = DynamicBatcher(frozen, max_delay_ms=1, queue_limit=8)
    # batcher NOT started: requests age in the queue past their deadline
    req = b.submit(np.zeros(6, np.float32), timeout_ms=20)
    time.sleep(0.08)
    b.start()                                     # dispatcher finds it late
    with pytest.raises(DeadlineExceededError):
        req.wait(5.0)
    b.stop()
    assert prof.counters().get("serving/serving.rejected_deadline", 0) >= 1


def test_oversized_input_is_clean_client_error(frozen):
    b = DynamicBatcher(frozen)
    with pytest.raises(InvalidInputError) as ei:
        b.submit(np.zeros((9, 6), np.float32))    # > largest bucket... but
    # a multi-sample array is first rejected as not-a-single-sample
    assert ei.value.code == 400


def test_shape_and_dtype_mismatch_rejected(frozen):
    b = DynamicBatcher(frozen)
    with pytest.raises(InvalidInputError):
        b.submit(np.zeros(7, np.float32))         # wrong shape
    with pytest.raises(InvalidInputError):
        b.submit(np.zeros(6, np.float64))         # wrong dtype
    assert prof.counters().get("serving/serving.requests", 0) >= 0


def test_queue_full_backpressure_fails_fast(frozen):
    b = DynamicBatcher(frozen, queue_limit=4)     # not started: queue holds
    for _ in range(4):
        b.submit(np.zeros(6, np.float32))
    with pytest.raises(QueueFullError) as ei:
        b.submit(np.zeros(6, np.float32))
    assert ei.value.code == 429
    b._closed = True                              # discard quietly
    b._stopped = True


def test_graceful_drain_completes_accepted_requests(frozen):
    b = DynamicBatcher(frozen, max_delay_ms=500, queue_limit=64)
    reqs = [b.submit(np.random.RandomState(i).randn(6).astype(np.float32),
                     timeout_ms=0)               # 0 = no deadline
            for i in range(6)]
    b.start()
    b.stop(drain=True)                            # must serve all six
    for r in reqs:
        out = r.wait(0.1)                         # already fulfilled
        assert out[0].shape == (3,)
    with pytest.raises(ServerClosedError):
        b.submit(np.zeros(6, np.float32))


def test_stop_without_drain_rejects_not_drops(frozen):
    b = DynamicBatcher(frozen, queue_limit=8)
    reqs = [b.submit(np.zeros(6, np.float32)) for _ in range(3)]
    b.stop(drain=False)
    for r in reqs:
        with pytest.raises(ServerClosedError):
            r.wait(1.0)


# ---------------------------------------------------------------------------
# ModelServer (HTTP)
# ---------------------------------------------------------------------------

def _post(url, doc, timeout=30):
    body = json.dumps(doc).encode()
    req = urllib.request.Request(url, data=body,
                                 headers={"Content-Type":
                                          "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_http_server_concurrent_clients_batch_and_bit_exact(frozen):
    prof.reset_counters()
    srv = ModelServer(frozen, max_delay_ms=25, queue_limit=128)
    host, port = srv.start()
    url = f"http://{host}:{port}/predict"
    n = 64
    xs = np.random.RandomState(7).randn(n, 6).astype(np.float32)
    out = [None] * n
    errs = []

    def client(i):
        try:
            _, out[i] = _post(url, {"data": xs[i].tolist()})
        except Exception as e:  # noqa: BLE001
            errs.append((i, repr(e)))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs[:3]
    stats = srv.stats()
    srv.stop()
    # zero dropped; demonstrable coalescing; sane latency telemetry
    assert stats["serving.responses"] == n
    assert stats["batch_fill"] > 1.5, stats
    assert 0 < stats["p50_ms"] <= stats["p95_ms"] <= stats["p99_ms"]
    # bit-exact vs the compiled forward on the SAME batch composition
    # each request was actually served in (batch_id/batch_index report
    # it); eager-per-op is checked at float32 resolution — see the
    # FrozenModel test for why
    net_h = _mlp()
    net_h.hybridize()
    by_batch = {}
    for i in range(n):
        by_batch.setdefault(out[i]["batch_id"], []).append(i)
    for idxs in by_batch.values():
        rows = sorted(idxs, key=lambda i: out[i]["batch_index"])
        xb = xs[rows]
        bucket = frozen.bucket_for(len(rows))
        if bucket != len(rows):
            xb = np.concatenate(
                [xb, np.zeros((bucket - len(rows), 6), np.float32)])
        ref = net_h(nd.array(xb)).asnumpy()
        for pos, i in enumerate(rows):
            got = np.asarray(out[i]["output"], np.float32)
            np.testing.assert_array_equal(got, ref[pos])
    net = _mlp()
    for i in range(0, n, 8):
        ref1 = net(nd.array(xs[i:i + 1])).asnumpy()[0]
        np.testing.assert_allclose(
            np.asarray(out[i]["output"], np.float32), ref1,
            rtol=1e-6, atol=1e-7)
    assert any(o["batch_size"] > 1 for o in out)


def test_http_error_codes_and_healthz(frozen):
    srv = ModelServer(frozen, max_delay_ms=5)
    host, port = srv.start()
    base = f"http://{host}:{port}"
    with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
        doc = json.loads(r.read())
        assert r.status == 200 and doc["status"] == "ok"
        assert doc["buckets"] == [1, 2, 4, 8]
    # malformed body -> 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(f"{base}/predict", {"nope": 1})
    assert ei.value.code == 400
    # wrong shape -> 400 with the taxonomy name
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(f"{base}/predict", {"data": [1.0, 2.0]})
    assert ei.value.code == 400
    assert json.loads(ei.value.read())["error"] == "InvalidInputError"
    # unknown route -> 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(f"{base}/bogus", timeout=10)
    assert ei.value.code == 404
    srv.stop()


def test_http_stats_and_telemetry_flow_through_exporters(frozen):
    from incubator_mxnet_tpu import diagnostics as diag
    from incubator_mxnet_tpu.diagnostics import flight as _flight
    prof.reset_counters()
    diag.enable_flight_recorder(dump_on_crash=False, record_ops=False)
    try:
        srv = ModelServer(frozen, max_delay_ms=5)
        host, port = srv.start()
        for i in range(5):
            _post(f"http://{host}:{port}/predict",
                  {"data": [0.1 * i] * 6})
        with urllib.request.urlopen(f"http://{host}:{port}/stats",
                                    timeout=10) as r:
            stats = json.loads(r.read())
        srv.stop()
        assert stats["serving.responses"] == 5
        assert stats["qps"] > 0
        assert stats["serving.latency_ms"]["count"] == 5
        # Prometheus text: histogram family with cumulative buckets
        text = diag.prometheus_text()
        assert "# TYPE serving_serving_latency_ms histogram" in text
        assert 'serving_serving_latency_ms_bucket{le="+Inf"} 5.0' in text
        # flight dump carries serving events + the histogram snapshot
        path = _flight.dump(reason="test")
        doc = json.load(open(path))
        assert any(e["kind"] == "serving" for e in doc["events"])
        assert doc["counter_kinds"]["serving/serving.latency_ms"] == \
            "histogram"
        assert doc["counters"]["serving/serving.latency_ms"]["count"] == 5
    finally:
        diag.disable_flight_recorder()


# ---------------------------------------------------------------------------
# Histogram kind
# ---------------------------------------------------------------------------

def test_histogram_percentiles_and_snapshot_shape():
    prof.reset_counters()
    h = prof.histogram("t.lat_ms", "serving")
    for v in [1.0] * 50 + [10.0] * 45 + [400.0] * 5:
        h.observe(v)
    s = h.value
    assert s["count"] == 100 and s["buckets"]["+Inf"] == 100
    assert s["min"] == 1.0 and s["max"] == 400.0
    assert s["p50"] <= s["p95"] <= s["p99"] <= 400.0
    assert s["p50"] <= 10.0 and s["p99"] > 10.0
    # registered in the shared registry with its kind
    assert prof.counter_kinds()["serving/t.lat_ms"] == "histogram"
    # a name already registered as a counter cannot become a histogram
    prof.counter("t.plain", "serving").increment()
    with pytest.raises(TypeError):
        prof.histogram("t.plain", "serving")


def test_histogram_concurrent_observe_consistency():
    prof.reset_counters()
    h = prof.histogram("t.conc", "serving")
    n_threads, per = 8, 500

    def work(seed):
        rng = np.random.RandomState(seed)
        for _ in range(per):
            h.observe(float(rng.gamma(2.0, 5.0)))

    ts = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    s = h.value
    assert s["count"] == n_threads * per
    assert s["buckets"]["+Inf"] == n_threads * per


def test_trace_check_validates_serving_artifacts(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "trace_check", "tools/trace_check.py")
    tc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tc)
    prof.reset_counters()
    h = prof.histogram("t.check", "serving")
    for v in (1.0, 5.0, 300.0):
        h.observe(v)
    assert tc.check_histogram_snapshot(h.value) == []
    bad = h.value
    bad["buckets"]["+Inf"] = 99                   # torn snapshot
    assert tc.check_histogram_snapshot(bad)
    # bench-json serving section validation
    good = {"metric": "serving_x", "value": 1.0, "extra": {"serving": {
        "requests": 3, "responses": 3, "batches": 2, "batch_fill": 1.5,
        "p50_ms": 1.0, "p95_ms": 2.0, "p99_ms": 3.0, "qps": 10.0,
        "latency_ms": h.value}}}
    p = tmp_path / "BENCH_serving.json"
    p.write_text(json.dumps(good))
    assert tc.check_bench_json(str(p)) == []
    assert tc.check_file(str(p)) == []            # auto-detected kind
    good["extra"]["serving"]["p99_ms"] = 0.5      # unordered percentiles
    p.write_text(json.dumps(good))
    assert tc.check_bench_json(str(p))


# ---------------------------------------------------------------------------
# deep /healthz (healthmon PR satellite)
# ---------------------------------------------------------------------------

def _get_healthz(base):
    try:
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_deep_healthz_reports_checks_when_healthy(frozen):
    srv = ModelServer(frozen, max_delay_ms=2)
    host, port = srv.start()
    base = f"http://{host}:{port}"
    try:
        code, doc = _get_healthz(base)
        assert code == 200 and doc["status"] == "ok"
        checks = doc["checks"]
        assert checks["batcher_alive"] is True
        assert checks["queue_depth"] == 0
        assert checks["queue_limit"] == srv.batcher.queue_limit
        assert checks["queue_saturation"] == 0.0
        assert checks["last_predict_age_s"] is None   # no traffic yet
        assert checks["healthmon"]["enabled"] is False
        # after a predict the freshness age becomes a small number
        _post(base + "/predict", {"data": np.zeros(6).tolist()})
        code, doc = _get_healthz(base)
        assert code == 200
        age = doc["checks"]["last_predict_age_s"]
        assert age is not None and 0 <= age < 10
    finally:
        srv.stop()


def test_deep_healthz_503_when_dispatcher_dead(frozen):
    srv = ModelServer(frozen, max_delay_ms=2)
    host, port = srv.start()
    base = f"http://{host}:{port}"
    try:
        # kill the dispatcher thread without marking the server draining
        # — exactly the wedge a load balancer must be able to see
        srv.batcher.stop(drain=True)
        srv._draining = False
        code, doc = _get_healthz(base)
        assert code == 503 and doc["status"] == "degraded"
        assert "batcher_dead" in doc["problems"]
    finally:
        srv.stop()


def test_deep_healthz_503_when_queue_saturated(frozen):
    srv = ModelServer(frozen, max_delay_ms=2, queue_limit=4)
    host, port = srv.start()
    base = f"http://{host}:{port}"
    try:
        # saturate without serving: park requests in the queue with the
        # dispatcher parked (stopped thread, queue left intact)
        srv.batcher._stopped = True
        srv.batcher._thread.join(2)
        for _ in range(4):
            srv.batcher._q.append(object())
        code, doc = _get_healthz(base)
        assert code == 503
        assert "queue_saturated" in doc["problems"]
        assert doc["checks"]["queue_saturation"] >= 1.0
        srv.batcher._q.clear()
    finally:
        srv.stop(drain=False)


def test_deep_healthz_draining_still_503_with_checks(frozen):
    srv = ModelServer(frozen, max_delay_ms=2)
    host, port = srv.start()
    base = f"http://{host}:{port}"
    try:
        srv._draining = True
        code, doc = _get_healthz(base)
        assert code == 503 and doc["status"] == "draining"
        assert "checks" in doc            # deep info even while draining
    finally:
        srv._draining = False
        srv.stop()


def test_deep_healthz_reports_healthmon_watchdog_status(frozen):
    from incubator_mxnet_tpu import healthmon as hm
    from incubator_mxnet_tpu.profiler.counters import reset_counters
    srv = ModelServer(frozen, max_delay_ms=2)
    host, port = srv.start()
    base = f"http://{host}:{port}"
    try:
        import tempfile
        mon = hm.enable(hm_dir=tempfile.mkdtemp(), stall_timeout_s=0)
        mon.observe_loss(float("nan"))
        code, doc = _get_healthz(base)
        # training-side alerts are REPORTED, not a routing failure
        assert code == 200
        assert doc["checks"]["healthmon"]["enabled"] is True
        assert doc["checks"]["healthmon"]["nan_alerts"] == 1
    finally:
        hm.disable()
        reset_counters()
        srv.stop()


def test_serving_batches_emit_structured_events(frozen, tmp_path):
    from incubator_mxnet_tpu import healthmon as hm
    mon = hm.enable(hm_dir=str(tmp_path), stall_timeout_s=0)
    b = DynamicBatcher(frozen, max_delay_ms=2).start()
    try:
        b.predict(np.zeros(6, np.float32))
    finally:
        b.stop()
        hm.disable()
    recs = [json.loads(ln) for ln in open(mon.events.path)
            if ln.strip()]
    batch = [r for r in recs if r["name"] == "serving.batch"]
    assert batch and batch[0]["kind"] == "serving"
    assert batch[0]["args"]["n"] == 1
