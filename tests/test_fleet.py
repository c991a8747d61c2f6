"""mxtpu.fleet — continuous batching, quantized/sharded FrozenModel,
and the replica fleet.

Covers the fleet acceptance surface: iteration-level (slot-based)
admission with the ``slotted`` span mark and the full rejection
taxonomy preserved, the stop(drain=True) admission race (a queued
request must settle with ServerClosedError, never hang), int8/bf16
quantized parity bounds per bucket, mesh-sharded bucket compiles that
are provably resharding-clean (and the ReshardingGateError surface),
the shared on-disk CompileCache (replica N+1 skips the XLA compile),
the Router's least-loaded dispatch + zero-drop draining deploy, and
the fleet halves of the tooling contract (merge_serving_stats,
check_fleet_extra).

Everything here is in-process and CPU-only; the spawned-worker
multi-process path is exercised end to end by tools/fleet_smoke.sh.
"""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd, servescope
from incubator_mxnet_tpu import profiler as prof
from incubator_mxnet_tpu.fleet import (CompileCache, ContinuousBatcher,
                                       ReplicaSet, Router)
from incubator_mxnet_tpu.parallel import make_mesh
from incubator_mxnet_tpu.serving import (DeadlineExceededError, FrozenModel,
                                         ModelServer, QueueFullError,
                                         ReshardingGateError,
                                         ServerClosedError)


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


@pytest.fixture
def armed():
    """Servescope armed (sample=1: every request gets a span)."""
    servescope.enable()
    yield servescope._SS
    servescope.disable()


def _load_tool(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, f"tools/{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _post(url, doc, timeout=30):
    body = json.dumps(doc).encode()
    req = urllib.request.Request(url, data=body,
                                 headers={"Content-Type":
                                          "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


class _Blocked:
    """Hold the frozen model's exec open so the continuous batcher is
    provably mid-flight while we admit more requests."""

    def __init__(self, frozen_model):
        self.entered = threading.Event()
        self.release = threading.Event()
        orig = frozen_model.predict_batch

        def slow(x, timings=None):
            self.entered.set()
            assert self.release.wait(10), "test never released the exec"
            return orig(x, timings=timings)

        frozen_model.predict_batch = slow


# ---------------------------------------------------------------------------
# ContinuousBatcher — iteration-level scheduling
# ---------------------------------------------------------------------------

def test_continuous_batcher_serves_correct_results(frozen):
    prof.reset_counters()
    b = ContinuousBatcher(frozen, queue_limit=32).start()
    try:
        xs = np.random.RandomState(7).randn(8, 6).astype(np.float32)
        results = [None] * 8

        def client(i):
            results[i] = b.predict(xs[i])

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        want = frozen.predict_batch(xs)[0]
        got = np.stack([r[0] for r in results])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    finally:
        b.stop(drain=True)


def test_midflight_admission_is_slotted_and_counted(frozen, armed):
    prof.reset_counters()
    gate = _Blocked(frozen)
    b = ContinuousBatcher(frozen, queue_limit=8,
                          default_timeout_ms=10_000).start()
    try:
        first = b.submit(np.zeros(6, np.float32))
        assert gate.entered.wait(10)     # iteration 1 is on the device
        # admitted while a dispatch is in flight: rides the NEXT
        # iteration's slots, span stamped, counter incremented
        mid = b.submit(np.ones(6, np.float32))
        assert mid.span is not None and mid.span.slotted
        assert first.span is not None and not first.span.slotted
        assert prof.counters().get(
            "serving/serving.slotted_admissions", 0) == 1
        gate.release.set()
        first.wait(timeout=10)
        out = mid.wait(timeout=10)
        want = frozen.predict_batch(
            np.ones((1, 6), np.float32))[0][0]
        np.testing.assert_allclose(out[0], want, rtol=1e-5, atol=1e-6)
    finally:
        gate.release.set()
        b.stop(drain=True)


def test_midflight_admissions_keep_rejection_taxonomy(frozen, armed):
    """Slotted requests still go through the base class's admission
    control unchanged: deadline expiry is a rejection (not a silent
    drop) and queue-limit backpressure fails fast."""
    prof.reset_counters()
    gate = _Blocked(frozen)
    b = ContinuousBatcher(frozen, queue_limit=2,
                          default_timeout_ms=10_000).start()
    try:
        b.submit(np.zeros(6, np.float32))
        assert gate.entered.wait(10)
        ok = b.submit(np.ones(6, np.float32))                 # queued: 1
        doomed = b.submit(np.ones(6, np.float32),
                          timeout_ms=1)                       # queued: 2
        assert ok.span.slotted and doomed.span.slotted
        with pytest.raises(QueueFullError):                   # queued: full
            b.submit(np.ones(6, np.float32))
        time.sleep(0.01)                  # let doomed's 1 ms deadline pass
        gate.release.set()
        ok.wait(timeout=10)
        with pytest.raises(DeadlineExceededError):
            doomed.wait(timeout=10)
        c = prof.counters()
        assert c.get("serving/serving.rejected_deadline", 0) >= 1
        assert c.get("serving/serving.rejected_queue_full", 0) >= 1
        assert c.get("serving/serving.slotted_admissions", 0) == 2
    finally:
        gate.release.set()
        b.stop(drain=True)


@pytest.mark.parametrize("kind", ["dynamic", "continuous"])
def test_stop_drain_race_settles_queued_requests(frozen, armed, kind):
    """The drain race pin: a request admitted before stop(drain=True)
    whose dispatcher never runs again must settle promptly with
    ServerClosedError and a settled span — never hang. The
    never-started batcher is the deterministic worst case (there is no
    dispatcher at all to flush the queue)."""
    from incubator_mxnet_tpu.serving import DynamicBatcher
    prof.reset_counters()
    cls = DynamicBatcher if kind == "dynamic" else ContinuousBatcher
    b = cls(frozen)                       # never started, on purpose
    req = b.submit(np.zeros(6, np.float32))
    t0 = time.perf_counter()
    b.stop(drain=True, timeout=2.0)
    with pytest.raises(ServerClosedError):
        req.wait(timeout=2.0)
    assert time.perf_counter() - t0 < 2.0, \
        "queued request hung across stop(drain=True)"
    assert prof.counters().get("serving/serving.rejected_closed", 0) >= 1


# ---------------------------------------------------------------------------
# FrozenModel.quantize — int8 / bf16 parity per bucket
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,bound", [("bf16", 1e-2), ("int8", 5e-2)])
def test_quantize_parity_bounds_per_bucket(frozen, mode, bound):
    q = frozen.quantize(mode)
    assert q.buckets == frozen.buckets
    for n in frozen.buckets:
        x = np.random.RandomState(n).randn(n, 6).astype(np.float32)
        ref = frozen.predict_batch(x)[0]
        got = q.predict_batch(x)[0]
        assert got.dtype == ref.dtype     # request/response dtype untouched
        maxdiff = float(np.max(np.abs(got - ref)))
        assert maxdiff < bound, \
            f"{mode} bucket {n}: maxdiff {maxdiff} vs float32 " \
            f"exceeds {bound}"


def test_quantize_rejects_unknown_mode(frozen):
    with pytest.raises(ValueError, match="int8.*bf16|bf16.*int8"):
        frozen.quantize("fp4")


# ---------------------------------------------------------------------------
# Sharded FrozenModel — resharding-clean serve path
# ---------------------------------------------------------------------------

def test_sharded_buckets_compile_resharding_clean():
    """A dp-sharded FrozenModel passes the reshard gate at freeze time
    and its commscope verdict proves zero resharding collectives in
    every compiled bucket (the accidental-all-gather catastrophe the
    gate exists to catch)."""
    mesh = make_mesh({"dp": -1})          # all 8 fake CPU devices
    net = _mlp()
    fm = FrozenModel(net, input_shape=(6,), batch_buckets=(1, 8),
                     mesh=mesh)           # reshard_gate=True default
    verdicts = fm.comm_verdicts()
    assert set(verdicts) == {"1", "8"}, \
        "commscope never captured the sharded bucket compiles"
    for b, v in verdicts.items():
        assert v.get("resharding_collectives") == 0, \
            f"bucket {b} compiled with resharding collectives: {v}"
    # sharded numerics match the unsharded float32 reference
    ref = FrozenModel(_mlp(), input_shape=(6,), batch_buckets=(1, 8))
    x = np.random.RandomState(3).randn(8, 6).astype(np.float32)
    np.testing.assert_allclose(fm.predict_batch(x)[0],
                               ref.predict_batch(x)[0],
                               rtol=1e-5, atol=1e-5)


def test_reshard_gate_refuses_flagged_layout():
    mesh = make_mesh({"dp": -1})
    fm = FrozenModel(_mlp(), input_shape=(6,), batch_buckets=(1,),
                     mesh=mesh)
    fm.comm_verdicts = lambda: {"1": {"resharding_collectives": 3,
                                      "hlo_available": True}}
    with pytest.raises(ReshardingGateError, match="resharding"):
        fm._check_reshard_gate()


# ---------------------------------------------------------------------------
# CompileCache — replica N+1 skips the XLA compile
# ---------------------------------------------------------------------------

def test_compile_cache_miss_then_hit(tmp_path):
    prof.reset_counters()
    cache = CompileCache(str(tmp_path / "aot"))
    buckets = (1, 4)
    m1 = FrozenModel(_mlp(), input_shape=(6,), batch_buckets=buckets,
                     compile_cache=cache)
    c = prof.counters()
    assert c.get("fleet/fleet.compile_cache_misses", 0) == len(buckets)
    assert c.get("fleet/fleet.compile_cache_stores", 0) == len(buckets)
    assert c.get("fleet/fleet.compile_cache_hits", 0) == 0
    assert cache.entries() == len(buckets)
    # replica N+1: same arch, same buckets — every warmup is a hit
    m2 = FrozenModel(_mlp(), input_shape=(6,), batch_buckets=buckets,
                     compile_cache=cache)
    c = prof.counters()
    assert c.get("fleet/fleet.compile_cache_hits", 0) == len(buckets)
    assert c.get("fleet/fleet.compile_cache_misses", 0) == len(buckets)
    x = np.random.RandomState(0).randn(4, 6).astype(np.float32)
    np.testing.assert_array_equal(m1.predict_batch(x)[0],
                                  m2.predict_batch(x)[0])


# ---------------------------------------------------------------------------
# ReplicaSet + Router — least-loaded dispatch, draining deploys
# ---------------------------------------------------------------------------

def _factory(compile_cache=None):
    return FrozenModel(_mlp(), input_shape=(6,), batch_buckets=(1, 2, 4),
                       compile_cache=compile_cache)


@pytest.fixture
def fleet(tmp_path):
    prof.reset_counters()
    rset = ReplicaSet(_factory, n=2, batcher="continuous",
                      compile_cache=CompileCache(str(tmp_path / "aot")),
                      server_kwargs={"max_delay_ms": 0.0})
    rset.start()
    router = Router(rset, poll_interval_s=10.0)
    host, port = router.start()
    yield rset, router, f"http://{host}:{port}"
    router.stop()
    rset.stop(drain=False)


def test_router_dispatches_across_replicas_and_tags_reply(fleet):
    rset, router, base = fleet
    x = np.zeros(6, np.float32).tolist()
    seen = set()
    for _ in range(8):
        status, doc = _post(f"{base}/predict", {"data": x})
        assert status == 200
        seen.add(doc["replica"])
    assert seen == {"replica0", "replica1"}, \
        f"least-loaded dispatch never balanced: {seen}"
    stats = router.stats()
    assert stats["fleet.routed"] >= 8
    assert stats["dispatch_imbalance"] >= 1.0
    # shared cache: replica 1's warmup was a hit, not a recompile
    c = prof.counters()
    assert c.get("fleet/fleet.compile_cache_hits", 0) >= 3


def test_router_routes_around_draining_replica(fleet):
    rset, router, base = fleet
    rep0 = router.replicas[0]
    assert router.drain(rep0, timeout=10.0)
    x = np.zeros(6, np.float32).tolist()
    for _ in range(4):
        status, doc = _post(f"{base}/predict", {"data": x})
        assert status == 200
        assert doc["replica"] == "replica1"
    router.readmit(rep0)
    seen = {_post(f"{base}/predict", {"data": x})[1]["replica"]
            for _ in range(8)}
    assert "replica0" in seen


def test_deploy_swaps_every_replica_with_zero_drops(fleet, tmp_path):
    rset, router, base = fleet
    stop = threading.Event()
    failures = []
    x = np.zeros(6, np.float32).tolist()

    def client():
        while not stop.is_set():
            try:
                status, doc = _post(f"{base}/predict", {"data": x},
                                    timeout=30)
                if status != 200:
                    failures.append(doc)
            except Exception as e:  # noqa: BLE001
                failures.append(repr(e))

    threads = [threading.Thread(target=client) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.2)           # traffic flowing before the deploy
        router.deploy(_factory, compile_cache=rset.compile_cache,
                      timeout=30.0)
        time.sleep(0.2)           # traffic flowing after the deploy
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not failures, f"deploy dropped/errored requests: {failures[:3]}"
    c = prof.counters()
    assert c.get("fleet/fleet.drains", 0) == 2
    assert c.get("fleet/fleet.swaps", 0) == 2
    assert c.get("fleet/fleet.readmits", 0) == 2


@pytest.mark.parametrize("holds,why", [
    (False, "2 workers on a host where the first takes all 1 chip"),
    (True, "this process already holds the chips")])
def test_spawned_workers_refused_where_they_cannot_have_a_chip(
        monkeypatch, holds, why):
    """One process per chip: on a TPU host spawned replicas would hang on
    their ready line for ten minutes — refused before anything spawns."""
    from incubator_mxnet_tpu import context
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(context, "holds_accelerator", lambda: holds)
    monkeypatch.setattr(context, "devices_seen_by_a_child",
                        lambda: ("tpu", "TPU v5 lite", 1))
    rset = ReplicaSet({"model": "lenet", "input_shape": [1, 28, 28]},
                      n=2, spawn=True)
    monkeypatch.setattr(rset, "_spawn_one", lambda *a, **k: pytest.fail(
        "a worker was spawned"))
    with pytest.raises(RuntimeError, match="one process at a time") as ei:
        rset.start()
    assert why in str(ei.value) and "spawn=False" in str(ei.value)


def test_model_server_continuous_batcher_knob(frozen):
    srv = ModelServer(frozen, batcher="continuous")
    assert isinstance(srv.batcher, ContinuousBatcher)
    assert srv.stats()["batcher"] == "continuous"
    with pytest.raises(ValueError, match="batcher"):
        ModelServer(frozen, batcher="clairvoyant")


# ---------------------------------------------------------------------------
# Tooling contract — merge_serving_stats, check_fleet_extra
# ---------------------------------------------------------------------------

def _snap(requests, lat_buckets, count, total):
    return {"serving.requests": requests, "serving.batches": requests,
            "serving.batched_requests": requests,
            "serving.latency_ms": {"count": count, "sum": total,
                                   "min": 1.0, "max": 50.0,
                                   "p50": 5.0, "p95": 20.0, "p99": 40.0,
                                   "buckets": lat_buckets}}


def test_merge_serving_stats_sums_counters_and_merges_histograms():
    sl = _load_tool("serve_load")
    a = _snap(10, {"5": 6, "25": 9, "100": 10, "+Inf": 10}, 10, 80.0)
    b = _snap(30, {"5": 10, "25": 25, "100": 30, "+Inf": 30}, 30, 400.0)
    merged = sl.merge_serving_stats([a, b])
    assert merged["serving.requests"] == 40
    h = merged["serving.latency_ms"]
    assert h["count"] == 40 and h["sum"] == 480.0
    assert h["min"] == 1.0 and h["max"] == 50.0
    assert h["buckets"] == {"5": 16, "25": 34, "100": 40, "+Inf": 40}
    # percentiles re-estimated from MERGED buckets, ordered
    assert h["p50"] <= h["p95"] <= h["p99"]
    assert h["p50"] == 25.0      # rank 20 of 40: cum 16@5 < 20 <= 34@25
    assert h["p99"] == 100.0     # rank 40 of 40 lands in the last bucket
    assert merged["batch_fill"] == 1.0


def test_check_fleet_extra_schema():
    tc = _load_tool("trace_check")
    good = {"replicas": 2,
            "per_replica": [
                {"name": "replica0", "requests": 40, "qps": 100.0,
                 "p50_ms": 4.0, "p95_ms": 9.0, "p99_ms": 12.0},
                {"name": "replica1", "requests": 38, "qps": 95.0,
                 "p50_ms": 4.1, "p95_ms": 9.3, "p99_ms": 13.0}],
            "dispatch_imbalance": 1.03, "routed": 78,
            "routed_errors": 0, "no_replica_available": 0}
    assert tc.check_fleet_extra(good) == []
    assert tc.check_fleet_extra(None) == []

    bad = dict(good, replicas=3)
    assert any("per_replica has 2 rows" in e
               for e in tc.check_fleet_extra(bad))
    bad = dict(good, routed=10)
    assert any("lost accounting" in e for e in tc.check_fleet_extra(bad))
    bad = dict(good, dispatch_imbalance=0.5)
    assert any("dispatch_imbalance" in e
               for e in tc.check_fleet_extra(bad))
    unordered = json.loads(json.dumps(good))
    unordered["per_replica"][0]["p50_ms"] = 99.0
    assert any("ordered" in e for e in tc.check_fleet_extra(unordered))
