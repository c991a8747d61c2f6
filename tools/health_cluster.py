#!/usr/bin/env python
"""Healthmon 2-process cluster exercise: the acceptance harness for
cross-rank training health (the tier-1 test
tests/test_healthmon_cluster.py asserts on its output).

Parent mode (default): spawns a REAL 2-process jax cluster over loopback
gloo (the same bootstrap tests/test_multihost_real.py exercises), with

* an injected straggler — rank 1 sleeps ``MXTPU_HM_TEST_SLEEP_MS``
  (default 80) before every forward, and
* an injected NaN — rank 0's observed loss is NaN at step
  ``MXTPU_HM_NAN_STEP`` (default 7),

then asserts the healthmon contract end to end:

* ``healthmon.collective_skew_ms`` reports the injected skew and
  ``healthmon.slowest_rank`` attributes it to rank 1 on EVERY rank
  (the verdict is computed from the exchanged table, so fast ranks
  know who is slow);
* the NaN raised a watchdog alert (counter + flight event + structured
  log record) on rank 0;
* each rank's ``mxtpu.events/1`` log and flight dump validate, and
  ``mxdiag merge`` interleaves them into one cross-rank timeline that
  shows both ranks, the skew report, and the NaN alert.

Worker mode (``--worker PID NPROC PORT STEPS``): one rank of the
cluster — tiny dense model, gluon.Trainer over a ``dist_sync`` kvstore
(so every step runs a real cross-process collective), healthmon armed
with a 5-step exchange cadence and the every-3-steps grad-norm sentinel.

Exit 0 iff every assertion holds; prints ``HEALTH_SMOKE_OK {json}``.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)

STEPS = int(os.environ.get("MXTPU_HM_TEST_STEPS", "20"))
SLEEP_MS = float(os.environ.get("MXTPU_HM_TEST_SLEEP_MS", "80"))
NAN_STEP = int(os.environ.get("MXTPU_HM_NAN_STEP", "7"))
WORKER_TIMEOUT_S = int(os.environ.get("MXTPU_TEST_WORKER_TIMEOUT", "420"))


# ---------------------------------------------------------------------------
# worker
# ---------------------------------------------------------------------------

def worker(pid: int, nproc: int, port: str, steps: int) -> None:
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu import diagnostics as diag
    from incubator_mxnet_tpu.profiler.counters import counters

    out_dir = os.environ["MXTPU_HM_OUT"]
    mx.distributed.init(coordinator_address=f"127.0.0.1:{port}",
                        num_processes=nproc, process_id=pid)
    rank = mx.distributed.rank()
    diag.enable_flight_recorder(dump_on_crash=False, dump_dir=out_dir)
    mon = mx.healthmon.enable(hm_dir=out_dir, exchange_every=5,
                              stall_timeout_s=0, grad_norm_every=3)

    np.random.seed(0)
    mx.random.seed(0)
    net = gluon.nn.Dense(4)
    net.initialize(init=mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05}, kvstore="dist_sync")
    L = gluon.loss.L2Loss()
    x = nd.array(np.random.rand(8, 6).astype(np.float32))
    y = nd.array(np.random.rand(8, 4).astype(np.float32))

    for i in range(1, steps + 1):
        if rank == 1 and SLEEP_MS > 0:
            time.sleep(SLEEP_MS / 1e3)   # the injected straggler
        with mx.autograd.record():
            loss = L(net(x), y).mean()
        loss.backward()
        trainer.step(8)
        val = float(loss.asscalar())
        if rank == 0 and i == NAN_STEP:
            val = float("nan")           # the injected divergence
        mx.healthmon.observe_loss(val, step=i)

    flight_path = diag.dump_flight(
        reason="health_worker",
        path=os.path.join(out_dir, f"flight_rank{rank}.json"))
    snap = {k: v for k, v in counters().items()
            if k.startswith("healthmon/")}
    events_path = mon.events.path
    mx.healthmon.disable()
    print("HEALTH " + json.dumps({
        "rank": rank, "counters": snap,
        "events_file": events_path, "flight_file": flight_path}),
        flush=True)
    mx.distributed.barrier()
    mx.distributed.shutdown()
    print("WORKER_DONE", flush=True)


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------

def _free_port() -> int:
    """Coordinator port outside the ephemeral range (see
    tests/test_multihost_real.py for the rationale)."""
    base = 23000 + (os.getpid() * 131) % 500
    for off in range(1000):
        port = 23000 + (base - 23000 + off) % 1000
        s = socket.socket()
        try:
            s.bind(("127.0.0.1", port))
        except OSError:
            continue
        finally:
            s.close()
        return port
    raise RuntimeError("no free coordination port in 23000-23999")


def _load_tool(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main() -> int:
    out_dir = os.environ.get("MXTPU_HM_OUT",
                             "/tmp/mxtpu_health_cluster")
    import shutil
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    port = str(_free_port())
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)       # workers pin their own device count
    env["MXTPU_HM_OUT"] = out_dir
    env.setdefault("MXTPU_RUN_ID", f"health-smoke-{int(time.time())}")
    env.setdefault("MXTPU_INIT_TIMEOUT", "180")

    print(f"health_cluster: 2-proc cluster, {STEPS} steps, "
          f"rank-1 sleep {SLEEP_MS}ms, NaN at step {NAN_STEP} "
          f"-> {out_dir}", flush=True)
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker",
         str(pid), "2", port, str(STEPS)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=_REPO) for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=WORKER_TIMEOUT_S)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rc, out, err in outs:
        if rc != 0:
            print(f"health_cluster: worker failed rc={rc}\n"
                  f"stdout:{out}\nstderr:{err[-3000:]}", file=sys.stderr)
            return 1

    reports = {}
    for _, out, _ in outs:
        for ln in out.splitlines():
            if ln.startswith("HEALTH "):
                doc = json.loads(ln[len("HEALTH "):])
                reports[doc["rank"]] = doc
    assert sorted(reports) == [0, 1], f"missing rank reports: {reports}"

    failures = []

    def check(cond, msg):
        if not cond:
            failures.append(msg)

    sleep_floor = 0.4 * SLEEP_MS
    for rank, doc in sorted(reports.items()):
        c = doc["counters"]
        check(c.get("healthmon/healthmon.steps") == STEPS,
              f"rank {rank}: steps counter {c.get('healthmon/healthmon.steps')} != {STEPS}")
        check(c.get("healthmon/healthmon.exchanges", 0) >= STEPS // 5,
              f"rank {rank}: too few exchanges: {c}")
        skew = c.get("healthmon/healthmon.collective_skew_ms", 0)
        check(skew >= sleep_floor,
              f"rank {rank}: skew {skew}ms < floor {sleep_floor}ms "
              f"(injected {SLEEP_MS}ms)")
        check(c.get("healthmon/healthmon.slowest_rank") == 1,
              f"rank {rank}: slowest_rank "
              f"{c.get('healthmon/healthmon.slowest_rank')} != 1")
        check("healthmon/healthmon.grad_global_norm" in c,
              f"rank {rank}: grad-norm gauge missing")
    check(reports[0]["counters"].get(
        "healthmon/healthmon.nan_alerts", 0) >= 1,
        "rank 0: injected NaN raised no alert")

    # artifacts: per-rank validation + the merged cross-rank timeline
    tc = _load_tool("trace_check")
    md = _load_tool("mxdiag")
    artifact_errors = []
    paths = []
    for rank, doc in sorted(reports.items()):
        artifact_errors += tc.check_events_jsonl(doc["events_file"])
        artifact_errors += tc.check_flight(doc["flight_file"])
        paths += [doc["events_file"], doc["flight_file"]]
    merged_path = os.path.join(out_dir, "merged.jsonl")
    merged = md.merge_timelines(paths, out_path=merged_path)
    artifact_errors += tc.check_events_jsonl(merged_path)
    check(not artifact_errors, f"artifact validation: {artifact_errors[:5]}")

    merged_ranks = {r["rank"] for r in merged}
    check(merged_ranks >= {0, 1},
          f"merged timeline missing ranks: {sorted(merged_ranks)}")
    check(any(r["name"] == "skew_report" for r in merged),
          "merged timeline has no skew_report")
    check(any(r["name"] == "healthmon.nan_loss" for r in merged),
          "merged timeline has no NaN alert")
    nan_steps = [r["step"] for r in merged
                 if r["name"] == "healthmon.nan_loss"]
    check(NAN_STEP in nan_steps,
          f"NaN alert not attributed to step {NAN_STEP}: {nan_steps}")

    if failures:
        for f in failures:
            print(f"health_cluster: FAIL: {f}", file=sys.stderr)
        return 1
    summary = {
        "skew_ms": reports[0]["counters"].get(
            "healthmon/healthmon.collective_skew_ms"),
        "slowest_rank": reports[0]["counters"].get(
            "healthmon/healthmon.slowest_rank"),
        "nan_alerts_rank0": reports[0]["counters"].get(
            "healthmon/healthmon.nan_alerts"),
        "merged_records": len(merged), "merged_file": merged_path}
    print("HEALTH_SMOKE_OK " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=2"
        sys.path.insert(0, _REPO)
        import jax
        jax.config.update("jax_platforms", "cpu")
        worker(int(sys.argv[2]), int(sys.argv[3]), sys.argv[4],
               int(sys.argv[5]))
        sys.exit(0)
    sys.exit(main())
