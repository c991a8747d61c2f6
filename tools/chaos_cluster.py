#!/usr/bin/env python
"""Chaos harness for mxtpu.resilience: inject real faults, assert real
recovery (the tier-1 test tests/test_resilience.py::test_chaos_*
asserts on its output). The
health_cluster.py pattern, escalated from detection to self-healing:
healthmon's harness proves the verdicts fire; THIS one proves training
outlives them.

Scenarios (``--scenario nan|torn|freeze|kill|all``; all = default):

* **nan** — a poison batch (NaN feature) lands mid-run in a supervised
  TrainLoop: the loss goes non-finite, the Supervisor rolls back to the
  last good async checkpoint, skips the batch, and the run converges.
* **torn** — phase 1 trains and checkpoints, the parent CORRUPTS the
  newest checkpoint on disk (bit-flip in the largest payload file),
  phase 2 restarts: restore detects the torn checkpoint via its
  manifest digests, falls back to the previous good one (counted +
  evented), resumes past the consumed batches, and converges.
* **freeze** — the data source wedges forever mid-run: the stall
  watchdog fires, the Supervisor (``on_stall=exit``) dies with
  RESTART_EXIT_CODE, the parent restarts it, and the resumed run
  converges from last-good.
* **kill** — a 2-rank elastic group (rank-0 TCP coordinator) trains
  data-parallel by model averaging; rank 1 SIGKILLs itself MID-STEP:
  rank 0's round deadline evicts it, the survivor rolls back to
  last-good and keeps training at world size 1; the parent then
  relaunches rank 1, which re-joins at the checkpoint boundary and
  both finish. Merged cross-rank timeline validates.

Every scenario asserts the three-surface contract: >= 1 recovery in
the ``resilience.*`` counters, in the flight ring, AND in the
``mxtpu.events/1`` log — plus loss decreasing through the fault and a
clean ``mxdiag.py recover`` rendering.

Exit 0 iff every assertion holds; prints ``CHAOS_OK {json}``.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)

STEPS = int(os.environ.get("MXTPU_CHAOS_STEPS", "24"))
NAN_BATCH = int(os.environ.get("MXTPU_CHAOS_NAN_BATCH", "9"))
KILL_STEP = int(os.environ.get("MXTPU_CHAOS_KILL_STEP", "8"))
FREEZE_BATCH = int(os.environ.get("MXTPU_CHAOS_FREEZE_BATCH", "8"))
WORKER_TIMEOUT_S = int(os.environ.get("MXTPU_TEST_WORKER_TIMEOUT", "300"))
CKPT_EVERY = int(os.environ.get("MXTPU_CHAOS_CKPT_EVERY", "4"))


# ---------------------------------------------------------------------------
# shared worker plumbing
# ---------------------------------------------------------------------------

def _toy(seed=0):
    """Deterministic toy regression: y = x @ W. Loss must DECREASE
    through every injected fault — that is the acceptance bar."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import nn
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(1))
    net.initialize(init=mx.init.Xavier())
    return net, gluon.loss.L2Loss()


_W = None


def _batch(i, poison=False):
    import numpy as np
    global _W
    if _W is None:
        _W = np.random.RandomState(7).randn(8, 1).astype(np.float32)
    r = np.random.RandomState(1000 + i)
    x = r.randn(16, 8).astype(np.float32)
    if poison:
        x[0, 0] = np.nan
    return (x, (x @ _W).astype(np.float32))


def _arm_telemetry(out_dir, tag, stall_s=0):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import diagnostics as diag
    diag.enable_flight_recorder(dump_on_crash=False, dump_dir=out_dir)
    mon = mx.healthmon.enable(
        hm_dir=out_dir, stall_timeout_s=stall_s, exchange_every=0,
        events_path=os.path.join(out_dir, f"events_{tag}.jsonl"),
        stall_check_interval_s=0.25 if stall_s else None)
    return mon


def _finish(tag, mon, extra):
    """Worker epilogue: flight dump + counters snapshot on stdout."""
    from incubator_mxnet_tpu import diagnostics as diag
    from incubator_mxnet_tpu.profiler.counters import counters
    import incubator_mxnet_tpu as mx
    out_dir = os.environ["MXTPU_CHAOS_OUT"]
    flight_path = diag.dump_flight(
        reason=f"chaos_{tag}",
        path=os.path.join(out_dir, f"flight_{tag}.json"))
    snap = {k: v for k, v in counters().items()
            if (k.startswith("resilience/") or k.startswith("healthmon/"))
            and not isinstance(v, dict)}
    events_path = mon.events.path
    mx.healthmon.disable()
    print("CHAOS " + json.dumps(dict(
        extra, tag=tag, counters=snap, events_file=events_path,
        flight_file=flight_path)), flush=True)


def _loss_trend(losses):
    import numpy as np
    arr = np.asarray(losses, np.float64)
    head = float(arr[:2].mean())
    tail = float(arr[-2:].mean())
    return {"n": int(arr.size), "first": head, "last": tail,
            "decreased": bool(tail < head) and bool(np.isfinite(tail))}


# ---------------------------------------------------------------------------
# workers
# ---------------------------------------------------------------------------

def worker_nan():
    """Supervised TrainLoop with a poison batch: rollback + skip."""
    from incubator_mxnet_tpu import gluon, resilience
    from incubator_mxnet_tpu.trainloop import TrainLoop
    out_dir = os.environ["MXTPU_CHAOS_OUT"]
    mon = _arm_telemetry(out_dir, "nan")
    net, L = _toy()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, kvstore=None)
    loop = TrainLoop(net, L, tr, chunk=2)
    data = [_batch(i, poison=(i == NAN_BATCH)) for i in range(200)]
    sup = resilience.Supervisor(
        os.path.join(out_dir, "ckpt_nan"), every=CKPT_EVERY, keep=3)
    losses = loop.fit(data, steps=STEPS, resilience=sup)
    _finish("nan", mon, {"losses": _loss_trend(losses)})


def worker_torn(phase):
    """Phase 1 trains + checkpoints and exits; phase 2 resumes after
    the parent tore the newest checkpoint."""
    from incubator_mxnet_tpu import gluon, resilience
    from incubator_mxnet_tpu.trainloop import TrainLoop
    out_dir = os.environ["MXTPU_CHAOS_OUT"]
    mon = _arm_telemetry(out_dir, f"torn{phase}")
    net, L = _toy()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, kvstore=None)
    loop = TrainLoop(net, L, tr, chunk=2)
    data = [_batch(i) for i in range(400)]
    ckpt_dir = os.path.join(out_dir, "ckpt_torn")
    sup = resilience.Supervisor(ckpt_dir, every=CKPT_EVERY, keep=4)
    target = STEPS // 2 if phase == 1 else STEPS
    losses = loop.fit(data, steps=target, resilience=sup)
    from incubator_mxnet_tpu.parallel import list_steps
    _finish(f"torn{phase}", mon,
            {"losses": _loss_trend(losses), "ckpt_dir": ckpt_dir,
             "ckpt_steps": list_steps(ckpt_dir)})


def worker_freeze(phase):
    """Phase 1 wedges mid-run (frozen data source) -> stall watchdog ->
    RESTART_EXIT_CODE; phase 2 is the supervised restart."""
    from incubator_mxnet_tpu import gluon, resilience
    from incubator_mxnet_tpu.trainloop import TrainLoop
    out_dir = os.environ["MXTPU_CHAOS_OUT"]
    # phase 1 proves the stall fires: the deadline must cover the
    # tiny-net compile but not much more. Phase 2 proves the RESUME
    # converges — its cold-start restore + chunk recompile must not
    # read as the stall phase 1 already proved, so it gets slack.
    mon = _arm_telemetry(out_dir, f"freeze{phase}",
                         stall_s=6.0 if phase == 1 else 20.0)
    net, L = _toy()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, kvstore=None)
    loop = TrainLoop(net, L, tr, chunk=2)

    def batches():
        i = 0
        while True:
            if phase == 1 and i == FREEZE_BATCH:
                time.sleep(10_000)     # the wedge: a dead input queue
            yield _batch(i)
            i += 1

    sup = resilience.Supervisor(
        os.path.join(out_dir, "ckpt_freeze"), every=CKPT_EVERY,
        keep=3, on_stall="exit")
    losses = loop.fit(batches(), steps=STEPS, resilience=sup)
    # phase 1 never reaches here (os._exit on the watchdog thread)
    _finish(f"freeze{phase}", mon, {"losses": _loss_trend(losses)})


def worker_kill(rank, rejoin=False):
    """One rank of the elastic group: local FusedTrainStep + per-step
    model averaging through ElasticGroup.sync. Rank 1 SIGKILLs itself
    MID-STEP (after local compute, before the sync) at KILL_STEP; the
    relaunched rank 1 (--rejoin) re-enters via the checkpoint boundary,
    restores last-good, and runs a few joint rounds before draining.
    A small per-step sleep keeps the round cadence slower than process
    startup so the re-join lands while rank 0 is still training."""
    import numpy as np
    from incubator_mxnet_tpu import gluon, nd, resilience
    from incubator_mxnet_tpu.parallel import (latest_step,
                                              FusedTrainStep,
                                              restore_train_step,
                                              save_train_step)
    out_dir = os.environ["MXTPU_CHAOS_OUT"]
    sleep_s = float(os.environ.get("MXTPU_CHAOS_STEP_SLEEP", "0.25"))
    tag = f"kill_r{rank}" + ("_rejoin" if rejoin else "")
    mon = _arm_telemetry(out_dir, tag)
    net, L = _toy(seed=0)            # identical init on every rank
    step = FusedTrainStep(net, L,
                          gluon.Trainer(net.collect_params(), "sgd",
                                        {"learning_rate": 0.05},
                                        kvstore=None))
    ckpt_dir = os.path.join(out_dir, "ckpt_kill")
    port = int(os.environ["MXTPU_CHAOS_ELASTIC_PORT"])
    g = resilience.ElasticGroup(
        rank=rank, port=port if rank == 0 else 0,
        addr=None if rank == 0 else ("127.0.0.1", port),
        sync_timeout_s=3.0)
    x0, y0 = _batch(0)
    step.ensure_built(nd.array(x0), nd.array(y0))   # compile before join
    info = g.join()
    if rejoin:
        # re-entry at the checkpoint boundary: restore last-good, then
        # enter at the group's CURRENT step (not the possibly-stale one
        # from admission — compile time passed since)
        lg = info["last_good"]
        assert lg is not None, "rejoin admitted without last-good state"
        restore_train_step(ckpt_dir, step)
        resilience.record_recovery(
            "resume", {"restored_step": lg["step"], "rank": rank,
                       "via": "elastic_rejoin"},
            step=lg["step"])
        s = g._call("info")["max_step"] + 1
    else:
        s = info["next_step"]

    def flat_params():
        return np.concatenate([np.asarray(p.data()._data).ravel()
                               for p in step.params])

    def set_params(vec):
        import jax.numpy as jnp
        off = 0
        for p in step.params:
            n = int(np.prod(p.data().shape))
            p._data._data = jnp.asarray(
                vec[off:off + n].reshape(p.data().shape), jnp.float32)
            off += n

    losses = []
    departed_seen = rejoined_seen = False
    joint_rounds = 0
    hard_cap = STEPS + 200
    while s <= hard_cap:
        x, y = _batch(1000 * rank + s)   # each rank its own data shard
        loss = float(step(nd.array(x), nd.array(y)))
        if rank == 1 and not rejoin and s == KILL_STEP:
            os.kill(os.getpid(), signal.SIGKILL)   # mid-step hard death
        try:
            mean, sync_info = g.sync(s, flat_params())
        except resilience.GroupClosed:
            break
        if sync_info["membership_changed"] and sync_info["departed"]:
            # survivors re-form at the smaller world size and roll back
            # to last-good so every survivor restarts from the same
            # state (the departed rank's half-step dies with it)
            departed_seen = True
            lg = sync_info["last_good"]
            if lg is not None:
                restore_train_step(ckpt_dir, step)
            resilience.record_recovery(
                "rollback",
                {"reason": "rank_departed", "rank": rank,
                 "departed": sync_info["departed"],
                 "to_step": (lg or {}).get("step"),
                 "from_step": s, "steps_lost":
                     max(0, s - ((lg or {}).get("step") or 0))},
                step=s)
            s += 1
            continue
        if sync_info["membership_changed"] and sync_info["joined"] \
                and departed_seen:
            # only a join AFTER the departure is the re-join this
            # scenario proves (the initial join can also arrive through
            # the boundary path when rank 1 starts a beat late)
            rejoined_seen = True
        set_params(np.asarray(mean, np.float32))
        losses.append(loss)
        mon.step_end(loss=loss)
        if rank == 0 and s % CKPT_EVERY == 0:
            path = save_train_step(ckpt_dir, step, step_num=s)
            g.report_checkpoint(s, path)
        if rejoin:
            joint_rounds += 1
            if joint_rounds >= 4:
                break                  # drained after proving the rejoin
        elif rank == 0 and s >= STEPS:
            # rank 0 finishes only once the whole story happened: the
            # departure was observed AND the relaunched rank re-joined
            # and ran a couple of joint rounds (else keep the group
            # open, up to the hard cap)
            if not departed_seen or rejoined_seen:
                if rejoined_seen:
                    joint_rounds += 1
                if not departed_seen or joint_rounds >= 3:
                    break
        elif rank != 0 and s >= STEPS:
            break
        time.sleep(sleep_s)
        s += 1
    g.leave()
    _finish(tag, mon, {"losses": _loss_trend(losses), "rank": rank,
                       "rejoin_observed": rejoined_seen,
                       "departure_observed": departed_seen,
                       "last_ckpt": latest_step(ckpt_dir)})


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------

def _free_port() -> int:
    base = 24000 + (os.getpid() * 137) % 500
    for off in range(1000):
        port = 24000 + (base - 24000 + off) % 1000
        s = socket.socket()
        try:
            s.bind(("127.0.0.1", port))
        except OSError:
            continue
        finally:
            s.close()
        return port
    raise RuntimeError("no free elastic port in 24000-24999")


def _load_tool(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _spawn(args, env, timeout=WORKER_TIMEOUT_S, ok_codes=(0,)):
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker"] + args,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=_REPO)
    try:
        out, err = p.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        p.kill()
        out, err = p.communicate()
        raise RuntimeError(f"worker {args} timed out\nstderr:{err[-2000:]}")
    if p.returncode not in ok_codes:
        raise RuntimeError(f"worker {args} rc={p.returncode} not in "
                           f"{ok_codes}\nstdout:{out}\n"
                           f"stderr:{err[-3000:]}")
    return p.returncode, out, err


def _parse_chaos(out):
    docs = [json.loads(ln[len("CHAOS "):]) for ln in out.splitlines()
            if ln.startswith("CHAOS ")]
    return docs[-1] if docs else None


def _corrupt_latest(ckpt_dir):
    """Bit-flip the largest payload file of the NEWEST checkpoint —
    manifest untouched, so the digests must catch it."""
    from glob import glob
    steps = sorted(glob(os.path.join(ckpt_dir, "step_*")))
    victim_dir = steps[-1]
    best, best_size = None, -1
    for root, _dirs, files in os.walk(victim_dir):
        for f in files:
            if f == "manifest.json":
                continue
            p = os.path.join(root, f)
            if os.path.getsize(p) > best_size:
                best, best_size = p, os.path.getsize(p)
    with open(best, "r+b") as f:
        f.seek(best_size // 2)
        b = f.read(1) or b"\0"
        f.seek(best_size // 2)
        f.write(bytes([b[0] ^ 0xFF]))
    return victim_dir, best


class Checker:
    def __init__(self):
        self.failures = []

    def check(self, cond, msg):
        if not cond:
            self.failures.append(msg)
        return cond

    def three_surfaces(self, doc, counter_keys, flight_names,
                       event_names, what):
        """The acceptance contract: the recovery must be visible on
        counters AND flight AND events."""
        c = doc["counters"]
        self.check(any(c.get(f"resilience/{k}", 0) >= 1
                       for k in counter_keys),
                   f"{what}: no recovery counter among {counter_keys}: "
                   f"{ {k: v for k, v in c.items() if 'resilience' in k} }")
        try:
            with open(doc["flight_file"]) as f:
                fl = json.load(f)
            names = {e.get("name") for e in fl.get("events", [])
                     if e.get("kind") == "resilience"}
        except (OSError, ValueError) as e:
            names = set()
            self.failures.append(f"{what}: unreadable flight dump: {e}")
        self.check(names & set(flight_names),
                   f"{what}: no {flight_names} breadcrumb in flight ring "
                   f"(saw {sorted(names)})")
        ev_names = set()
        try:
            with open(doc["events_file"]) as f:
                for ln in f:
                    if ln.strip():
                        ev_names.add(json.loads(ln).get("name"))
        except (OSError, ValueError) as e:
            self.failures.append(f"{what}: unreadable event log: {e}")
        self.check(ev_names & set(event_names),
                   f"{what}: no {event_names} record in events "
                   f"(saw {sorted(n for n in ev_names if n and 'resil' in n)})")

    def loss_decreased(self, doc, what):
        tr = doc.get("losses") or {}
        self.check(tr.get("decreased"),
                   f"{what}: loss did not decrease through the fault "
                   f"({tr})")


def run_scenarios(scenarios):
    out_dir = os.environ.get("MXTPU_CHAOS_OUT", "/tmp/mxtpu_chaos")
    import shutil
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["MXTPU_CHAOS_OUT"] = out_dir
    env.setdefault("MXTPU_RUN_ID", f"chaos-{int(time.time())}")
    ck = Checker()
    tc = _load_tool("trace_check")
    md = _load_tool("mxdiag")
    summary = {}
    event_files = []

    if "nan" in scenarios:
        print(f"chaos[nan]: poison batch at index {NAN_BATCH}",
              flush=True)
        _, out, _ = _spawn(["nan"], env)
        doc = _parse_chaos(out)
        ck.check(doc is not None, "nan: no CHAOS report") and (
            ck.three_surfaces(doc, ["resilience.rollbacks"],
                              ["rollback"], ["resilience.rollback"],
                              "nan"),
            ck.loss_decreased(doc, "nan"),
            event_files.append(doc["events_file"]))
        if doc:
            summary["nan"] = {"rollbacks": doc["counters"].get(
                "resilience/resilience.rollbacks"),
                "losses": doc["losses"]}

    if "torn" in scenarios:
        print("chaos[torn]: train, tear newest checkpoint, restart",
              flush=True)
        _, out1, _ = _spawn(["torn", "1"], env)
        doc1 = _parse_chaos(out1)
        doc2 = None
        # gate phase 2 on the precondition so a failed phase 1 surfaces
        # as the curated verdict, not a TypeError on doc1[...]
        if ck.check(doc1 is not None and len(doc1["ckpt_steps"]) >= 2,
                    f"torn: phase 1 left <2 checkpoints "
                    f"({doc1 and doc1['ckpt_steps']}) — nothing to fall "
                    f"back to"):
            victim, vfile = _corrupt_latest(doc1["ckpt_dir"])
            print(f"chaos[torn]: corrupted {vfile}", flush=True)
            _, out2, _ = _spawn(["torn", "2"], env)
            doc2 = _parse_chaos(out2)
            ck.check(doc2 is not None, "torn: no phase-2 CHAOS report")
        if doc2:
            c = doc2["counters"]
            ck.check(c.get("resilience/resilience.corrupt_checkpoints",
                           0) >= 1,
                     f"torn: corrupt checkpoint not detected: {c}")
            ck.three_surfaces(doc2, ["resilience.resumes"],
                              ["resume"], ["resilience.resume"], "torn")
            ck.loss_decreased(doc2, "torn")
            event_files.append(doc2["events_file"])
            summary["torn"] = {
                "corrupt_detected": c.get(
                    "resilience/resilience.corrupt_checkpoints"),
                "resumes": c.get("resilience/resilience.resumes"),
                "losses": doc2["losses"]}

    if "freeze" in scenarios:
        print(f"chaos[freeze]: source wedges at batch {FREEZE_BATCH}; "
              f"stall watchdog must fire and exit 96", flush=True)
        # rc 0 is "watchdog never fired" — a CURATED failure below, not
        # a worker crash, so it must get past _spawn's rc gate
        rc, out1, err1 = _spawn(["freeze", "1"], env,
                                ok_codes=(0, 96))
        doc2 = None
        if ck.check(rc == 96,
                    f"freeze: phase 1 exited {rc}, wanted "
                    f"RESTART_EXIT_CODE 96"):
            _, out2, _ = _spawn(["freeze", "2"], env)
            doc2 = _parse_chaos(out2)
            ck.check(doc2 is not None, "freeze: no phase-2 CHAOS report")
        if doc2:
            ck.three_surfaces(doc2, ["resilience.resumes"],
                              ["resume"], ["resilience.resume"],
                              "freeze")
            ck.loss_decreased(doc2, "freeze")
            event_files.append(doc2["events_file"])
            # phase 1's stall escalation left its own trail
            ev1 = os.path.join(out_dir, "events_freeze1.jsonl")
            names = set()
            if os.path.exists(ev1):
                with open(ev1) as f:
                    names = {json.loads(ln).get("name") for ln in f
                             if ln.strip()}
            ck.check("resilience.restart_requested" in names,
                     f"freeze: no restart_requested event in phase 1 "
                     f"({sorted(n for n in names if n)})")
            event_files.append(ev1)
            summary["freeze"] = {
                "resumes": doc2["counters"].get(
                    "resilience/resilience.resumes"),
                "losses": doc2["losses"]}

    if "kill" in scenarios:
        port = _free_port()
        kenv = dict(env, MXTPU_CHAOS_ELASTIC_PORT=str(port))
        print(f"chaos[kill]: 2-rank elastic group on :{port}; rank 1 "
              f"SIGKILLs itself mid-step {KILL_STEP}", flush=True)
        p0 = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "kill", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=kenv, cwd=_REPO)
        time.sleep(1.0)
        p1 = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "kill", "1"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=kenv, cwd=_REPO)
        p1.wait(timeout=WORKER_TIMEOUT_S)
        ck.check(p1.returncode == -signal.SIGKILL,
                 f"kill: rank 1 exited {p1.returncode}, wanted SIGKILL")
        # the survivor is re-forming; give it a beat, then relaunch
        # rank 1 to prove re-join at the checkpoint boundary
        time.sleep(2.0)
        try:
            rc1b, out1b, err1b = _spawn(["kill", "1", "--rejoin"], kenv)
        except RuntimeError as e:
            ck.check(False, f"kill: rejoin worker failed: {e}")
            out1b = ""
            p0.kill()
        out0, err0 = p0.communicate(timeout=WORKER_TIMEOUT_S)
        ck.check(p0.returncode == 0,
                 f"kill: rank 0 rc={p0.returncode}\n"
                 f"stderr:{err0[-2000:]}")
        doc0 = _parse_chaos(out0)
        doc1b = _parse_chaos(out1b)
        ck.check(doc0 is not None, "kill: no rank-0 CHAOS report")
        if doc0:
            c = doc0["counters"]
            ck.check(c.get("resilience/resilience.rank_departures",
                           0) >= 1,
                     f"kill: rank 0 never observed the departure: {c}")
            ck.check(c.get("resilience/resilience.rank_joins", 0) >= 1,
                     f"kill: rank 0 never observed the re-join: {c}")
            ck.three_surfaces(
                doc0, ["resilience.recoveries_total"],
                ["rank_departed", "rollback"],
                ["resilience.rank_departed", "resilience.rollback"],
                "kill")
            ck.loss_decreased(doc0, "kill")
            ck.check(doc0.get("departure_observed"),
                     "kill: rank 0 reports no departure observed")
            ck.check(doc0.get("rejoin_observed"),
                     "kill: rank 0 reports no re-join observed")
            event_files.append(doc0["events_file"])
        if doc1b:
            event_files.append(doc1b["events_file"])
            summary["kill"] = {
                "departures": doc0 and doc0["counters"].get(
                    "resilience/resilience.rank_departures"),
                "joins": doc0 and doc0["counters"].get(
                    "resilience/resilience.rank_joins"),
                "losses": doc0 and doc0["losses"],
                "rejoin_observed": doc0 and doc0.get("rejoin_observed")}

    # merged timeline: every scenario's events interleave into one
    # validated stream, and the recovery renderer must accept it
    artifact_errors = []
    event_files = [p for p in event_files if p and os.path.exists(p)]
    for p in event_files:
        artifact_errors += tc.check_events_jsonl(p)
    merged_path = os.path.join(out_dir, "merged.jsonl")
    merged = md.merge_timelines(event_files, out_path=merged_path)
    artifact_errors += tc.check_events_jsonl(merged_path)
    ck.check(not artifact_errors,
             f"artifact validation: {artifact_errors[:5]}")
    recover_rc = md.print_recover(merged)
    ck.check(recover_rc == 0,
             f"mxdiag recover flagged the merged timeline (rc="
             f"{recover_rc})")

    if ck.failures:
        for f in ck.failures:
            print(f"chaos: FAIL: {f}", file=sys.stderr)
        return 1
    summary["merged_records"] = len(merged)
    summary["merged_file"] = merged_path
    print("CHAOS_OK " + json.dumps(summary), flush=True)
    return 0


def main() -> int:
    scen = "all"
    argv = sys.argv[1:]
    if argv and argv[0] == "--scenario":
        scen = argv[1]
    scenarios = ("nan", "torn", "freeze", "kill") if scen == "all" \
        else tuple(scen.split(","))
    return run_scenarios(scenarios)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.pop("XLA_FLAGS", None)
        sys.path.insert(0, _REPO)
        which = sys.argv[2]
        if which == "nan":
            worker_nan()
        elif which == "torn":
            worker_torn(int(sys.argv[3]))
        elif which == "freeze":
            worker_freeze(int(sys.argv[3]))
        elif which == "kill":
            worker_kill(int(sys.argv[3]),
                        rejoin="--rejoin" in sys.argv)
        else:
            raise SystemExit(f"unknown worker {which!r}")
        sys.exit(0)
    sys.exit(main())
