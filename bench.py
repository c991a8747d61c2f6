#!/usr/bin/env python
"""Headline benchmark: ResNet-50 ImageNet-shape training throughput on one
TPU chip (BASELINE.json: images/sec/chip vs MXNet-on-V100 reference).

Prints exactly one JSON line:
  {"metric": "...", "value": N, "unit": "images/sec", "vs_baseline": N}

Baseline: published MXNet ResNet-50 fp32 V100 throughput ~390 img/s
(BASELINE.json north star: target >=70% of that on one v5e chip).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import incubator_mxnet_tpu as mx  # noqa: E402
from incubator_mxnet_tpu import gluon, nd  # noqa: E402
from incubator_mxnet_tpu.models import get_model  # noqa: E402
from incubator_mxnet_tpu.parallel import FusedTrainStep  # noqa: E402
from incubator_mxnet_tpu.runtime.cache_guard import use_compile_cache  # noqa: E402

V100_BASELINE_IMG_S = 390.0  # MXNet ResNet-50 fp32, single V100 (published)
RESNET50_FLOPS_PER_SAMPLE = 3 * 4.09e9   # fwd+bwd, 224x224 (both benches)

# updated once the model is resolved; all error paths report through this
_CURRENT_METRIC = "resnet50_imagenet_images_per_sec_per_chip"

class _PhaseTimeout(Exception):
    pass


class _phase_deadline:
    """SIGALRM deadline around one phase: a phase that overruns raises
    _PhaseTimeout, and the run ends with an error line instead of eating
    the caller's whole time limit in silence."""

    def __init__(self, seconds, what):
        self.seconds = int(seconds)
        self.what = what

    def __enter__(self):
        import signal

        def handler(signum, frame):
            raise _PhaseTimeout(f"{self.what} exceeded {self.seconds}s")

        self._old = signal.signal(signal.SIGALRM, handler)
        signal.alarm(self.seconds)
        return self

    def __exit__(self, *exc):
        import signal
        signal.alarm(0)
        signal.signal(signal.SIGALRM, self._old)
        return False


def _log(msg):
    print(f"bench[{time.strftime('%H:%M:%S')}]: {msg}", file=sys.stderr,
          flush=True)


def _bench_profile_start():
    """Arm the profiler for phase scopes around the bench run. Imperative
    op timing stays OFF (it syncs per op and would distort the measured
    rate); only layer/phase scopes are recorded. Returns the trace path,
    or None when BENCH_TRACE=0."""
    if os.environ.get("BENCH_TRACE", "1") != "1":
        return None
    from incubator_mxnet_tpu import profiler as prof
    path = os.environ.get("BENCH_TRACE_FILE", "/tmp/mxtpu_bench_trace.json")
    prof.reset()
    prof.set_config(filename=path, profile_imperative=False)
    prof.start()
    return path


def _bench_diag_start():
    """Arm the always-on diagnostics layer for the bench run. The memory
    ledger is on unconditionally (its peaks land in BENCH_*.json so
    memory regressions show up in the perf trajectory); BENCH_DIAG=1
    additionally runs the metrics sampler (BENCH_DIAG_INTERVAL_MS,
    default 100) and the flight recorder, whose outputs are validated by
    tools/trace_check at the end of the run."""
    from incubator_mxnet_tpu import diagnostics as diag
    diag.enable_memory()
    if os.environ.get("BENCH_DIAG", "0") != "1":
        return None
    diag_dir = os.environ.get("MXTPU_DIAG_DIR", "/tmp/mxtpu_bench_diag")
    os.makedirs(diag_dir, exist_ok=True)
    diag.enable_flight_recorder(dump_dir=diag_dir)
    jsonl = os.path.join(diag_dir, "metrics.jsonl")  # sampler truncates it
    diag.start_sampler(
        interval_ms=int(os.environ.get("BENCH_DIAG_INTERVAL_MS", "100")),
        jsonl_path=jsonl, prom_path=os.path.join(diag_dir, "metrics.prom"))
    return diag_dir


def _bench_healthmon_start():
    """BENCH_HEALTHMON=1: arm the cross-rank health layer for the bench
    run — the structured event log + watchdogs (stall deadline widened to
    cover the compile phase, BENCH_HEALTHMON_STALL_S). The bench loop
    feeds it one mark per step, so the emitted BENCH json carries the
    healthmon counters and the events file — and the run doubles as the
    measured-overhead harness tools/health_smoke.sh compares against a
    healthmon-off run."""
    if os.environ.get("BENCH_HEALTHMON", "0") != "1":
        return None
    from incubator_mxnet_tpu import healthmon as hm
    diag_dir = os.environ.get("MXTPU_DIAG_DIR", "/tmp/mxtpu_bench_diag")
    os.makedirs(diag_dir, exist_ok=True)
    return hm.enable(
        hm_dir=diag_dir,
        stall_timeout_s=float(os.environ.get("BENCH_HEALTHMON_STALL_S",
                                             "1200")))


def _healthmon_mark_step():
    """One completed bench step (no-op when healthmon is off)."""
    from incubator_mxnet_tpu import healthmon as hm
    if hm._HM is not None:
        hm._HM.step_end()


# the run's CheckpointManager when BENCH_RESILIENCE=1 (closed and
# reported as extra.resilience by _finish_profile)
_RES_MGR = None


def _bench_resilience_start(step):
    """BENCH_RESILIENCE=1: arm async checkpointing (mxtpu.resilience)
    over the steady phase — cadence BENCH_RESILIENCE_EVERY (default 20)
    into BENCH_RESILIENCE_DIR (default a fresh temp dir) — so the BENCH
    json carries extra.resilience: checkpoint cadence, save-cost
    p50/p95, and any recovery accounting. The measured loop pays only
    the boundary device→host copies; serialization stays on the
    manager's worker thread (docs/resilience.md's cost model)."""
    global _RES_MGR
    if os.environ.get("BENCH_RESILIENCE", "0") != "1":
        return None
    import tempfile
    from incubator_mxnet_tpu.resilience import CheckpointManager
    d = os.environ.get("BENCH_RESILIENCE_DIR") or \
        tempfile.mkdtemp(prefix="mxtpu_bench_ckpt_")
    every = int(os.environ.get("BENCH_RESILIENCE_EVERY", "20"))
    keep = int(os.environ.get("BENCH_RESILIENCE_KEEP", "3"))
    _log(f"resilience armed: async checkpoints every {every} steps "
         f"(keep {keep}) -> {d}")
    _RES_MGR = CheckpointManager(d, step, every=every, keep=keep)
    return _RES_MGR


def _resilience_mark_step():
    """One completed bench step/chunk boundary (no-op when resilience
    is off — one predicate, the disabled-cost contract)."""
    if _RES_MGR is not None:
        _RES_MGR.maybe_save()


def _bench_perfscope_start():
    """Arm roofline-aware cost capture (mxtpu.perfscope) for the run:
    every compile site (fused step, loop chunk, jit cache, serving
    buckets) records XLA FLOPs/bytes + a roofline verdict, and the
    steady phase gets a step-time decomposition into
    `extra.perfscope`. BENCH_PERFSCOPE=0 disables."""
    if os.environ.get("BENCH_PERFSCOPE", "1") != "1":
        return None
    from incubator_mxnet_tpu import perfscope as ps
    return ps.enable()


def _bench_commscope_start():
    """Arm collective/resharding extraction (mxtpu.commscope) for the
    run: every compile site's optimized HLO is walked for its collective
    inventory (kind / count / payload bytes / mesh axis / analytic ICI
    estimate), the resharding detector flags accidental all-gathers, and
    the result lands in `extra.commscope` + the step budget's estimated
    `collective` component. Zero cost without a mesh (no collectives to
    find, nothing compiled); under BENCH_MESH it pays one extra XLA
    compile per captured program. BENCH_COMMSCOPE=0 disables; commscope
    rides perfscope's capture hooks (enable() arms perfscope), so a
    default-on commscope DECLINES when BENCH_PERFSCOPE=0 was set —
    the perfscope opt-out must not be silently undone. An explicit
    BENCH_COMMSCOPE=1 wins the conflict (and says so)."""
    if os.environ.get("BENCH_COMMSCOPE", "1") != "1":
        return None
    if os.environ.get("BENCH_PERFSCOPE", "1") != "1":
        if os.environ.get("BENCH_COMMSCOPE") != "1":
            return None
        _log("BENCH_COMMSCOPE=1 overrides BENCH_PERFSCOPE=0: commscope "
             "rides perfscope's capture hooks, arming both")
    from incubator_mxnet_tpu import commscope as cs
    return cs.enable()


def _bench_devicescope_start():
    """BENCH_DEVICESCOPE=1: arm measured device-timeline capture
    (mxtpu.devicescope) — one bounded window (BENCH_DEVICESCOPE_STEPS,
    default 10) of the steady phase runs under jax.profiler.trace; the
    artifact is ingested into measured busy fraction / top-K device ops
    / idle-gap taxonomy, the step budget's provenance upgrades to
    measured(profile), and `extra.devicescope` carries the
    analytic-vs-measured reconciliation. OFF by default: the traced
    steps pay profiler overhead, so the window must be asked for.
    Artifact dirs rotate (MXTPU_DEVICESCOPE_KEEP, default 3)."""
    if os.environ.get("BENCH_DEVICESCOPE", "0") != "1":
        return None
    from incubator_mxnet_tpu import devicescope as ds
    return ds.enable()


def _bench_memscope_start():
    """BENCH_MEMSCOPE=1: arm memory observability (mxtpu.memscope) —
    every captured program additionally reads
    `compiled.memory_analysis()` into a static footprint table joined
    to the roofline verdicts, the steady loops feed a bounded
    watermark ring of allocator samples (+ host RSS), an escaping
    RESOURCE_EXHAUSTED assembles an attributed post-mortem, and
    `extra.memscope` carries it all (validated by trace_check's
    check_memscope_extra). OFF by default: a capture site holding only
    a lowered program pays one extra host-side XLA compile per program
    (the commscope acquisition cost), so the footprints must be asked
    for. Rides perfscope's capture hooks (enable() arms perfscope)."""
    if os.environ.get("BENCH_MEMSCOPE", "0") != "1":
        return None
    from incubator_mxnet_tpu import memscope as ms
    return ms.enable()


def _memscope_mark(step_no):
    """One watermark-ring allocator sample at a steady-loop step
    boundary when memscope is armed (mxtpu.trainloop marks its own
    chunks, so loop mode needs no bench-side mark). One predicate when
    off; sampling never raises."""
    from incubator_mxnet_tpu import memscope as ms
    if ms._MS is not None:
        ms.sample(step=step_no, workload="train")


def _bench_strict_start():
    """MXTPU_STRICT=1 (or BENCH_STRICT=1): arm the mxlint strict-mode
    jit-program auditor (mxtpu.mxlint.runtime) — every steady-loop
    dispatch runs under transfer-guard + NDArray-sentinel host-sync
    detection, perfscope compile captures feed the recompile-storm
    detector, and `extra.mxlint` carries the verdicts (validated by
    trace_check's check_mxlint_extra). On CPU the sentinel counts and
    the run completes; an accelerator jax-guard trip is a counted,
    LOUD failure (no side-effect-safe re-run of a dispatched step
    exists) — a smoke/CI mode, not a production default."""
    from incubator_mxnet_tpu.mxlint import runtime as mxa
    if mxa.enabled():              # armed at import via MXTPU_STRICT=1
        return mxa.auditor()
    if os.environ.get("BENCH_STRICT", "0") == "1":
        return mxa.enable()
    return None


def _strict_guarded(aud, thunk):
    """One steady-loop dispatch through the strict guard (or plainly —
    the loops call this with aud=None when strict is off). The guard
    SEMANTICS live in one home (StrictAuditor.guarded); this wrapper
    only spares the off path an attribute lookup per dispatch."""
    if aud is None:
        return thunk()
    return aud.guarded(thunk)


def _devicescope_window(total_steps, steps_per_dispatch=1):
    """A started capture window over the first N steady steps when
    devicescope is armed, else None (zero overhead: the loops guard
    every mark with `if win is not None`)."""
    from incubator_mxnet_tpu import devicescope as ds
    if ds._DS is None:
        return None
    n = int(os.environ.get("BENCH_DEVICESCOPE_STEPS", "10"))
    n = max(int(steps_per_dispatch), min(n, int(total_steps)))
    win = ds.capture(steps=n).start()
    if win.active:
        _log(f"devicescope: capture window armed ({n} steps) -> "
             f"{win.logdir}")
    else:
        _log("devicescope: capture window DECLINED (profiler busy or "
             "unavailable)")
    return win


def _bench_mesh():
    """BENCH_MESH=dp4|dp2mp2|fsdp4|…: register a process-global device
    mesh (mxtpu.sharding) so the steady phase runs through the SHARDED
    executor — one jit whose in/out shardings carry the resolved
    per-param NamedShardings, XLA inserting the collectives. The token
    grammar (concatenated <axis><size> pairs, the `fsdp` pseudo-axis,
    the model-axis → mode='auto' rule) lives in autotune.knobs.
    parse_mesh — ONE home, shared with the trial runner — and the spec
    itself resolves through the knob table (BENCH_MESH > MXTPU_MESH >
    cached tuning winner). Returns the sharding mode, or None when no
    mesh is configured. On CPU pair with
    XLA_FLAGS=--xla_force_host_platform_device_count=N
    (tools/shard_smoke.sh does)."""
    from incubator_mxnet_tpu.autotune import knobs as _knobs
    from incubator_mxnet_tpu.parallel import make_mesh
    from incubator_mxnet_tpu.parallel import sharding as _shmod
    spec = _knobs.resolve("mesh")[0]
    if not spec:
        return None
    mode, axes = _knobs.parse_mesh(spec)
    mesh = make_mesh(axes)
    _shmod.set_mesh(mesh)
    _log(f"sharding: mesh {dict(mesh.shape)} mode={mode} over "
         f"{mesh.size} of {len(jax.devices())} devices")
    return mode


def _bench_autotune(model, batch, dtype):
    """MXTPU_AUTOTUNE=1: resolve the tuning cache for this
    (model, mesh, device-kind) key — hit: the stored winner's knobs
    install as the below-env defaults with ZERO trials; miss: a bounded
    search runs first (each trial a short bench.py SUBPROCESS —
    docs/autotune.md's cost model), the winner installs and persists.
    A chip belongs to one process at a time, so main() calls this BEFORE
    it touches jax: the trials need the device this process would hold.
    Explicit BENCH_*/MXTPU_* overrides still beat the winner (the knob
    precedence), so the tuner can never reinterpret a human A/B run.
    Returns the `extra.autotune` payload; the disabled shape
    ({"enabled": false}) when unarmed, so every training BENCH json
    carries a validatable section either way."""
    from incubator_mxnet_tpu import autotune as at
    if not at.enabled():
        return at.bench_extra(None)
    data_mode = os.environ.get("BENCH_DATA", "synthetic")
    if data_mode not in ("", "synthetic"):
        # the trial runner pins BENCH_* per trial (BENCH_DATA included),
        # so every search trial would measure the SYNTHETIC input path
        # while this run is the JPEG-decode path — input starvation is
        # exactly what data mode changes — and the cache key carries no
        # data-mode leg, so the wrong winner would then poison the
        # synthetic key too. Run untuned rather than tune the wrong
        # workload; the record says why.
        _log(f"autotune: BENCH_DATA={data_mode} runs the record input "
             f"path but search trials measure the synthetic path — "
             f"running UNTUNED (data-path trials not supported yet)")
        return {"enabled": True, "cache_hit": False, "trials": 0,
                "trials_failed": 0, "trials_pruned": 0,
                "winner": None, "score": None,
                "error": f"BENCH_DATA={data_mode}: data-path trials "
                         f"not supported"}
    mesh = at.knobs.resolve("mesh")[0]
    _log(f"autotune armed: model={model} batch={batch} dtype={dtype} "
         f"mesh={mesh}")
    try:
        result = at.ensure_tuned(model=model, batch=batch, dtype=dtype,
                                 mesh=mesh, log=_log)
    except Exception as e:  # noqa: BLE001 — tuning is advisory: a
        _log(f"autotune failed ({type(e).__name__}: {e}); "  # broken
             "running untuned")                # tuner must not cost the
        return {"enabled": True, "cache_hit": False,   # measured run
                "trials": 0, "trials_failed": 0, "trials_pruned": 0,
                "winner": None, "score": None,
                "error": f"{type(e).__name__}: {e}"[:200]}
    return at.bench_extra(result)


def _perfscope_budget(steps_per_dispatch=1):
    """A primed StepBudget when perfscope is armed, else None."""
    from incubator_mxnet_tpu import perfscope as ps
    if ps._PS is None:
        return None
    return ps.StepBudget(steps_per_dispatch=steps_per_dispatch).begin()


def _perfscope_settle(result, budget, steps, steady_s, probe_fn,
                      steps_per_call, flops_per_step, dtype):
    """Close the steady-phase budget: device-time probe (a few extra
    synchronized steps — each ends in a host fetch, a device barrier),
    settle the decomposition, and attach
    `extra.perfscope` (decomposition + per-program roofline verdicts +
    the peak table) to the result JSON."""
    from incubator_mxnet_tpu import perfscope as ps
    if budget is None:
        return
    # the whole settle path is best-effort: the headline number is
    # already measured, and attribution must NEVER destroy it (the same
    # contract as the k=1 control) — a probe that fails costs the
    # decomposition, not the result
    try:
        budget.end(steps=steps, steady_s=steady_s)
        n_probe = int(os.environ.get("BENCH_PERFSCOPE_PROBE", "5"))
        if n_probe > 0 and probe_fn is not None:
            with _phase_deadline(int(os.environ.get("BENCH_PROBE_TIMEOUT",
                                                    "600")),
                                 "perfscope device-time probe"):
                p = budget.probe(probe_fn, iters=n_probe,
                                 steps_per_call=steps_per_call)
            _log(f"perfscope probe: {p['median_ms']:.3f} ms/step sync "
                 f"({p['iters']} iters)")
        decomp = budget.finish(model_flops_per_step=flops_per_step,
                               dtype=dtype)
        result.setdefault("extra", {})["perfscope"] = ps.bench_extra(decomp)
    except Exception as e:  # noqa: BLE001
        _log(f"perfscope settle failed ({type(e).__name__}: {e}); "
             "reporting the measured result without a decomposition")
        try:
            result.setdefault("extra", {})["perfscope"] = ps.bench_extra()
        except Exception:  # noqa: BLE001
            pass
    # the collective inventory rides along whenever commscope is armed
    # (BENCH_MESH runs carry the real payload; unsharded runs an empty
    # one, so the schema is uniform) — attached OUTSIDE the settle try
    # so a failed probe can't cost the comms table too
    try:
        from incubator_mxnet_tpu import commscope as cs
        if cs._CS is not None:
            result.setdefault("extra", {})["commscope"] = cs.bench_extra()
    except Exception as e:  # noqa: BLE001
        _log(f"commscope attach failed ({type(e).__name__}: {e})")
    # the measured device-timeline summary rides along whenever
    # devicescope is armed (window summary + reconciliation; the
    # armed-but-declined shape is `{"window": null}` so the schema is
    # uniform) — also outside the settle try, for the same reason
    try:
        from incubator_mxnet_tpu import devicescope as dsc
        if dsc._DS is not None:
            result.setdefault("extra", {})["devicescope"] = \
                dsc.bench_extra()
    except Exception as e:  # noqa: BLE001
        _log(f"devicescope attach failed ({type(e).__name__}: {e})")
    # the memory footprints / watermarks / headroom / reconciliation
    # ride along whenever memscope is armed — also outside the settle
    # try, so a failed probe can't cost the memory evidence either
    try:
        from incubator_mxnet_tpu import memscope as msc
        if msc._MS is not None:
            result.setdefault("extra", {})["memscope"] = msc.bench_extra()
    except Exception as e:  # noqa: BLE001
        _log(f"memscope attach failed ({type(e).__name__}: {e})")


def _profiled_compile_warmup(run_compile, run_warmup):
    """Shared compile+warmup phase instrumentation for both bench paths:
    arms the profiler, runs the compile under a bench.compile scope and
    the usual phase deadline, times both phases. Returns
    (trace_path, compile_s, warmup_s)."""
    from incubator_mxnet_tpu import profiler as prof
    trace_path = _bench_profile_start()
    t_c = time.time()
    with prof.record_function("bench.compile", "bench", sync=False), \
            _phase_deadline(int(os.environ.get("BENCH_COMPILE_TIMEOUT",
                                               "2400")),
                            "train step compile"):
        run_compile()
    compile_s = time.time() - t_c
    _log(f"compile done in {compile_s:.1f}s; warmup")
    t_w = time.time()
    run_warmup()
    warmup_s = time.time() - t_w
    return trace_path, compile_s, warmup_s


def _load_trace_check():
    import importlib.util
    tc_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tools", "trace_check.py")
    spec = importlib.util.spec_from_file_location("trace_check", tc_path)
    tc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tc)
    return tc


def _finish_profile(result, trace_path, **phase_s):
    """Publish per-phase wall times as profiler gauges, attach them to the
    result JSON (-> BENCH_*.json), then dump the Chrome trace (and any
    diagnostics artifacts) and schema-check everything with
    tools/trace_check — malformed telemetry fails the bench run loudly
    instead of shipping garbage."""
    from incubator_mxnet_tpu import diagnostics as diag
    from incubator_mxnet_tpu import profiler as prof
    phases = {k: round(float(v), 4) for k, v in phase_s.items()}
    for k, v in phases.items():
        prof.set_gauge("bench/" + k, v)
    result.setdefault("extra", {})["phases"] = phases
    # dispatch-overhead regression canary: host dispatches per train step
    # (always-live counter gauge — FusedTrainStep reports 1, 1/k under
    # run_k; the eager Trainer reports #params unfused / #(rule,dtype)
    # groups with fused_update). Visible in BENCH_*.json without a TPU.
    result["extra"]["dispatches_per_step"] = prof.counters().get(
        "mxtpu/trainer.dispatches_per_step")
    # memory-regression canary: the allocation ledger's peaks + the final
    # counters snapshot ride along in BENCH_*.json so drift shows up in
    # the perf trajectory next to step times
    mem = diag.memory_summary(include_reconcile=False)
    result["extra"]["memory"] = {
        "peak_bytes": mem["peak_bytes"],
        "current_bytes": mem["current_bytes"],
        "live_arrays": mem["live_arrays"],
        "by_context": mem["by_context"],
    }
    result["extra"]["counters"] = prof.counters()
    tc = _load_trace_check()
    errors = []
    if trace_path is not None:
        prof.stop()
        prof.dump(filename=trace_path)
        errors += tc.check_trace(trace_path)
        result["extra"]["trace_file"] = trace_path
    if diag.flight_enabled() or diag.sampler_running():
        diag.stop_sampler()
        flight_path = diag.dump_flight(reason="bench_end")
        if flight_path:
            errors += tc.check_flight(flight_path)
            result["extra"]["flight_file"] = flight_path
        diag_dir = os.environ.get("MXTPU_DIAG_DIR", "/tmp/mxtpu_bench_diag")
        for name, checker in (("metrics.jsonl", tc.check_metrics_jsonl),
                              ("metrics.prom", tc.check_prom)):
            p = os.path.join(diag_dir, name)
            if os.path.exists(p):
                errors += checker(p)
                result["extra"]["diag_" + name.split(".")[1]] = p
    global _RES_MGR
    if _RES_MGR is not None:
        # drain the worker so the save histograms cover every enqueued
        # checkpoint, then report cadence + cost + recovery accounting
        from incubator_mxnet_tpu import resilience as _rs
        _RES_MGR.close()
        result["extra"]["resilience"] = _rs.bench_extra(_RES_MGR)
        _RES_MGR = None
    from incubator_mxnet_tpu import healthmon as hm
    if hm.enabled():
        mon = hm.current()
        events_path = mon.events.path
        result["extra"]["healthmon"] = {
            "events_file": events_path,
            "steps": mon.step,
            "counters": {k: v for k, v in prof.counters().items()
                         if k.startswith("healthmon/")},
        }
        hm.disable()               # closes the event log before validation
        errors += tc.check_events_jsonl(events_path)
    if errors:
        raise RuntimeError("bench telemetry failed schema check: "
                           + "; ".join(errors[:5]))
    if trace_path is not None:
        _log(f"trace OK: {trace_path} ({len(phases)} phases)")


def _require_tpu():
    """The benchmark measures the chip. Any other platform is an error,
    unless the caller asked for the CPU by name (JAX_PLATFORMS=cpu: the
    smoke scripts and tier-1 check the plumbing there, and nothing they
    print is a device metric)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            f"bench.py needs a TPU; jax found platform {dev.platform!r} "
            f"({dev.device_kind}). Set JAX_PLATFORMS=cpu to check the "
            f"plumbing on the CPU.")
    _log(f"backend ready: {dev} ({dev.device_kind})")


def _build_resnet(batch, dtype):
    # BENCH_S2D=1: MLPerf-style space-to-depth stem — exact-equivalent
    # 4x4/s1 conv on a (112,112,12) image instead of 7x7/s2 on (224,224,3),
    # quadrupling MXU input-lane utilization in the stem
    net = get_model("resnet50_v1", classes=1000, layout="NHWC",
                    stem_s2d=os.environ.get("BENCH_S2D") == "1")
    net.initialize(init=mx.init.Xavier())
    if dtype == "bfloat16":
        net.cast("bfloat16")
    x = nd.array(np.random.randn(batch, 224, 224, 3).astype(np.float32))
    if dtype == "bfloat16":
        x = x.astype("bfloat16")
    y = nd.array(np.random.randint(0, 1000, batch))
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    flops_per_sample = RESNET50_FLOPS_PER_SAMPLE
    return net, L, x, y, flops_per_sample, "resnet50_imagenet"


def _build_bert(batch, dtype):
    """Secondary benchmark (BASELINE §6): BERT-base pretraining-shape step
    (seq 128, cls head as the loss surface)."""
    from incubator_mxnet_tpu.models.bert import BERTModel
    seq = int(os.environ.get("BENCH_SEQ", "128"))
    layers = int(os.environ.get("BENCH_LAYERS", "12"))
    bert = BERTModel(num_layers=layers, units=768, hidden_size=3072,
                     num_heads=12, max_length=seq, vocab_size=30522,
                     dropout=0.1, use_pooler=False)
    net = gluon.nn.HybridSequential()
    net.add(bert, gluon.nn.Dense(2, flatten=False, in_units=768))
    net.initialize(init=mx.init.Normal(0.02))
    if dtype == "bfloat16":
        net.cast("bfloat16")
    x = nd.array(np.random.randint(0, 30522, (batch, seq)))
    y = nd.array(np.random.randint(0, 2, (batch, seq)))
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    flops_per_sample = 6 * 110e6 * seq * layers / 12  # ~6*N*T per token pass
    return net, L, x, y, flops_per_sample, f"bert_base_seq{seq}"


def _build_lenet(batch, dtype):
    """BASELINE config 1: LeNet on MNIST shapes
    (example/image-classification/train_mnist.py)."""
    net = get_model("lenet", classes=10)
    net.initialize(init=mx.init.Xavier())
    if dtype == "bfloat16":
        net.cast("bfloat16")
    x = nd.array(np.random.rand(batch, 1, 28, 28).astype(np.float32))
    if dtype == "bfloat16":
        x = x.astype("bfloat16")
    y = nd.array(np.random.randint(0, 10, batch))
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    return net, L, x, y, 3 * 4.3e6, "lenet_mnist"


def _build_ssd(batch, dtype):
    """BASELINE config 4: SSD-512 VOC-shape training step (example/ssd).
    Synthetic boxes; hard negatives are re-mined against the CURRENT
    predictions every step, inside the compiled step (MultiBoxTarget is
    pure lax, so the mining compiles into the same XLA program — the
    reference's per-iteration MultiBoxTarget, minus its CPU round trip).
    Mining inputs are stop-gradiented: targets are labels, not a
    differentiable path."""
    from incubator_mxnet_tpu.models.ssd import ssd_512_resnet50_v1, SSDLoss
    classes = 20
    net = ssd_512_resnet50_v1(classes=classes, layout="NHWC")
    net.initialize(init=mx.init.Xavier())
    if dtype == "bfloat16":
        net.cast("bfloat16")
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(batch, 512, 512, 3).astype(np.float32))
    if dtype == "bfloat16":
        x = x.astype("bfloat16")
    label = np.zeros((batch, 2, 5), np.float32)
    for b in range(batch):
        for j in range(2):
            x0, y0 = rng.rand(2) * 0.5
            label[b, j] = [rng.randint(0, classes), x0, y0,
                           x0 + 0.3, y0 + 0.3]
    label_nd = nd.array(label)
    ssd_l = SSDLoss()

    def loss_fn(out, _y):
        anchor, cls_pred, box_pred = out
        bt, bm, ct = net.targets(nd.stop_gradient(anchor),
                                 nd.stop_gradient(cls_pred), label_nd)
        return ssd_l(cls_pred, box_pred, ct, bt, bm)

    y = nd.array(np.zeros(batch, np.float32))     # unused placeholder
    return net, loss_fn, x, y, 3 * 30e9, "ssd512_voc"


def _build_transformer_lm(batch, dtype):
    """Causal-LM step (GPT-2-base scale by default): fused-QKV causal
    flash attention, tied head, shifted-CE loss."""
    from incubator_mxnet_tpu.models import TransformerLM
    from incubator_mxnet_tpu.models.transformer_lm import lm_loss
    seq = int(os.environ.get("BENCH_SEQ", "512"))
    layers = int(os.environ.get("BENCH_LAYERS", "12"))
    units = int(os.environ.get("BENCH_UNITS", "768"))
    if units < 64 or units % 64:
        raise ValueError(f"BENCH_UNITS={units} must be a multiple of 64 "
                         "(64 dims per attention head)")
    vocab = 50257
    # dropout 0 by default: attention-weight dropout forces the dense
    # O(L^2) softmax path (ops/_raw.py) and the throughput bench should
    # measure the flash kernel; BENCH_DROPOUT restores training realism
    net = TransformerLM(vocab, num_layers=layers, units=units,
                        hidden_size=4 * units, num_heads=units // 64,
                        max_length=seq,
                        dropout=float(os.environ.get("BENCH_DROPOUT", "0")))
    net.initialize(init=mx.init.Normal(0.02))
    if dtype == "bfloat16":
        net.cast("bfloat16")
    x = nd.array(np.random.randint(0, vocab, (batch, seq)))

    def loss_fn(logits, y):
        return lm_loss(logits, y).mean()

    # fwd+bwd = 3x fwd. Per layer per sample: 6*params (block params
    # ~= 12*units^2 GEMMs) + the attention score/value matmuls
    # (QK^T + AV: 2 * 2*L^2*units). Plus the tied-head logits GEMM
    # (units x vocab per token — dense, ~30% of total at base config).
    # Only the input-embedding gather is excluded.
    flops_per_sample = (3 * (2 * 12 * units * units * seq
                             + 4 * seq * seq * units) * layers
                        + 3 * 2 * seq * units * vocab)
    return net, loss_fn, x, x, flops_per_sample, f"gpt_{units}_seq{seq}"


def _recsys_config():
    """The recsys family's shape knobs (bench.py is the env-exempt
    root; the package itself reads nothing raw)."""
    return {
        "tables": int(os.environ.get("BENCH_RECSYS_TABLES", "8")),
        "vocab": int(os.environ.get("BENCH_RECSYS_VOCAB", "512")),
        "dim": int(os.environ.get("BENCH_RECSYS_DIM", "32")),
        "dense": int(os.environ.get("BENCH_RECSYS_DENSE", "13")),
        "bag": int(os.environ.get("BENCH_RECSYS_BAG", "4")),
    }


def _recsys_row(rng, cfg):
    """One synthetic record: dense features + zipf-distributed ids
    (float-encoded; exact for vocab < 2^24) + a learnable click label
    (parity of the first table's first id — the tables, not the dense
    features, carry the signal, so a decreasing loss proves the
    embedding path trains)."""
    dense = rng.randn(cfg["dense"]).astype(np.float32)
    n_ids = cfg["tables"] * cfg["bag"]
    ids = np.minimum(rng.zipf(1.5, (n_ids,)) - 1,
                     cfg["vocab"] - 1).astype(np.float32)
    label = np.float32(int(ids[0]) % 2)
    return np.concatenate([dense, ids, [label]])


def _build_recsys(batch, dtype):
    """DLRM (models/dlrm.py): embedding bags on the model axis + MLPs +
    pairwise interaction — the memory/comms-bound family
    (docs/embedding.md). Ids ride float32 regardless of `dtype` (the
    id-normalization path rounds them back to int32 exactly); a
    bfloat16 run casts the MLPs and tables only."""
    from incubator_mxnet_tpu.models.dlrm import (dlrm_small, dlrm_loss,
                                                 dlrm_flops_per_sample)
    cfg = _recsys_config()
    net = dlrm_small(num_tables=cfg["tables"], vocab_size=cfg["vocab"],
                     embed_dim=cfg["dim"], dense_dim=cfg["dense"],
                     bag_size=cfg["bag"])
    net.initialize(init=mx.init.Normal(0.05))
    if dtype == "bfloat16":
        net.cast("bfloat16")
    rng = np.random.RandomState(0)
    rows = np.stack([_recsys_row(rng, cfg) for _ in range(batch)])
    x = nd.array(rows[:, :-1])
    y = nd.array(rows[:, -1])

    def loss_fn(logits, yb):
        return dlrm_loss(logits, yb).mean()

    flops_per_sample = dlrm_flops_per_sample(net)
    return net, loss_fn, x, y, flops_per_sample, "dlrm_recsys"


_BENCH_MODELS = {"resnet50": _build_resnet, "bert": _build_bert,
                 "lenet": _build_lenet, "ssd": _build_ssd,
                 "transformer_lm": _build_transformer_lm,
                 "recsys": _build_recsys}

# per-model default global batch — the ONE home (a run without an explicit
# BENCH_BATCH ran at THIS batch, and the tuning-cache key must say so)
DEFAULT_BATCH = {"resnet50": 128, "bert": 32, "lenet": 512, "ssd": 16,
                 "transformer_lm": 16, "recsys": 256, "serving": 1}


def _mfu(samples_per_s, flops_per_sample, dtype):
    """Model FLOPs utilization: achieved model FLOP/s over the device's
    peak — ROADMAP item 1's regression metric, emitted into every
    training BENCH json. Peaks come from perfscope's shared table, so
    this number and extra.perfscope's MFU decomposition agree by
    construction. None on a device the table does not hold: a CPU run
    has no utilisation to report."""
    from incubator_mxnet_tpu.perfscope.cost import (device_peaks,
                                                    peak_flops_for)
    peak = peak_flops_for(dtype, device_peaks())
    if not peak:
        return None
    return round(samples_per_s * flops_per_sample / peak, 6)

# per-sample input shapes for the serving bench (BENCH_MODEL=serving)
_SERVING_SHAPES = {"lenet": (1, 28, 28), "resnet50_v1": (224, 224, 3)}


def _serving_bench():
    """BENCH_MODEL=serving: the inference-path benchmark. Freezes a
    model_zoo network (AOT per-bucket compile + warmup), starts the
    ModelServer, fires BENCH_SERVING_CLIENTS concurrent HTTP clients
    each sending BENCH_SERVING_REQS single-sample requests, and reports
    QPS + latency percentiles + batch-fill. Hard-fails (so the smoke
    and the driver see it) on any dropped request or any response that
    is not bit-exact against direct eager `net(x)`."""
    import threading
    import urllib.request

    from incubator_mxnet_tpu import devicescope
    from incubator_mxnet_tpu import profiler as prof
    from incubator_mxnet_tpu import servescope, serving

    # request-lifecycle tracing + tail-latency attribution rides every
    # serving bench by default (BENCH_SERVESCOPE=0 opts out) —
    # extra.servescope in the BENCH json. Sampled at a stride of 4
    # unless MXTPU_SERVESCOPE_SAMPLE says otherwise: the bench's
    # p50/p95/p99/QPS are the perf_regress-gated headline numbers, and
    # tracing EVERY sub-ms predict would measure the instrumentation,
    # not the server, against pre-servescope baselines
    if os.environ.get("BENCH_SERVESCOPE", "1") != "0":
        servescope.enable(
            sample=os.environ.get("MXTPU_SERVESCOPE_SAMPLE", 4))

    name = os.environ.get("BENCH_SERVING_MODEL", "lenet")
    if name not in _SERVING_SHAPES:
        raise ValueError(f"BENCH_SERVING_MODEL={name!r} has no serving "
                         f"shape; choose from {sorted(_SERVING_SHAPES)}")
    shape = _SERVING_SHAPES[name]
    clients = int(os.environ.get("BENCH_SERVING_CLIENTS", "64"))
    per_client = int(os.environ.get("BENCH_SERVING_REQS", "4"))
    max_delay_ms = float(os.environ.get("BENCH_SERVING_MAX_DELAY_MS", "25"))

    kwargs = {"layout": "NHWC"} if name.startswith("resnet") else {}
    net = get_model(name, classes=10 if name == "lenet" else 1000, **kwargs)
    net.initialize(init=mx.init.Xavier())

    frozen = [None]
    trace_path, compile_s, warmup_s = _profiled_compile_warmup(
        lambda: frozen.__setitem__(0, net.freeze(input_shape=shape)),
        lambda: None)           # freeze() warms every bucket itself
    srv = serving.ModelServer(frozen[0], max_delay_ms=max_delay_ms,
                              queue_limit=max(256, clients * per_client))
    host, port = srv.start()
    _log(f"serving {name} at {srv.address} buckets={frozen[0].buckets}")

    n_req = clients * per_client
    rng = np.random.RandomState(0)
    X = rng.rand(n_req, *shape).astype(np.float32)
    outputs = [None] * n_req
    failures = []

    def client(c):
        for j in range(per_client):
            i = c * per_client + j
            body = json.dumps({"data": X[i].tolist(),
                               "timeout_ms": 60000}).encode()
            try:
                r = urllib.request.urlopen(urllib.request.Request(
                    f"http://{host}:{port}/predict", data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=120)
                outputs[i] = json.loads(r.read())
            except Exception as e:  # noqa: BLE001
                failures.append((i, f"{type(e).__name__}: {e}"))

    _log(f"firing {clients} clients x {per_client} requests")
    # BENCH_DEVICESCOPE=1: one measured device window over the serving
    # dispatches (the batcher marks each executed batch), upgrading the
    # attribution's device_exec provenance to measured(profile)
    ds_win = None
    if os.environ.get("BENCH_DEVICESCOPE", "") == "1":
        ds_win = devicescope.capture(
            steps=int(os.environ.get("BENCH_DEVICESCOPE_STEPS", "10"))
        ).start()
    t0 = time.time()
    with prof.record_function("bench.steady", "bench", sync=False):
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    serve_s = time.time() - t0
    if ds_win is not None:
        ds_win.stop()
    stats = srv.stats()             # ONE registry snapshot: every
    srv.stop()                      # derived number below reads it
    #                                 (graceful drain)

    if failures:
        raise RuntimeError(f"{len(failures)}/{n_req} requests failed; "
                           f"first: {failures[0]}")
    # bit-exactness: reconstruct each dispatched batch (batch_id /
    # batch_index from the responses) and run net() — HYBRIDIZED, i.e.
    # the compiled CachedOp forward, the only path any compiled serving
    # stack can promise bit-identity with (per-op eager may differ by
    # ~1 ULP from any fused program; docs/serving.md) — on the SAME
    # padded batch: every served row must be bit-identical. The eager
    # per-request diff is reported as a number, not asserted.
    by_batch = {}
    for i in range(n_req):
        by_batch.setdefault(outputs[i]["batch_id"], []).append(i)
    eager_diff = 0.0
    for i in range(0, n_req, max(1, n_req // 16)):
        got = np.asarray(outputs[i]["output"], np.float32)
        ref1 = net(nd.array(X[i:i + 1])).asnumpy()[0]
        eager_diff = max(eager_diff, float(np.abs(got - ref1).max()))
    net.hybridize()
    for bid, idxs in by_batch.items():
        rows = sorted(idxs, key=lambda i: outputs[i]["batch_index"])
        bsz = outputs[rows[0]]["batch_size"]
        if len(rows) != bsz:
            raise RuntimeError(f"batch {bid}: {len(rows)} responses but "
                               f"batch_size={bsz}")
        xb = X[rows]
        bucket = frozen[0].bucket_for(bsz)
        if bucket != bsz:
            xb = np.concatenate(
                [xb, np.zeros((bucket - bsz,) + xb.shape[1:], xb.dtype)])
        ref = net(nd.array(xb)).asnumpy()
        for row_pos, i in enumerate(rows):
            got = np.asarray(outputs[i]["output"], np.float32)
            if not np.array_equal(got, ref[row_pos]):
                raise RuntimeError(
                    f"batch {bid} row {row_pos} (request {i}) diverges "
                    f"from the compiled net() forward on the same batch: "
                    f"max abs diff {np.abs(got - ref[row_pos]).max()}")
    dropped = n_req - int(stats.get("serving.responses", 0))
    if dropped:
        raise RuntimeError(f"{dropped} requests dropped "
                           f"(responses != submitted)")

    qps = n_req / serve_s
    # the histogram comes from the SAME snapshot as the percentiles —
    # a second counters() read here could see a later epoch than the
    # stats-derived numbers and trip the validator's lost-observations
    # check under concurrent traffic
    hist = stats.get("serving.latency_ms") or {}
    extra_serving = {
        "model": name, "clients": clients, "per_client": per_client,
        "requests": n_req,
        "responses": int(stats.get("serving.responses", 0)),
        "batches": int(stats.get("serving.batches", 0)),
        "batch_fill": round(stats.get("batch_fill", 0.0), 3),
        "rejected_queue_full": int(stats.get("serving.rejected_queue_full",
                                             0)),
        "rejected_deadline": int(stats.get("serving.rejected_deadline", 0)),
        "rejected_deadline_post_batch": int(stats.get(
            "serving.rejected_deadline_post_batch", 0)),
        "rejected_invalid": int(stats.get("serving.rejected_invalid", 0)),
        "qps": round(qps, 2),
        "p50_ms": stats.get("p50_ms"),
        "p95_ms": stats.get("p95_ms"),
        "p99_ms": stats.get("p99_ms"),
        "latency_ms": hist,
        "max_delay_ms": max_delay_ms,
        "buckets": list(frozen[0].buckets),
        "bit_exact": True,        # vs compiled net() on the same batch
        "max_abs_diff_vs_single_eager": eager_diff,
        "n_dispatch_batches": len(by_batch),
    }
    result = {
        "metric": f"serving_{name}_requests_per_sec",
        "value": round(qps, 2),
        "unit": "requests/sec",
        "vs_baseline": None,
        "extra": {"model": f"serving_{name}", "batch": None,
                  "dtype": "float32", "steps": n_req,
                  "serving": extra_serving,
                  "device": str(jax.devices()[0])},
    }
    from incubator_mxnet_tpu import perfscope as _psmod
    if _psmod._PS is not None:
        # serving has no train-step budget, but the per-bucket roofline
        # verdicts still ride along
        result["extra"]["perfscope"] = _psmod.bench_extra(None)
    if servescope._SS is not None:
        # the tail-latency attribution (per-bucket components + the
        # roofline/resharding verdict join — docs/servescope.md)
        result["extra"]["servescope"] = servescope.bench_extra()
    if ds_win is not None:
        result["extra"]["devicescope"] = devicescope.bench_extra()
    _finish_profile(result, trace_path, compile_s=compile_s,
                    warmup_s=warmup_s, steady_s=serve_s)
    return result


class _CastNorm(gluon.nn.HybridBlock):
    """Device-side input finishing: cast to the compute dtype and, for raw
    uint8 input, apply (x/1 - mean)/std INSIDE the compiled step. The host
    then ships raw decoded bytes — 4x less host-to-device traffic than float32
    — and normalization fuses into the step (reference contrast:
    iter_image_recordio_2.cc normalizes on CPU threads)."""

    def __init__(self, dtype, normalize=False,
                 mean=(123.68, 116.28, 103.53), std=(58.40, 57.12, 57.38)):
        super().__init__()
        self._dtype = dtype
        self._normalize = normalize
        self._mean = np.asarray(mean, np.float32)
        self._std = np.asarray(std, np.float32)

    def forward(self, x):
        from incubator_mxnet_tpu.ndarray import _apply
        import jax.numpy as jnp
        dt, norm = self._dtype, self._normalize
        mean, std = self._mean, self._std

        def fn(a):
            a = a.astype(jnp.float32)
            if norm:
                a = (a - mean) / std          # NHWC: broadcasts over C
            return a.astype(dt)

        return _apply(fn, [x], name="cast_norm")


def _ensure_bench_rec(n, size):
    """Synthetic indexed .rec of n JPEGs at size x size (cached on disk:
    encoding hundreds of JPEGs on the 1-core box is slow)."""
    from incubator_mxnet_tpu import recordio
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_rec")
    os.makedirs(d, exist_ok=True)
    rec = os.path.join(d, f"train_{size}_{n}.rec")
    idx = os.path.join(d, f"train_{size}_{n}.idx")
    if os.path.exists(rec) and os.path.exists(idx):
        return rec
    _log(f"building synthetic record file: {n} JPEGs @ {size}px")
    rng = np.random.RandomState(0)
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(n):
        img = rng.randint(0, 256, (size, size, 3), np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 1000), i, 0), img, quality=90))
    w.close()
    return rec


def _io_slow_transform():
    """BENCH_IO_SLOW_MS: injected per-batch decode latency (a sleep in
    the decode pool's transform hook) — the smoke's stand-in for an
    expensive augment/parse, so a CPU box can demonstrate that the pool
    hides decode wall behind compute. Returns (transform|None, ms)."""
    ms = float(os.environ.get("BENCH_IO_SLOW_MS", "0") or 0)
    if ms <= 0:
        return None, 0.0

    def slow(x, y, _s=ms / 1e3):
        time.sleep(_s)
        return x, y
    return slow, ms


def _io_extra(workers, depth, slow_ms=0.0):
    """extra.io: the ingest pipeline's geometry + per-stage walls, read
    from the io.* counter family (trace_check's check_io_extra
    validates the shape; docs/io.md explains reading the split)."""
    from incubator_mxnet_tpu import profiler as prof
    c = prof.counters()

    def ms(k):
        return round(float(c.get(f"io/io.{k}", 0.0)), 3)

    io = {"workers": int(workers), "depth": int(depth),
          "batches_prefetched": int(c.get("io/io.batches_prefetched", 0)),
          "wait_ms": ms("wait_ms"), "read_ms": ms("read_ms"),
          "decode_ms": ms("decode_ms"), "stage_ms": ms("stage_ms"),
          "put_ms": ms("put_ms")}
    if c.get("io/io.batches_skipped"):
        io["batches_skipped"] = int(c["io/io.batches_skipped"])
    if c.get("io/io.records_read"):
        io["records_read"] = int(c["io/io.records_read"])
    if slow_ms:
        io["slow_ms"] = float(slow_ms)
    return io


def _record_data_bench(mode, batch, steps, dtype):
    """BENCH_DATA=record | record_cached: ResNet-50 trained from the real
    JPEG input path instead of synthetic tensors.

    record        — ImageRecordIter decodes+augments on native engine
                    threads with a bounded prefetch queue; the queue runs
                    ahead of the chip, so host decode overlaps device
                    compute.
    record_cached — decode ONCE into a host uint8 cache (the reference's
                    im2rec pre-resize moves work offline the same way),
                    then ship raw uint8 slices; normalize on device.
    Reports the data-path rate and end-to-end rate, and names the
    bottleneck."""
    import incubator_mxnet_tpu.io as mio
    size = int(os.environ.get("BENCH_IMG_SIZE", "224"))
    n_img = int(os.environ.get("BENCH_REC_IMAGES", str(max(4 * batch, 512))))
    rec = _ensure_bench_rec(n_img, size)

    core = get_model("resnet50_v1", classes=1000, layout="NHWC")
    net = gluon.nn.HybridSequential()
    net.add(_CastNorm(dtype, normalize=(mode == "record_cached")))
    net.add(core)
    net.initialize(init=mx.init.Xavier())
    if dtype == "bfloat16":
        core.cast("bfloat16")
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9,
                              wd=1e-4, multi_precision=(dtype == "bfloat16"))
    from incubator_mxnet_tpu.autotune import knobs as _knobs
    _kc = _knobs.KnobConfig.from_env()
    step = FusedTrainStep(net, L, opt, remat=_kc.remat,
                          remat_policy=_kc.remat_policy)

    threads = int(os.environ.get("BENCH_DECODE_THREADS", "4"))
    def make_iter():
        return mio.ImageRecordIter(
            path_imgrec=rec, data_shape=(3, size, size), batch_size=batch,
            shuffle=True, rand_mirror=True, layout="NHWC",
            preprocess_threads=threads, prefetch_buffer=8,
            mean_r=123.68, mean_g=116.28, mean_b=103.53,
            std_r=58.40, std_g=57.12, std_b=57.38)

    if mode == "record_cached":
        # one decode pass builds the uint8 cache; augment = mirror flip on
        # the cached tensor (cheap), normalization happens on device
        _log("building uint8 cache (one decode pass)")
        from incubator_mxnet_tpu.image import imdecode
        from incubator_mxnet_tpu.recordio import MXIndexedRecordIO, unpack
        r = MXIndexedRecordIO(rec[:-4] + ".idx", rec, "r")
        cache = np.empty((len(r.keys), size, size, 3), np.uint8)
        labels = np.empty((len(r.keys),), np.float32)
        for j, k in enumerate(r.keys):
            h, img = unpack(r.read_idx(k))
            cache[j] = imdecode(img, to_rgb=True).asnumpy()
            labels[j] = h.label if np.isscalar(h.label) else h.label[0]
        rng = np.random.RandomState(0)

        def batches():
            while True:
                sel = rng.randint(0, len(cache), batch)
                xb = cache[sel]
                if rng.rand() < 0.5:
                    xb = xb[:, :, ::-1]        # mirror augment on cache
                yield nd.array(np.ascontiguousarray(xb)), nd.array(labels[sel])
        gen = batches()
        next_batch = lambda: next(gen)           # noqa: E731
    else:
        it = [make_iter()]

        def next_batch():
            try:
                b = it[0].next()
            except StopIteration:
                it[0].reset()
                b = it[0].next()
            return b.data[0], b.label[0]

    # data-path-only rate (no chip work): how fast can the host feed?
    probe_steps = max(4, min(steps, 8))
    next_batch()                                  # spin up threads
    t0 = time.time()
    for _ in range(probe_steps):
        xb, yb = next_batch()
    np.asarray(xb.asnumpy()[:1])                  # materialize
    data_rate = batch * probe_steps / (time.time() - t0)

    _log("compiling fused train step (record path)")
    xb, yb = next_batch()
    from incubator_mxnet_tpu import profiler as prof
    trace_path, compile_s, warmup_s = _profiled_compile_warmup(
        lambda: float(step(xb, yb)),
        lambda: float(step(*next_batch())))

    _log(f"timing {steps} end-to-end steps @ batch {batch} ({mode})")
    # strict mode audits THIS steady loop too (extra.mxlint must never
    # claim a clean audit for dispatches that were not guarded)
    from incubator_mxnet_tpu.mxlint import runtime as _mxa_mod
    strict_aud = _mxa_mod.auditor()
    if strict_aud is not None:
        strict_aud.mark_warmup_done()
    budget = _perfscope_budget()
    ds_win = _devicescope_window(steps)
    t0 = time.time()
    with prof.record_function("bench.steady", "bench", sync=False):
        for _i in range(steps):
            td = time.perf_counter()
            nb = next_batch()
            loss = _strict_guarded(strict_aud, lambda: step(*nb))
            disp_s = time.perf_counter() - td
            if budget is not None:
                budget.add_dispatch(disp_s)
            if ds_win is not None:
                ds_win.step(1, dispatch_ms=disp_s * 1e3,
                            sync=lambda: float(loss), workload="train")
            _memscope_mark(_i + 1)
        loss_val = float(loss)                    # host fetch = barrier
    dt = time.time() - t0
    if ds_win is not None:
        ds_win.stop()
    e2e = batch * steps / dt
    bottleneck = ("input-bound (decode/host)" if data_rate < 1.2 * e2e
                  else "chip-bound")
    result = {
        "metric": "resnet50_imagenet_images_per_sec_per_chip",
        "value": round(e2e, 2),
        "unit": "images/sec",
        "vs_baseline": round(e2e / V100_BASELINE_IMG_S, 3),
        "extra": {"model": f"resnet50_{mode}", "batch": batch,
                  "dtype": dtype, "steps": steps,
                  "mfu": _mfu(e2e, RESNET50_FLOPS_PER_SAMPLE, dtype),
                  "data_path_img_s": round(data_rate, 2),
                  "bottleneck": bottleneck,
                  "decode_threads": threads,
                  "final_loss": round(loss_val, 4),
                  "device": str(jax.devices()[0])},
    }
    from incubator_mxnet_tpu.mxlint import runtime as _mxa_mod
    result["extra"]["mxlint"] = _mxa_mod.bench_extra()
    # record-path probe includes next_batch(): the synchronized step is
    # the end-to-end unit here (decode overlap is what the mode measures)
    _perfscope_settle(result, budget, steps, dt,
                      lambda: float(step(*next_batch())), steps_per_call=1,
                      flops_per_step=RESNET50_FLOPS_PER_SAMPLE * batch,
                      dtype=dtype)
    _finish_profile(result, trace_path, compile_s=compile_s,
                    warmup_s=warmup_s, steady_s=dt,
                    step_ms=dt / steps * 1e3)
    return result


def _ensure_token_rec(n, seq, vocab):
    """Synthetic indexed .rec of n int32 token sequences (cached on
    disk beside the JPEG benches' records). Each record is one packed
    (seq,) int32 row — the LM analogue of the JPEG file."""
    from incubator_mxnet_tpu import recordio
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_rec")
    os.makedirs(d, exist_ok=True)
    rec = os.path.join(d, f"tokens_{seq}_{n}.rec")
    idx = os.path.join(d, f"tokens_{seq}_{n}.idx")
    if os.path.exists(rec) and os.path.exists(idx):
        return rec
    _log(f"building synthetic token record file: {n} rows @ seq {seq}")
    rng = np.random.RandomState(0)
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(n):
        toks = rng.randint(0, vocab, (seq,)).astype(np.int32)
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, 0.0, i, 0), toks.tobytes()))
    w.close()
    return rec


def _token_record_bench(batch, steps, dtype):
    """BENCH_DATA=record x BENCH_MODEL=transformer_lm: causal-LM
    training fed from the indexed record path through the staged ingest
    pipeline (ShardedRecordReader → DevicePrefetcher) instead of
    synthetic tensors — token rows unpack on the reader thread, batches
    assemble and run the optional transform in the decode pool, and the
    transfer stage lands them on device. The LM twin of
    _record_data_bench; reports the same data-path vs end-to-end split
    plus extra.io stage walls."""
    from incubator_mxnet_tpu.io.pipeline import ShardedRecordReader
    from incubator_mxnet_tpu.io.prefetch import DevicePrefetcher
    from incubator_mxnet_tpu.recordio import unpack
    net, L, x, _y, flops_per_sample, tag = _build_transformer_lm(batch,
                                                                 dtype)
    seq = int(x.shape[1])
    vocab = 50257
    n_rec = int(os.environ.get("BENCH_REC_IMAGES", str(max(4 * batch,
                                                           256))))
    rec = _ensure_token_rec(n_rec, seq, vocab)
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9,
                              wd=1e-4,
                              multi_precision=(dtype == "bfloat16"))
    from incubator_mxnet_tpu.autotune import knobs as _knobs
    _kc = _knobs.KnobConfig.from_env()
    step = FusedTrainStep(net, L, opt, remat=_kc.remat,
                          remat_policy=_kc.remat_policy)

    def decode_row(payload):
        _h, s = unpack(payload)
        return np.frombuffer(s, np.int32).reshape(seq)

    reader = ShardedRecordReader(rec[:-4] + ".idx", rec,
                                 decode_fn=decode_row)

    def batches():
        it = iter(reader)
        while True:
            rows = []
            while len(rows) < batch:
                try:
                    rows.append(next(it))
                except StopIteration:
                    reader.reset()
                    it = iter(reader)
            xb = np.stack(rows)
            yield xb, xb       # causal LM: the loss shifts internally

    io_tf, io_slow_ms = _io_slow_transform()
    pf = DevicePrefetcher(batches(), depth=_kc.prefetch_depth,
                          workers=_kc.io_workers, transform=io_tf)

    # data-path-only rate: how fast can the sharded reader + pool feed?
    probe_steps = max(4, min(steps, 8))
    next(pf)                                      # spin up the stages
    t0 = time.time()
    for _ in range(probe_steps):
        xb, yb = next(pf)
    np.asarray(xb)[:1]                            # materialize
    data_rate = batch * probe_steps / (time.time() - t0)

    _log("compiling fused train step (token record path)")
    xb, yb = next(pf)
    from incubator_mxnet_tpu import profiler as prof
    trace_path, compile_s, warmup_s = _profiled_compile_warmup(
        lambda: float(step(nd.NDArray(xb), nd.NDArray(yb))),
        lambda: float(step(*map(nd.NDArray, next(pf)))))

    _log(f"timing {steps} end-to-end steps @ batch {batch} "
         f"(token record)")
    from incubator_mxnet_tpu.mxlint import runtime as _mxa_mod
    strict_aud = _mxa_mod.auditor()
    if strict_aud is not None:
        strict_aud.mark_warmup_done()
    budget = _perfscope_budget()
    ds_win = _devicescope_window(steps)
    t0 = time.time()
    with prof.record_function("bench.steady", "bench", sync=False):
        for _i in range(steps):
            td = time.perf_counter()
            nb = tuple(map(nd.NDArray, next(pf)))
            loss = _strict_guarded(strict_aud, lambda: step(*nb))
            disp_s = time.perf_counter() - td
            if budget is not None:
                budget.add_dispatch(disp_s)
            if ds_win is not None:
                ds_win.step(1, dispatch_ms=disp_s * 1e3,
                            sync=lambda: float(loss), workload="train")
            _memscope_mark(_i + 1)
        loss_val = float(loss)                    # host fetch = barrier
    dt = time.time() - t0
    if ds_win is not None:
        ds_win.stop()
    e2e = batch * steps / dt
    bottleneck = ("input-bound (read/decode host path)"
                  if data_rate < 1.2 * e2e else "chip-bound")
    result = {
        "metric": f"{tag}_samples_per_sec_per_chip",
        "value": round(e2e, 2),
        "unit": "samples/sec",
        "vs_baseline": None,
        "extra": {"model": f"{tag}_record", "batch": batch,
                  "dtype": dtype, "steps": steps,
                  "mfu": _mfu(e2e, flops_per_sample, dtype),
                  "data_path_samples_s": round(data_rate, 2),
                  "bottleneck": bottleneck,
                  "final_loss": round(loss_val, 4),
                  "device": str(jax.devices()[0])},
    }
    result["extra"]["io"] = _io_extra(pf._workers, _kc.prefetch_depth,
                                      slow_ms=io_slow_ms)
    result["extra"]["mxlint"] = _mxa_mod.bench_extra()
    _perfscope_settle(result, budget, steps, dt,
                      lambda: float(step(*map(nd.NDArray, next(pf)))),
                      steps_per_call=1,
                      flops_per_step=flops_per_sample * batch,
                      dtype=dtype)
    _finish_profile(result, trace_path, compile_s=compile_s,
                    warmup_s=warmup_s, steady_s=dt,
                    step_ms=dt / steps * 1e3)
    pf.close()
    return result


def _ensure_recsys_rec(n, cfg):
    """Synthetic indexed .rec of n recsys rows (cached beside the other
    benches' records). Each record is one packed float32 row:
    dense features + float-encoded zipf ids + label."""
    from incubator_mxnet_tpu import recordio
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_rec")
    os.makedirs(d, exist_ok=True)
    stem = (f"recsys_{cfg['dense']}_{cfg['tables']}x{cfg['bag']}"
            f"_{cfg['vocab']}_{n}")
    rec = os.path.join(d, stem + ".rec")
    idx = os.path.join(d, stem + ".idx")
    if os.path.exists(rec) and os.path.exists(idx):
        return rec
    _log(f"building synthetic recsys record file: {n} rows")
    rng = np.random.RandomState(0)
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(n):
        row = _recsys_row(rng, cfg).astype(np.float32)
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, 0.0, i, 0), row.tobytes()))
    w.close()
    return rec


def _recsys_bench(batch, steps, dtype, shard_mode):
    """BENCH_MODEL=recsys: DLRM training fed from the indexed record
    path through the staged ingest pipeline (ShardedRecordReader →
    DevicePrefetcher) — the categorical stream the embedding subsystem
    exists for. Reports extra.embedding (table census: per-device vs
    replicated bytes, dedup rate, rows touched/step — schema:
    tools/trace_check.py check_embedding_extra) on top of the io/
    sharding/perfscope sections the other record benches carry."""
    from incubator_mxnet_tpu.io.pipeline import ShardedRecordReader
    from incubator_mxnet_tpu.io.prefetch import DevicePrefetcher
    from incubator_mxnet_tpu.recordio import unpack
    from incubator_mxnet_tpu import embedding as _embmod
    from incubator_mxnet_tpu.models.dlrm import dlrm_bytes_per_sample
    cfg = _recsys_config()
    net, L, x, _y, flops_per_sample, tag = _build_recsys(batch, dtype)
    row_len = cfg["dense"] + cfg["tables"] * cfg["bag"] + 1
    n_rec = int(os.environ.get("BENCH_REC_IMAGES", str(max(4 * batch,
                                                           256))))
    rec = _ensure_recsys_rec(n_rec, cfg)
    opt = mx.optimizer.create(
        os.environ.get("BENCH_RECSYS_OPT", "rowsparseadagrad"),
        learning_rate=float(os.environ.get("BENCH_LR", "0.05")))
    from incubator_mxnet_tpu.autotune import knobs as _knobs
    _kc = _knobs.KnobConfig.from_env()
    step = FusedTrainStep(net, L, opt, remat=_kc.remat,
                          remat_policy=_kc.remat_policy,
                          sharding=shard_mode)

    def decode_row(payload):
        _h, s = unpack(payload)
        return np.frombuffer(s, np.float32).reshape(row_len)

    reader = ShardedRecordReader(rec[:-4] + ".idx", rec,
                                 decode_fn=decode_row)

    def batches():
        it = iter(reader)
        while True:
            rows = []
            while len(rows) < batch:
                try:
                    rows.append(next(it))
                except StopIteration:
                    reader.reset()
                    it = iter(reader)
            m = np.stack(rows)
            yield m[:, :-1], m[:, -1]

    io_tf, io_slow_ms = _io_slow_transform()
    pf = DevicePrefetcher(batches(), depth=_kc.prefetch_depth,
                          workers=_kc.io_workers, transform=io_tf)

    # data-path-only rate: how fast can the sharded reader + pool feed?
    probe_steps = max(4, min(steps, 8))
    next(pf)                                      # spin up the stages
    t0 = time.time()
    for _ in range(probe_steps):
        xb, yb = next(pf)
    np.asarray(xb)[:1]                            # materialize
    data_rate = batch * probe_steps / (time.time() - t0)

    _log("compiling fused train step (recsys record path)")
    xb, yb = next(pf)
    from incubator_mxnet_tpu import profiler as prof
    first_loss = []
    trace_path, compile_s, warmup_s = _profiled_compile_warmup(
        lambda: (first_loss.append(float(step(nd.NDArray(xb),
                                              nd.NDArray(yb))))
                 or first_loss[0]),
        lambda: float(step(*map(nd.NDArray, next(pf)))))

    _log(f"timing {steps} end-to-end steps @ batch {batch} (recsys)")
    from incubator_mxnet_tpu.mxlint import runtime as _mxa_mod
    strict_aud = _mxa_mod.auditor()
    if strict_aud is not None:
        strict_aud.mark_warmup_done()
    budget = _perfscope_budget()
    ds_win = _devicescope_window(steps)
    t0 = time.time()
    with prof.record_function("bench.steady", "bench", sync=False):
        for _i in range(steps):
            td = time.perf_counter()
            raw_x, raw_y = next(pf)
            # host-side id accounting: the concrete batch is already in
            # hand, so the dedup-rate gauges cost one np.unique
            _embmod.observe_batch(
                np.asarray(raw_x)[:, cfg["dense"]:], cfg["vocab"])
            nb = (nd.NDArray(raw_x), nd.NDArray(raw_y))
            loss = _strict_guarded(strict_aud, lambda: step(*nb))
            disp_s = time.perf_counter() - td
            if budget is not None:
                budget.add_dispatch(disp_s)
            if ds_win is not None:
                ds_win.step(1, dispatch_ms=disp_s * 1e3,
                            sync=lambda: float(loss), workload="train")
            _memscope_mark(_i + 1)
        loss_val = float(loss)                    # host fetch = barrier
    dt = time.time() - t0
    if ds_win is not None:
        ds_win.stop()
    e2e = batch * steps / dt
    bottleneck = ("input-bound (read/decode host path)"
                  if data_rate < 1.2 * e2e else "chip-bound")
    result = {
        "metric": f"{tag}_samples_per_sec_per_chip",
        "value": round(e2e, 2),
        "unit": "samples/sec",
        "vs_baseline": None,
        "extra": {"model": f"{tag}_record", "batch": batch,
                  "dtype": dtype, "steps": steps,
                  "mfu": _mfu(e2e, flops_per_sample, dtype),
                  "data_path_samples_s": round(data_rate, 2),
                  "bottleneck": bottleneck,
                  "first_loss": round(first_loss[0], 4),
                  "final_loss": round(loss_val, 4),
                  "device": str(jax.devices()[0])},
    }
    emb_extra = _embmod.bench_extra()
    emb_extra["bytes_per_sample"] = round(dlrm_bytes_per_sample(
        net, emb_extra.get("dedup_rate") or 0.0), 3)
    result["extra"]["embedding"] = emb_extra
    if shard_mode is not None:
        from incubator_mxnet_tpu.parallel import sharding as _shmod
        result["extra"]["sharding"] = _shmod.summary()
    result["extra"]["io"] = _io_extra(pf._workers, _kc.prefetch_depth,
                                      slow_ms=io_slow_ms)
    result["extra"]["mxlint"] = _mxa_mod.bench_extra()
    _perfscope_settle(result, budget, steps, dt,
                      lambda: float(step(*map(nd.NDArray, next(pf)))),
                      steps_per_call=1,
                      flops_per_step=flops_per_sample * batch,
                      dtype=dtype)
    _finish_profile(result, trace_path, compile_s=compile_s,
                    warmup_s=warmup_s, steady_s=dt,
                    step_ms=dt / steps * 1e3)
    pf.close()
    return result


def main():
    global _CURRENT_METRIC
    _main_t0 = time.time()
    model = os.environ.get("BENCH_MODEL", "resnet50")
    if model not in _BENCH_MODELS and model != "serving":
        raise ValueError(f"unknown BENCH_MODEL {model!r}; choose from "
                         f"{sorted(_BENCH_MODELS) + ['serving']}")
    try:
        default_batch = DEFAULT_BATCH[model]
    except KeyError:
        raise ValueError(f"BENCH_MODEL {model!r} has no default batch; "
                         f"set BENCH_BATCH explicitly")
    from incubator_mxnet_tpu.autotune import knobs as _knobs
    batch = int(_knobs.resolve("batch")[0] or default_batch)
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")

    # MXTPU_AUTOTUNE=1: resolve the tuning cache / run the bounded
    # search FIRST — before this process touches jax (its trials are
    # child processes that need the chip), and before the mesh registers
    # and the knobs resolve below: the winner installs as the below-env
    # default layer, so everything from loop_chunk to the mesh spec
    # starts tuned on a cache hit
    autotune_extra = None
    if model != "serving":
        autotune_extra = _bench_autotune(model, batch, dtype)
    _require_tpu()
    # persistent compilation cache: JAX_COMPILATION_CACHE_DIR when set,
    # else <checkout>/.jax_cache — repeat bench runs start fast
    use_compile_cache(os.path.dirname(os.path.abspath(__file__)))
    # persistent-cache integrity canary (runtime/cache_guard): validate
    # the cache READ path now — before the big compile — so a corrupt
    # cache recompiles fresh instead of training on garbage executables
    from incubator_mxnet_tpu.runtime import cache_guard as _cg
    _log(f"compile-cache canary ok={_cg.check()}")
    # before model build so parameter allocations land in the ledger
    diag_dir = _bench_diag_start()
    if diag_dir:
        _log(f"diagnostics armed (sampler + flight recorder) -> {diag_dir}")
    if _bench_healthmon_start() is not None:
        _log("healthmon armed (watchdogs + structured event log)")
    if _bench_perfscope_start() is not None:
        _log("perfscope armed (roofline cost capture + step decomposition)")
    if _bench_commscope_start() is not None:
        _log("commscope armed (collective inventory + resharding detector)")
    if _bench_devicescope_start() is not None:
        _log("devicescope armed (windowed device-timeline capture)")
    if _bench_memscope_start() is not None:
        _log("memscope armed (program footprints + watermark ring + "
             "OOM forensics)")
    strict_aud = _bench_strict_start()
    if strict_aud is not None:
        _log("mxlint strict mode armed (host-sync + recompile + "
             "donation auditing)")
    # BENCH_MESH: register the global mesh BEFORE model build so param
    # init and the executor resolve against it
    shard_mode = _bench_mesh()
    np.random.seed(0)
    mx.random.seed(0)

    _CURRENT_METRIC = ("resnet50_imagenet_images_per_sec_per_chip"
                       if model == "resnet50"
                       else f"bench_{model}_samples_per_sec_per_chip")
    if model == "serving":
        _CURRENT_METRIC = (
            f"serving_{os.environ.get('BENCH_SERVING_MODEL', 'lenet')}"
            f"_requests_per_sec")
        result = _serving_bench()
        print(json.dumps(result))
        return
    if model == "recsys":
        # the recsys family ALWAYS trains from the record stream (the
        # categorical input path is the workload); BENCH_DATA does not
        # apply
        result = _recsys_bench(batch, steps, dtype, shard_mode)
        if autotune_extra is not None:
            autotune_extra["resolved"] = \
                _knobs.KnobConfig.from_env().to_dict()
            result.setdefault("extra", {})["autotune"] = autotune_extra
        print(json.dumps(result))
        return
    data_mode = os.environ.get("BENCH_DATA", "synthetic")
    if data_mode in ("record", "record_cached"):
        if model == "transformer_lm":
            if data_mode != "record":
                raise ValueError(
                    "BENCH_DATA=record_cached is a JPEG-path mode; "
                    "transformer_lm's token path supports "
                    "BENCH_DATA=record only")
            result = _token_record_bench(batch, steps, dtype)
        elif model == "resnet50":
            result = _record_data_bench(data_mode, batch, steps, dtype)
        else:
            raise ValueError(
                f"BENCH_DATA={data_mode} supports BENCH_MODEL=resnet50 "
                f"(the JPEG input path) or transformer_lm (the token "
                f"record path), got {model!r}")
        if autotune_extra is not None:
            autotune_extra["resolved"] = \
                _knobs.KnobConfig.from_env().to_dict()
            result.setdefault("extra", {})["autotune"] = autotune_extra
        print(json.dumps(result))
        return

    # builders can do real device work (SSD runs a full forward to
    # precompute matching targets) — deadline it like every device phase
    with _phase_deadline(int(os.environ.get("BENCH_BUILD_TIMEOUT", "1200")),
                         "model build"):
        net, L, x, y, flops_per_sample, tag = _BENCH_MODELS[model](batch,
                                                                   dtype)
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9, wd=1e-4,
                              multi_precision=(dtype == "bfloat16"))
    # knob resolution through the ONE table (autotune.knobs): call-site
    # > BENCH_* > MXTPU_* > cached tuning winner > default. loop_chunk
    # > 1 runs the steady phase through the whole-loop executor
    # (mxtpu.trainloop) — N micro-steps per dispatch, device-side
    # double-buffered prefetch, per-micro-step lr; the io.*/trainloop.*
    # counter families land in extra.counters.
    knob_cfg = _knobs.KnobConfig.from_env()
    if autotune_extra is not None:
        # what the run ACTUALLY resolved to (env overrides beat the
        # tuner) — the config perf_regress compares across artifacts
        autotune_extra["resolved"] = knob_cfg.to_dict()
    loop_k = knob_cfg.loop_chunk
    loop = None
    io_tf, io_slow_ms = _io_slow_transform()
    if loop_k > 1:
        from incubator_mxnet_tpu.trainloop import TrainLoop
        loop = TrainLoop(net, L, opt, chunk=loop_k,
                         remat=knob_cfg.remat,
                         remat_policy=knob_cfg.remat_policy,
                         sharding=shard_mode,
                         io_workers=knob_cfg.io_workers,
                         io_transform=io_tf)
        step = loop.step
    else:
        step = FusedTrainStep(net, L, opt,
                              remat=knob_cfg.remat,
                              remat_policy=knob_cfg.remat_policy,
                              sharding=shard_mode)
    if shard_mode is not None:
        from incubator_mxnet_tpu.parallel import sharding as _shmod
        dp_ax = _shmod.data_axis(step.mesh) or "dp"
        dp_n = int(step.mesh.shape.get(dp_ax, 1))
        if batch % dp_n:
            raise ValueError(
                f"BENCH_BATCH={batch} does not divide the {dp_ax}={dp_n} "
                f"mesh axis (BENCH_MESH={os.environ['BENCH_MESH']}); "
                f"pick a divisible global batch")

    _bench_resilience_start(step)

    # compile + warmup. Steps chain through updated params, so fetching the
    # final loss (a device barrier) times them all.
    # In loop mode the CHUNK program is the only one the steady phase runs,
    # so it is the one compiled/warmed (the single-step program is never
    # built — jax.jit is lazy).
    from incubator_mxnet_tpu import profiler as prof
    if loop is not None:
        import jax.numpy as jnp
        loop_xs = jnp.broadcast_to(x._data, (loop_k,) + x._data.shape)
        loop_ys = jnp.broadcast_to(y._data, (loop_k,) + y._data.shape)
        _log(f"compiling whole-loop chunk (k={loop_k})")
        trace_path, compile_s, warmup_s = _profiled_compile_warmup(
            lambda: float(loop.run_chunk(loop_xs, loop_ys)[loop_k - 1]),
            lambda: float(loop.run_chunk(loop_xs, loop_ys)[loop_k - 1]))
    else:
        _log("compiling fused train step (first call)")
        trace_path, compile_s, warmup_s = _profiled_compile_warmup(
            lambda: float(step(x, y)),
            lambda: float(step(x, y)))
    if strict_aud is not None:
        # everything compiled so far was warmup; from here a re-capture
        # of a known program is a steady-state recompile finding
        strict_aud.mark_warmup_done()

    # BENCH_K > 1: dispatch k micro-steps as ONE XLA program (lax.scan in
    # FusedTrainStep.run_k) — amortizes per-step host dispatch
    # latency. Default 1 since the 2026-07-31 on-chip sweep MEASURED the k
    # hypothesis and refuted it: k=1 2064 img/s vs k=8 2015 img/s at the
    # same config (PERF.md) — the 62 ms step is device-bound, not
    # dispatch-bound, so the scan only adds compile surface.
    k = int(os.environ.get("BENCH_K", "1"))
    if loop is not None:
        chunks = max(1, steps // loop_k)
        _log(f"timing {chunks} chunks x {loop_k} micro-steps through the "
             f"whole-loop executor @ batch {batch} {dtype} "
             f"(in_program_lr={loop.in_program_lr})")

        def batches():
            while True:
                yield x, y

        budget = _perfscope_budget(steps_per_dispatch=loop_k)
        # loop mode: run_chunk marks the active devicescope window itself
        # (it knows one dispatch was loop_k steps), so no per-step marks
        ds_win = _devicescope_window(chunks * loop_k,
                                     steps_per_dispatch=loop_k)
        with loop._prefetcher(batches(), cycle=False) as pf:
            t0 = time.time()
            with prof.record_function("bench.steady", "bench", sync=False):
                for _ in range(chunks):
                    xb, yb = next(pf)
                    losses = _strict_guarded(
                        strict_aud, lambda: loop.run_chunk(xb, yb))
                    _healthmon_mark_step()   # one mark per dispatched chunk
                    _resilience_mark_step()
                loss_val = float(losses[loop_k - 1])    # host fetch = barrier
            dt = time.time() - t0
        if ds_win is not None:
            ds_win.stop()
        steps = chunks * loop_k
        k = loop_k
        # loop-mode host_gap rides trainloop.dispatch_ms (run_chunk's own
        # counter), so no per-dispatch timing is needed here
        probe_fn = lambda: float(loop.run_chunk(loop_xs,        # noqa: E731
                                                loop_ys)[loop_k - 1])
    elif k > 1:
        import jax.numpy as jnp
        xs = jnp.broadcast_to(x._data, (k,) + x._data.shape)
        ys = jnp.broadcast_to(y._data, (k,) + y._data.shape)
        _log(f"compiling k-step scan (k={k})")
        with _phase_deadline(int(os.environ.get("BENCH_COMPILE_TIMEOUT",
                                                "2400")),
                             "k-step compile"):
            float(step.run_k(xs, ys)[k - 1])        # compile + warmup
        chunks = max(1, steps // k)
        _log(f"timing {chunks} chunks x {k} micro-steps @ batch {batch} "
             f"{dtype}")
        budget = _perfscope_budget(steps_per_dispatch=k)
        ds_win = _devicescope_window(chunks * k, steps_per_dispatch=k)
        t0 = time.time()
        with prof.record_function("bench.steady", "bench", sync=False):
            for _i in range(chunks):
                td = time.perf_counter()
                losses = _strict_guarded(strict_aud,
                                         lambda: step.run_k(xs, ys))
                disp_s = time.perf_counter() - td
                if budget is not None:
                    budget.add_dispatch(disp_s)
                if ds_win is not None:
                    # sync thunk = loss fetch, the one true barrier: a
                    # window closing at this mark must not close with
                    # its own steps still in flight (async dispatch)
                    ds_win.step(k, dispatch_ms=disp_s * 1e3,
                                sync=lambda: float(losses[k - 1]),
                                workload="train")
                _memscope_mark((_i + 1) * k)
                _healthmon_mark_step()     # one mark per dispatched chunk
                _resilience_mark_step()
            loss_val = float(losses[k - 1])         # host fetch = barrier
        dt = time.time() - t0
        if ds_win is not None:
            ds_win.stop()
        steps = chunks * k
        probe_fn = lambda: float(step.run_k(xs, ys)[k - 1])  # noqa: E731
    else:
        _log(f"timing {steps} steps @ batch {batch} {dtype}")
        budget = _perfscope_budget()
        ds_win = _devicescope_window(steps)
        t0 = time.time()
        with prof.record_function("bench.steady", "bench", sync=False):
            for _i in range(steps):
                td = time.perf_counter()
                loss = _strict_guarded(strict_aud, lambda: step(x, y))
                disp_s = time.perf_counter() - td
                if budget is not None:
                    budget.add_dispatch(disp_s)
                if ds_win is not None:
                    # see run_k path: the sync fetch only runs at the
                    # window boundary, so the other steps stay async
                    ds_win.step(1, dispatch_ms=disp_s * 1e3,
                                sync=lambda: float(loss),
                                workload="train")
                _memscope_mark(_i + 1)
                _healthmon_mark_step()
                _resilience_mark_step()
            loss_val = float(loss)
        dt = time.time() - t0
        if ds_win is not None:
            ds_win.stop()
        probe_fn = lambda: float(step(x, y))         # noqa: E731
    from incubator_mxnet_tpu import healthmon as _hm_mod
    if _hm_mod._HM is not None:
        # final-loss NaN sentinel: the one host value the bench fetched
        _hm_mod.observe_loss(loss_val)

    img_s = batch * steps / dt
    mfu = _mfu(img_s, flops_per_sample, dtype)

    # keep the headline metric name stable across rounds for the driver
    metric = ("resnet50_imagenet_images_per_sec_per_chip"
              if model == "resnet50" else f"{tag}_samples_per_sec_per_chip")
    _CURRENT_METRIC = metric
    result = {
        "metric": metric,
        "value": round(img_s, 2),
        "unit": "images/sec" if model == "resnet50" else "samples/sec",
        # the V100 390 img/s baseline is a ResNet-50 number; other models
        # report MFU instead of a cross-model ratio
        "vs_baseline": (round(img_s / V100_BASELINE_IMG_S, 3)
                        if model == "resnet50" else None),
        "extra": {"model": tag, "batch": batch, "dtype": dtype,
                  "steps": steps, "k_per_dispatch": k,
                  "mfu": mfu,
                  "loop_chunk": loop_k if loop is not None else None,
                  "in_program_lr": (loop.in_program_lr
                                    if loop is not None else None),
                  "k1_control_img_s": None,
                  "final_loss": round(loss_val, 4),
                  "device": str(jax.devices()[0])},
    }
    if loop is not None:
        # the ingest pipeline ran the steady phase (loop mode is the
        # only synthetic path with a prefetcher) — its stage walls are
        # the starvation-attribution record the smoke compares
        result["extra"]["io"] = _io_extra(loop.io_workers,
                                          loop.prefetch_depth,
                                          slow_ms=io_slow_ms)
    if shard_mode is not None:
        # the resolved layout the executor actually compiled: mesh shape,
        # per-param spec counts, fsdp on/off, per-device bytes
        from incubator_mxnet_tpu.parallel import sharding as _shmod
        result["extra"]["sharding"] = _shmod.summary()
    if autotune_extra is not None:
        # the tuning outcome (cache hit/miss, trials, winner, pruning
        # reasons, score provenance) — validated by trace_check's
        # check_autotune_extra in every training BENCH json
        result["extra"]["autotune"] = autotune_extra
    # strict-mode verdicts (or the {"strict": false} shape — uniform
    # schema, like extra.autotune); check_mxlint_extra validates it
    from incubator_mxnet_tpu.mxlint import runtime as _mxa_mod
    result["extra"]["mxlint"] = _mxa_mod.bench_extra()
    _perfscope_settle(result, budget, steps, dt, probe_fn,
                      steps_per_call=k,
                      flops_per_step=flops_per_sample * batch, dtype=dtype)
    _finish_profile(result, trace_path, compile_s=compile_s,
                    warmup_s=warmup_s, steady_s=dt,
                    step_ms=dt / steps * 1e3)
    # Self-check of the dispatch-latency hypothesis behind the K default:
    # time the ALREADY-COMPILED per-step path alongside, so every K>1
    # report carries its own k=1 control (the blind bet must measure
    # itself). Runs AFTER the headline is fully built, and a control that
    # errors must never destroy an already-measured number.
    # BENCH_K1_CONTROL=0 skips. (loop mode skips the control: its
    # single-step program was never compiled, so the control would time a
    # fresh compile, not dispatch)
    if k > 1 and loop is None \
            and os.environ.get("BENCH_K1_CONTROL", "1") == "1":
        try:
            n1 = max(4, min(10, steps // 2))
            t1 = time.time()
            for _ in range(n1):
                loss1 = step(x, y)
            float(loss1)
            k1_img_s = batch * n1 / (time.time() - t1)
            result["extra"]["k1_control_img_s"] = round(k1_img_s, 2)
            _log(f"k=1 control: {k1_img_s:.1f} img/s over {n1} steps "
                 f"(k={k} main run: {img_s:.1f})")
        except Exception as e:  # noqa: BLE001
            _log(f"k=1 control failed ({type(e).__name__}: {e}); "
                 "reporting main result without it")
    print(json.dumps(result))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # noqa: BLE001
        import traceback
        traceback.print_exc()
        # Emit a parseable JSON line even on failure so the driver records
        # a diagnostic instead of a bare rc=1.
        print(json.dumps({
            "metric": _CURRENT_METRIC,
            "value": 0.0,
            "unit": "images/sec",
            "vs_baseline": 0.0,
            "error": f"{type(e).__name__}: {e}"[:500],
        }))
        sys.exit(1)
