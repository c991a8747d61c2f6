"""mxtpu.autotune.trial — ONE way to measure a knob config.

Every trial executes a short steady-state bench.py window **in a
subprocess** and reads the measurement out of the emitted BENCH json.

Subprocess isolation is a design requirement, not a nicety:

* jax allows ONE profiler trace per process, so back-to-back devicescope
  windows (one per trial) are impossible in-process — the second window
  would DECLINE and every later trial would score on host_wall;
* a fresh process quarantines compile-cache state between configs (a
  corrupt deserialization in trial 3 cannot poison trial 4) and makes a
  trial death a counted skip instead of a tuner crash;
* the measured numbers come from the exact code path the driver runs.

A chip belongs to one process at a time, so a trial can only have the
device while its parent has NOT opened a jax backend: bench.py runs the
search before it touches jax, and :func:`run_trial` refuses — a counted
failed trial, with that reason — from a process that already holds an
accelerator (context.py, "one process per chip").

The measurement a trial yields (:func:`measurement_from_artifact`):
measured devicescope busy fraction + idle-gap taxonomy (score
provenance ``measured(profile)``), perfscope step wall / MFU /
``mfu_if_removed`` counterfactuals, and the headline throughput. When
the run carried no completed window (declined profiler, stripped
build), provenance degrades to ``host_wall`` and throughput decides —
marked, never silent.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from .knobs import KnobConfig

__all__ = ["TrialResult", "run_trial", "trial_env",
           "measurement_from_artifact", "score", "last_json_line",
           "SCORE_SOURCES"]

# score provenance taxonomy (extra.autotune + trace_check)
SCORE_SOURCES = ("measured(profile)", "host_wall")

# env vars a trial must never inherit: every BENCH_* (the config IS the
# trial), the ambient knob spellings (the config pins them explicitly),
# and MXTPU_AUTOTUNE itself (a trial that re-entered the tuner would
# recurse)
_SCRUB_PREFIXES = ("BENCH_",)
_SCRUB_EXACT = ("MXTPU_AUTOTUNE", "MXTPU_LOOP_CHUNK", "MXTPU_REMAT",
                "MXTPU_REMAT_POLICY", "MXTPU_PREFETCH_DEPTH",
                "MXTPU_IO_WORKERS", "MXTPU_MESH", "MXTPU_PALLAS",
                "MXTPU_NO_PALLAS", "MXTPU_FORCE_PALLAS",
                "MXTPU_DEVICESCOPE", "MXTPU_MEMSCOPE",
                "MXTPU_MEMSCOPE_CAPACITY", "MXTPU_MEMSCOPE_HEADROOM",
                "MXTPU_MEMSCOPE_RING")


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def last_json_line(stdout: str):
    """The last parseable JSON object line of a bench run's stdout (the
    bench contract: exactly one result line, possibly after logs)."""
    for ln in reversed((stdout or "").splitlines()):
        ln = ln.strip()
        if ln.startswith("{"):
            try:
                return json.loads(ln)
            except json.JSONDecodeError:
                continue
    return None


def _memscope_from_extra(extra: dict):
    """Pull the memory baseline the feasibility pruner scales from one
    BENCH artifact's ``extra.memscope``: the measured watermark peak
    when the ring saw the allocator (host RSS on backends whose devices
    report no memory_stats), else the largest static per-program
    footprint. None when the trial didn't arm memscope."""
    ms = extra.get("memscope")
    if not isinstance(ms, dict):
        return None
    peak, source = None, None
    wm = ms.get("watermarks") or {}
    for sect, tag in (("device", "watermark_device"),
                      ("host_rss", "watermark_host_rss")):
        s = wm.get(sect) if isinstance(wm, dict) else None
        p = s.get("peak") if isinstance(s, dict) else None
        if isinstance(p, (int, float)) and not isinstance(p, bool) \
                and p > 0:
            peak, source = int(p), tag
            break
    if peak is None:
        static = [r.get("peak_bytes") for r in (ms.get("programs") or [])
                  if isinstance(r, dict)
                  and isinstance(r.get("peak_bytes"), (int, float))
                  and not isinstance(r.get("peak_bytes"), bool)]
        if static:
            peak, source = int(max(static)), "static_footprint"
    cap = ms.get("capacity") if isinstance(ms.get("capacity"), dict) \
        else None
    batch = extra.get("batch")
    return {"peak_bytes": peak, "peak_source": source,
            "batch": (int(batch) if isinstance(batch, int)
                      and not isinstance(batch, bool) else None),
            "capacity": cap}


def measurement_from_artifact(doc: dict) -> dict:
    """Extract the scoring measurement from one BENCH artifact dict."""
    extra = (doc.get("extra") or {}) if isinstance(doc, dict) else {}
    ds = extra.get("devicescope") or {}
    bf = ds.get("busy_fraction")
    bf = float(bf) if isinstance(bf, (int, float)) \
        and not isinstance(bf, bool) else None
    gaps = None
    starved_split = None
    if isinstance(ds.get("gaps"), dict):
        if isinstance(ds["gaps"].get("taxonomy"), dict):
            gaps = dict(ds["gaps"]["taxonomy"])
        if isinstance(ds["gaps"].get("input_starved_split"), dict):
            # per-stage ingest attribution (read/decode/transfer) —
            # lets prune_plan pick io_workers over prefetch_depth when
            # the starvation is a decode problem
            starved_split = dict(ds["gaps"]["input_starved_split"])
    dec = (extra.get("perfscope") or {}).get("decomposition") or {}
    mfu = extra.get("mfu")
    value = doc.get("value") if isinstance(doc, dict) else None
    return {
        "memscope": _memscope_from_extra(extra),
        "busy_fraction": bf,
        "gaps": gaps,
        "starved_split": starved_split,
        "step_ms": dec.get("step_ms"),
        "mfu": mfu if isinstance(mfu, (int, float)) else None,
        "mfu_if_removed": dec.get("mfu_if_removed"),
        "value": float(value) if isinstance(value, (int, float))
        and not isinstance(value, bool) else None,
        "provenance": ("measured(profile)" if bf is not None
                       else "host_wall"),
    }


def score(measurement) -> tuple:
    """Orderable score: (busy_fraction rounded to 2 decimals, headline
    throughput). The primary key is the MEASURED busy fraction — the
    chip's idleness is what the tuner exists to close — rounded so
    near-ties defer to throughput, which also guards the remat
    pathology (a recompute knob can RAISE busy fraction while lowering
    samples/sec; throughput breaks that tie the right way). A trial
    with no measured window scores busy as -1: any measured trial
    outranks it, and among unmeasured trials throughput decides."""
    m = measurement or {}
    bf = m.get("busy_fraction")
    busy_key = round(float(bf), 2) if isinstance(bf, (int, float)) \
        and not isinstance(bf, bool) else -1.0
    v = m.get("value")
    val_key = float(v) if isinstance(v, (int, float)) \
        and not isinstance(v, bool) else 0.0
    return (busy_key, val_key)


class TrialResult:
    """One executed (or failed) trial. ``status``: "ok" | "failed".
    Failed trials carry ``error`` and no measurement — a counted skip,
    never a crash (the subprocess contract)."""

    def __init__(self, config, status, measurement=None, error=None,
                 wall_s=None, artifact=None, knob=None, value=None):
        self.config = config
        self.status = status
        self.measurement = measurement
        self.error = error
        self.wall_s = wall_s
        self.artifact = artifact
        self.knob = knob          # which coordinate move produced this
        self.value = value        # trial (None for the baseline)

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    @property
    def score(self) -> tuple:
        return score(self.measurement)

    def row(self) -> dict:
        """The ``extra.autotune.trial_table`` row."""
        m = self.measurement or {}
        return {
            "knob": self.knob, "value": self.value,
            "config": self.config.to_dict() if self.config else None,
            "status": self.status,
            "busy_fraction": m.get("busy_fraction"),
            "step_ms": m.get("step_ms"),
            "mfu": m.get("mfu"),
            "throughput": m.get("value"),
            "provenance": m.get("provenance"),
            "wall_s": self.wall_s,
            "error": self.error,
        }


def trial_env(config=None, model=None, batch=None, dtype=None,
              steps=None, measure=True, extra_env=None,
              scrub_ambient=True) -> dict:
    """Build the subprocess environment for one trial: the parent's env
    with every BENCH_*/knob spelling scrubbed (driver parity — a stray
    BENCH_MODEL would silently mislabel every trial), the config's
    canonical spellings exported, and — with
    ``measure=True`` — the measurement arming: one devicescope window
    (measured busy provenance), k=1 control off, Chrome trace off.
    ``extra_env`` applies LAST (the sweep's non-knob BENCH_K/BENCH_S2D
    rows ride there).

    ``scrub_ambient=False`` keeps the parent's MXTPU_* knob spellings
    (only BENCH_* is dropped, and MXTPU_AUTOTUNE still forced off) —
    the sweep's DRIVER-PARITY warm run: an operator's exported
    MXTPU_LOOP_CHUNK is part of the config the driver actually runs,
    and scrubbing it would silently mislabel the warm row. A search
    trial always scrubs: its config pins every knob explicitly."""
    scrub_exact = _SCRUB_EXACT if scrub_ambient else ("MXTPU_AUTOTUNE",)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(_SCRUB_PREFIXES) and k not in scrub_exact}
    env["MXTPU_AUTOTUNE"] = "0"
    if model:
        env["BENCH_MODEL"] = str(model)
    if batch:
        env["BENCH_BATCH"] = str(batch)
    if dtype:
        env["BENCH_DTYPE"] = str(dtype)
    if steps:
        env["BENCH_STEPS"] = str(steps)
    if measure:
        env["BENCH_DEVICESCOPE"] = "1"
        env["BENCH_DEVICESCOPE_STEPS"] = str(min(8, int(steps or 8)))
        # memscope rides the same measured trial: its watermark peak is
        # what the feasibility pruner scales for later batch candidates
        env["BENCH_MEMSCOPE"] = "1"
        env["BENCH_K1_CONTROL"] = "0"
        env["BENCH_TRACE"] = "0"
    if config is not None:
        env.update(config.to_env())
    for k, v in (extra_env or {}).items():
        env[k] = str(v)
    return env


def run_trial(config=None, *, model=None, batch=None, dtype=None,
              steps=12, timeout=900, measure=True, extra_env=None,
              bench_path=None, knob=None, value=None,
              scrub_ambient=True) -> TrialResult:
    """Execute one trial: bench.py in a subprocess under ``timeout``
    seconds, measurement read from its BENCH json line. NEVER raises —
    a timeout, a crash, an env_failure artifact, or garbage output all
    return ``status="failed"`` with the reason (the counted-skip
    contract; the search and the sweep both depend on a dead trial
    being data, not an exception).

    ``config=None`` exports NO knob env at all (bench resolves its own
    defaults) — the sweep's driver-parity warm run; a search trial
    always passes an explicit config so the trial is fully pinned."""
    from .. import context as _context
    if _context.holds_accelerator():
        return TrialResult(
            config, "failed", knob=knob, value=value,
            error="this process holds the accelerator, and a chip belongs "
                  "to one process at a time: the trial's child could not "
                  "have it (run the search before touching jax)")
    env = trial_env(config, model=model, batch=batch, dtype=dtype,
                    steps=steps, measure=measure, extra_env=extra_env,
                    scrub_ambient=scrub_ambient)
    bench = bench_path or os.path.join(_repo_root(), "bench.py")
    t0 = time.time()
    try:
        r = subprocess.run([sys.executable, bench], timeout=timeout,
                           capture_output=True, text=True,
                           cwd=os.path.dirname(bench) or ".", env=env)
    except subprocess.TimeoutExpired:
        return TrialResult(config, "failed", knob=knob, value=value,
                           wall_s=round(time.time() - t0, 1),
                           error=f"trial timed out after {timeout}s")
    except OSError as e:
        return TrialResult(config, "failed", knob=knob, value=value,
                           error=f"could not spawn trial: {e}")
    wall = round(time.time() - t0, 1)
    doc = last_json_line(r.stdout)
    if doc is None:
        return TrialResult(
            config, "failed", knob=knob, value=value, wall_s=wall,
            error=f"no JSON line (rc={r.returncode}); stderr tail: "
                  f"{(r.stderr or '')[-200:]}")
    if doc.get("status") == "env_failure" or doc.get("error"):
        return TrialResult(
            config, "failed", knob=knob, value=value, wall_s=wall,
            artifact=doc,
            error=str(doc.get("error") or "env_failure")[:200])
    value_num = doc.get("value")
    if not isinstance(value_num, (int, float)) or value_num <= 0:
        return TrialResult(config, "failed", knob=knob, value=value,
                           wall_s=wall, artifact=doc,
                           error=f"non-positive value {value_num!r}")
    return TrialResult(config, "ok",
                       measurement=measurement_from_artifact(doc),
                       artifact=doc, wall_s=wall, knob=knob, value=value)
