"""Per-program cost analysis: XLA ``cost_analysis()`` + analytic roofline.

Every compiled hot program in this framework passes through a handful of
well-known compile sites — the HybridBlock jit cache, FusedTrainStep's
single-step and k-step programs, TrainLoop chunks, FrozenModel serving
buckets. When perfscope is enabled, each site hands its lowered (or
to-be-lowered) program to :func:`analyze_lowered` / :func:`analyze_jit`,
which:

* pulls ``flops`` / ``bytes accessed`` out of XLA's HLO cost analysis
  (host-side — no device work);
* classifies the program against the device's peak-FLOPs/HBM-bandwidth
  point (:func:`classify`): **compute_bound** when its arithmetic
  intensity clears the ridge, **hbm_bound** when it doesn't,
  **trivial** when the FLOP count is too small for the verdict to mean
  anything, **unknown** when the backend's analysis is missing keys
  (XLA:CPU reports ``{}`` for data-movement-only programs) or the
  device has no row in the peak table;
* records the verdict as a flight-recorder compile span (so crash dumps
  and run artifacts say not just *that* a program compiled but *what
  it is bound by*), bumps the ``perfscope.*`` counters, and files the
  program in a process-wide table (``extra.perfscope.programs``, which
  ``tools/mxdiag.py perf`` renders).

The peak table is keyed by ``device_kind`` and holds published per-chip
numbers only. A device that is not in it — the CPU of a test run
included — has NO peaks: its ridge, roofline verdict and every
utilisation derived from a peak are ``None``/"unknown", never a default.
"""
from __future__ import annotations

import threading

from ..diagnostics import flight as _flight
from ..profiler.counters import counter as _counter

__all__ = ["device_peaks", "classify", "analyze_lowered", "analyze_jit",
           "programs", "reset_programs", "ROOFLINE_VERDICTS",
           "TRIVIAL_FLOPS"]

ROOFLINE_VERDICTS = ("compute_bound", "hbm_bound", "trivial", "unknown")

# below this many FLOPs a program's runtime is dominated by fixed launch/
# dispatch overhead, not by either roofline ceiling — calling it compute-
# or bandwidth-bound would be noise dressed up as analysis
TRIVIAL_FLOPS = 1e7

# (peak_flops_f32, peak_flops_bf16, hbm_bytes_per_s) per table row: the
# published per-chip peaks (Google Cloud TPU documentation).
_PEAK_TABLE = {
    # TPU v5e (v5 litepod): 197 Tf bf16 / 99 Tf f32, 819 GB/s HBM2
    "v5e": (99e12, 197e12, 819e9),
    # TPU v4: 275 Tf bf16 (no fp32 MXU mode: same peak), 1228 GB/s HBM2
    "v4": (137.5e12, 275e12, 1228e9),
    # TPU v5p: 459 Tf bf16, 2765 GB/s HBM2e
    "v5p": (229.5e12, 459e12, 2765e9),
}

# ordered (patterns, row): matched against the device_kind string with
# spaces/hyphens/underscores collapsed, so "TPU v5 lite" (what jax
# reports for a v5e), "v5litepod" (the GCE accelerator type) and a
# plain "v5e" all land on the v5e row. v5p checks first — "v5" alone
# would shadow it.
_KIND_PATTERNS = (
    (("v5p",), "v5p"),
    (("v5e", "v5lite"), "v5e"),
    (("v4",), "v4"),
)


def _env_float(name):
    # malformed override: keep the default (the analysis path promises it
    # never raises)
    from ..settings import env_float
    return env_float(name, None, on_error="default")


def device_peaks(device=None) -> dict:
    """Peak FLOP/s (f32 + bf16) and HBM bandwidth for a device, from the
    device-kind pattern table. An unknown kind matches no row:
    ``table_row`` and all three peaks are ``None``."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = str(device.device_kind).lower()
    collapsed = kind.replace(" ", "").replace("-", "").replace("_", "")
    row = next((key for patterns, key in _KIND_PATTERNS
                if any(p in collapsed for p in patterns)), None)
    f32, bf16, bw = _PEAK_TABLE[row] if row else (None, None, None)
    return {"device_kind": kind, "table_row": row,
            "peak_flops_f32": f32, "peak_flops_bf16": bf16,
            "hbm_bytes_per_s": bw}


def peak_flops_for(dtype, peaks):
    """bf16-class dtypes ride the MXU's doubled peak; everything else
    gets the f32 ceiling. ``None`` for a device without a table row."""
    d = str(dtype)
    if "bfloat16" in d or "float16" in d:
        return peaks["peak_flops_bf16"]
    return peaks["peak_flops_f32"]


def classify(flops, bytes_accessed, peaks=None, dtype="float32") -> dict:
    """Analytic roofline verdict for one program.

    Returns {verdict, flops, bytes_accessed, ai, ridge, est_compute_ms,
    est_memory_ms, peak_flops, hbm_bytes_per_s}. Never raises: missing
    or non-numeric inputs produce verdict "unknown" (the XLA:CPU backend
    returns an empty analysis for data-movement-only programs), zero/
    tiny-FLOP programs produce "trivial", and a device without peaks
    gets no ridge, no estimate and — for a non-trivial program —
    "unknown"."""
    peaks = peaks or device_peaks()
    pk = peak_flops_for(dtype, peaks)
    bw = peaks["hbm_bytes_per_s"]
    out = {"verdict": "unknown", "flops": None, "bytes_accessed": None,
           "ai": None, "ridge": pk / bw if pk and bw else None,
           "est_compute_ms": None, "est_memory_ms": None,
           "peak_flops": pk, "hbm_bytes_per_s": bw}
    try:
        f = float(flops) if flops is not None else None
        b = float(bytes_accessed) if bytes_accessed is not None else None
    except (TypeError, ValueError):
        return out
    if f is None or f != f:           # missing/NaN flops: no verdict
        return out
    out["flops"] = f
    out["bytes_accessed"] = b
    out["est_compute_ms"] = f / pk * 1e3 if pk else None
    if b is not None and b >= 0:
        out["est_memory_ms"] = b / bw * 1e3 if bw else None
    trivial = _env_float("MXTPU_PERFSCOPE_TRIVIAL_FLOPS") or TRIVIAL_FLOPS
    if f < trivial:
        out["verdict"] = "trivial"
        return out
    if out["ridge"] is None:          # no peaks: nothing to compare with
        if b:
            out["ai"] = f / b
        return out
    if not b or b <= 0:
        # real FLOPs, no reported traffic: the analysis says everything
        # stays on-chip — compute is the only ceiling left
        out["verdict"] = "compute_bound"
        return out
    out["ai"] = f / b
    out["verdict"] = "compute_bound" if out["ai"] >= out["ridge"] \
        else "hbm_bound"
    return out


# process-wide table of analyzed programs: name -> record (last analysis
# wins per name — recompiles of the same site overwrite, they don't grow
# the table unboundedly)
_PROGRAMS: "dict[str, dict]" = {}
_plock = threading.Lock()

# mxlint strict-mode recompile detector (mxlint/runtime.py pushes its
# note_program here when armed — one predicate per capture when off,
# the devicescope/commscope hook discipline)
_STRICT_HOOK = None


def programs() -> list:
    """Snapshot of every analyzed program, insertion-ordered."""
    with _plock:
        return [dict(v) for v in _PROGRAMS.values()]


def reset_programs() -> None:
    with _plock:
        _PROGRAMS.clear()


def _extract_costs(obj):
    """Normalize the two cost_analysis() shapes: Lowered returns a flat
    dict; Compiled returns a list of per-module dicts (sum them)."""
    if obj is None:
        return None, None
    if isinstance(obj, (list, tuple)):
        f = b = None
        for mod in obj:
            mf, mb = _extract_costs(mod)
            if mf is not None:
                f = (f or 0.0) + mf
            if mb is not None:
                b = (b or 0.0) + mb
        return f, b
    if isinstance(obj, dict):
        f = obj.get("flops")
        b = obj.get("bytes accessed")
        if b is None:
            # some backends report only the per-operand breakdown
            parts = [v for k, v in obj.items()
                     if k.startswith("bytes accessed") and k != "bytes accessed"]
            b = float(sum(parts)) if parts else None
        return f, b
    return None, None


def record_program(name: str, flops, bytes_accessed, dtype="float32",
                   kind: str = "program", extra: dict | None = None) -> dict:
    """Classify + publish one program's costs (the shared tail of
    analyze_lowered/analyze_jit; also the entry point for callers that
    computed flops themselves). Returns the stored record."""
    peaks = device_peaks()
    rec = classify(flops, bytes_accessed, peaks, dtype)
    rec.update({"name": name, "kind": kind, "dtype": str(dtype)})
    if extra:
        rec.update(extra)
    with _plock:
        _PROGRAMS[name] = rec
    if _STRICT_HOOK is not None:
        # a re-capture of a known name after warmup is a steady-state
        # recompile — the strict auditor counts + names it
        _STRICT_HOOK(name, kind)
    _counter("perfscope.programs_analyzed", "perfscope").increment()
    _counter(f"perfscope.{rec['verdict']}", "perfscope").increment()
    if _flight._REC is not None:
        # the compile-span record gains the cost fields — a crash dump or
        # run artifact now says what each program is bound by
        _flight.record("compile", f"perfscope.cost:{name}", {
            "flops": rec["flops"], "bytes_accessed": rec["bytes_accessed"],
            "roofline": rec["verdict"], "ai": rec["ai"],
            "est_compute_ms": rec["est_compute_ms"],
            "est_memory_ms": rec["est_memory_ms"]})
    return rec


def _devicescope_register(name, lowered):
    """Record the program's HLO module name with mxtpu.devicescope when
    armed — the join key between measured trace lanes (whose op events
    carry ``hlo_module``) and this program table. Never raises."""
    try:
        from .. import devicescope as _ds
        if _ds._DS is not None and lowered is not None:
            _ds.register_program(name, _ds.module_name_of(lowered))
    except Exception:  # noqa: BLE001 — registration never breaks compiles
        pass


def _commscope_capture(name, lowered=None, compiled=None, mesh=None,
                       mode=None, kind="program"):
    """Hand the program to mxtpu.commscope when armed — the collective/
    resharding extraction rides perfscope's capture hooks (one gate, one
    set of compile sites). Never raises."""
    try:
        from .. import commscope as _cs
        if _cs._CS is not None:
            _cs.capture(name, lowered=lowered, compiled=compiled,
                        mesh=mesh, mode=mode, kind=kind)
    except Exception:  # noqa: BLE001 — extraction never breaks compiles
        pass


def _memscope_capture(name, lowered=None, compiled=None, kind="program"):
    """Hand the program to mxtpu.memscope when armed — the static
    memory-footprint capture rides perfscope's capture hooks (one gate,
    one set of compile sites, the commscope discipline). Never
    raises."""
    try:
        from .. import memscope as _ms
        if _ms._MS is not None:
            _ms.capture(name, lowered=lowered, compiled=compiled,
                        kind=kind)
    except Exception:  # noqa: BLE001 — capture never breaks compiles
        pass


def analyze_lowered(lowered, name: str, dtype="float32",
                    kind: str = "program", extra: dict | None = None,
                    compiled=None, mesh=None, mode=None):
    """Cost-analyze an already-lowered (or compiled) jax stage object.
    Never raises — a backend without cost analysis yields an "unknown"
    record rather than breaking the compile site that called us.

    ``compiled``/``mesh``/``mode`` feed the commscope collective
    extraction when armed: a site that already holds the compiled
    executable (serving buckets) passes it so commscope reads the
    optimized HLO for free instead of compiling again."""
    costs = None
    try:
        costs = lowered.cost_analysis()
    except Exception:  # noqa: BLE001 — backend-dependent surface
        costs = None
    flops, nbytes = _extract_costs(costs)
    rec = record_program(name, flops, nbytes, dtype=dtype, kind=kind,
                         extra=extra)
    _devicescope_register(name, lowered)
    _commscope_capture(name, lowered=lowered, compiled=compiled,
                       mesh=mesh, mode=mode, kind=kind)
    _memscope_capture(name, lowered=lowered, compiled=compiled,
                      kind=kind)
    return rec


def analyze_jit(jit_fn, args, name: str, dtype="float32",
                kind: str = "program", extra: dict | None = None,
                kwargs: dict | None = None, mesh=None, mode=None):
    """Lower ``jit_fn`` against abstract ShapeDtypeStructs of ``args``
    and cost-analyze the result. Tracing happens on the host only (no
    device compile, no buffers touched — safe to call on arguments that
    are about to be donated). Never raises.

    ``mesh``/``mode`` describe the sharded layout for commscope's
    collective extraction (armed separately; it compiles the lowered
    program to read the optimized HLO — see docs/commscope.md for the
    cost model)."""
    try:
        import jax
        from ..ops import select as _sel

        def spec(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        specs = jax.tree_util.tree_map(spec, tuple(args))
        # quiet: this re-trace is purely to read the cost analysis —
        # the pallas selection counters already counted this program's
        # real trace, and must not count it again
        with _sel.quiet():
            lowered = jit_fn.lower(*specs, **(kwargs or {}))
    except Exception:  # noqa: BLE001 — analysis must never break training
        return record_program(name, None, None, dtype=dtype, kind=kind,
                              extra=extra)
    return analyze_lowered(lowered, name, dtype=dtype, kind=kind,
                           extra=extra, mesh=mesh, mode=mode)
