"""Collective inventory, link-time estimates, and the resharding detector.

Per captured program this module turns :mod:`.hlo`'s raw collective
records into the ``extra.commscope`` shape ``bench_extra()`` returns and
``tools/mxdiag.py comms`` renders:

* **aggregation** — records grouped by (op kind, mesh axis): count,
  payload bytes, analytic link-time estimate;
* **axis attribution** — replica groups matched against the partitions
  a mesh axis induces on the device grid (``{{0,2},{1,3}}`` on a 2×2
  ``(dp, mp)`` mesh is the dp axis; a single full group is the whole
  mesh);
* **estimates** — ring-algorithm lower bounds against per-topology ICI
  peak-bandwidth tables (v5e/v4/v5p + a CPU fallback, same table-row
  matching as perfscope's FLOP peaks; ``MXTPU_PEAK_ICI_BW`` overrides).
  These are *analytic estimates from static shapes*, clearly marked so
  downstream consumers (the step budget, artifact json) never confuse them
  with a measurement;
* **resharding detection** — a collective is flagged as
  compiler-inserted resharding when (a) its kind is outside the mode's
  expected signature (a reduce-scatter in a pure-dp program moves
  layout, not gradients), or (b) in dp/auto modes, an
  all-gather/all-to-all whose operand provenance walks back to a
  program *parameter* — the compiler un-doing an annotated input
  sharding the computation can't use (the "accidental all-gather" a bad
  ``Block.shard()`` or missing axis rule causes). FSDP is exempt from
  (b): gathering parameters is that mode's contract.

Everything lands in the ``commscope.*`` counter family, flight-recorder
compile spans, and a process-wide program table (``bench_extra()``'s
``extra.commscope``).
"""
from __future__ import annotations

import threading
import warnings

import numpy as np

from ..diagnostics import flight as _flight
from ..profiler.counters import counter as _counter, set_gauge as _set_gauge
from . import hlo as _hlo

__all__ = ["ici_peaks", "estimate_ms", "attribute_axis", "axis_for_groups",
           "expected_kinds", "detect_resharding", "record_inventory",
           "capture", "programs", "reset_programs", "step_estimate",
           "axis_by_kind", "EXPECTED_KINDS", "ICI_TABLE"]

# Per-chip aggregate ICI bandwidth (bytes/s, one direction). Published
# per-chip interconnect numbers: v4 ≈ 2.4 Tb/s, v5e ≈ 1.6 Tb/s,
# v5p ≈ 4.8 Tb/s. The CPU row is a deliberately round fallback — on the
# tier-1 fake-device mesh the *relative* estimates and the schema are
# the point, not the absolute milliseconds (docs/commscope.md).
ICI_TABLE = {
    "v5e": 200e9,
    "v4": 300e9,
    "v5p": 600e9,
    "cpu": 1e9,
}

# Expected collective-kind signature per sharding mode
# (parallel/sharding.MODES). Anything outside the set is flagged as a
# resharding collective. `None` (unknown mode: jit-cache / serving
# programs) expects everything except "other".
EXPECTED_KINDS = {
    # pure data parallel: gradient all-reduce; small batch-axis gathers
    # (loss index plumbing) are legitimate, so all-gather stays in the
    # set and the PARAM-provenance rule catches the accidental ones
    "dp": frozenset(("all-reduce", "all-gather")),
    # zero-style: param all-gather + grad reduce-scatter — which
    # XLA:CPU decomposes into all-to-all + local reduce, so both
    # spellings are the mode's signature
    "fsdp": frozenset(("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all")),
    # model-axis layouts: Megatron f/g pairs (activation all-reduce /
    # all-gather) + the dp gradient reduce; all-to-all stays in the set
    # because XLA:CPU spells reduce-scatter that way (same decomposition
    # the fsdp row documents), and collective-permute because XLA's
    # SPMD partitioner spells the reshard of an activation whose dim
    # does NOT divide the mesh axis as pad + halo permute (DLRM's
    # 28-wide interaction output on an mp4 mesh, e.g.) — the
    # param-provenance rule still catches an accidental
    # all-to-all/permute of an input
    "auto": frozenset(("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")),
    None: frozenset(("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute")),
}

# ring-algorithm traffic factor per kind: the fraction of the payload
# each device moves over its links (n = participating devices)
_RING_FACTOR = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n,
    "all-gather": lambda n: (n - 1) / n,
    "reduce-scatter": lambda n: (n - 1) / n,
    "all-to-all": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
    "other": lambda n: 1.0,
}


def _env_float(name):
    # never-raise contract: a typo'd override keeps the table
    from ..settings import env_float
    return env_float(name, None, on_error="default")


def ici_peaks(device=None) -> dict:
    """Peak interconnect bandwidth for the device's topology row.

    Reuses perfscope's device-kind pattern matching (one place decides
    that "TPU v5 lite" is the v5e row); ``MXTPU_PEAK_ICI_BW`` overrides
    the table for new hardware without a code change."""
    from ..perfscope import cost as _pcost
    base = _pcost.device_peaks(device)
    # perfscope has no row for an unknown device; the link-time estimate
    # (always flagged "estimated") keeps its round CPU stand-in
    row = base.get("table_row") or "cpu"
    bw = ICI_TABLE[row]
    env = _env_float("MXTPU_PEAK_ICI_BW")
    if env:
        bw = env
    return {"device_kind": base.get("device_kind"), "table_row": row,
            "ici_bytes_per_s": bw}


def estimate_ms(kind, nbytes, group_size, bw) -> float:
    """Analytic ring lower bound for one collective: milliseconds of
    link time to move `nbytes` across a group of `group_size`."""
    try:
        n = max(1, int(group_size or 1))
        b = float(nbytes or 0)
        if n <= 1 or b <= 0 or not bw:
            return 0.0
        factor = _RING_FACTOR.get(kind, _RING_FACTOR["other"])(n)
        return factor * b / float(bw) * 1e3
    except Exception:  # noqa: BLE001
        return 0.0


# --------------------------------------------------------------------------
# mesh-axis attribution
# --------------------------------------------------------------------------

def _id_grid(mesh):
    """Device-id array shaped like the mesh (replica groups name global
    device ids, not mesh positions)."""
    devs = np.asarray(mesh.devices, dtype=object)
    ids = np.empty(devs.shape, dtype=np.int64)
    for idx in np.ndindex(devs.shape):
        ids[idx] = int(getattr(devs[idx], "id", -1))
    return ids


def attribute_axis(groups, id_grid, axis_names):
    """Which mesh axis a replica-group partition communicates over.

    `groups`: list of device-id lists; `id_grid`: ndarray of device ids
    in mesh layout; `axis_names`: mesh axis names in grid order.
    Returns an axis name, ``"all"`` (single group spanning the mesh),
    ``"mixed"`` (a partition no single axis induces — combined-axis
    groups land here), or ``None`` when groups are unparseable."""
    if not groups:
        return None
    try:
        gset = frozenset(frozenset(int(i) for i in g) for g in groups)
        all_ids = frozenset(int(i) for i in id_grid.ravel())
        if gset == frozenset((all_ids,)):
            return axis_names[0] if len(axis_names) == 1 else "all"
        for ax, name in enumerate(axis_names):
            moved = np.moveaxis(id_grid, ax, -1)
            expected = frozenset(
                frozenset(int(i) for i in moved[idx])
                for idx in np.ndindex(moved.shape[:-1]))
            if gset == expected:
                return name
        return "mixed"
    except Exception:  # noqa: BLE001
        return None


def axis_for_groups(groups, mesh):
    """Mesh wrapper around :func:`attribute_axis`."""
    if mesh is None:
        return None
    return attribute_axis(groups, _id_grid(mesh), list(mesh.axis_names))


# --------------------------------------------------------------------------
# resharding detection
# --------------------------------------------------------------------------

def expected_kinds(mode):
    return EXPECTED_KINDS.get(mode, EXPECTED_KINDS[None])


def detect_resharding(collectives, defs, mode) -> list:
    """The subset of `collectives` that look like compiler-inserted
    layout changes, each annotated with a `reason`:

    * ``"unexpected-kind"`` — op kind outside the mode's signature;
    * ``"param-gather"`` — (dp/auto only) an all-gather/all-to-all (or,
      in auto mode, a collective-permute — the kind XLA spells
      uneven-dim reshards with) whose operand is a program input: the
      compiler is un-sharding an annotated parameter the computation
      needed replicated.

    The ``"other"`` bucket (unknown spellings) is exempt from both
    rules: unrecognized is not mis-laid-out."""
    expect = expected_kinds(mode)
    flagged = []
    for c in collectives:
        if c["kind"] == "other":
            # an unknown HLO spelling (future op, renamed after an XLA
            # upgrade) is inventoried but never indicted — "we don't
            # recognize it" is not evidence of a layout bug, and the
            # parser's never-raise contract would be undone by a
            # detector that hard-fails CI on it
            continue
        if c["kind"] not in expect:
            flagged.append(dict(c, reason="unexpected-kind"))
            continue
        provenance_kinds = (("all-gather", "all-to-all",
                             "collective-permute") if mode == "auto"
                            else ("all-gather", "all-to-all"))
        if (mode in ("dp", "auto")
                and c["kind"] in provenance_kinds
                and defs
                and any(_hlo.chases_to_parameter(defs, op)
                        for op in c.get("operands", ()))):
            flagged.append(dict(c, reason="param-gather"))
    return flagged


# --------------------------------------------------------------------------
# program table + capture
# --------------------------------------------------------------------------

_PROGRAMS: "dict[str, dict]" = {}
_plock = threading.Lock()
_warned: set = set()


def programs() -> list:
    """Snapshot of every captured program's inventory, insertion-ordered."""
    with _plock:
        return [dict(v) for v in _PROGRAMS.values()]


def reset_programs() -> None:
    with _plock:
        _PROGRAMS.clear()
    _warned.clear()


def step_estimate():
    """The steady-phase train program's per-step collective estimate —
    what perfscope's StepBudget splits out of device_compute in sharded
    mode. Scan-body inventories (fused_step_k) are static, i.e. per
    micro-step, so the newest ``train_step``-kind record IS the per-step
    number. None when no train program was captured."""
    with _plock:
        recs = [v for v in _PROGRAMS.values() if v.get("kind") == "train_step"]
    if not recs:
        return None
    rec = recs[-1]
    t = rec.get("totals") or {}
    mesh = rec.get("mesh")
    devices = 1
    if isinstance(mesh, dict):
        for s in mesh.values():
            devices *= int(s)
    return {"program": rec.get("name"), "est_ms": t.get("est_ms"),
            "bytes": t.get("bytes"), "count": t.get("count"),
            # the CAPTURED program's mesh — the provenance decision must
            # not depend on the process-global registry (an explicit
            # mesh= FusedTrainStep never registers one)
            "mesh": mesh, "devices": devices,
            # False = the optimized HLO could not be read/parsed: the
            # zero inventory is IGNORANCE, not a finding — the step
            # budget must report 'unavailable', never an estimated zero
            "hlo_available": bool(rec.get("hlo_available", True)),
            "resharding_collectives": rec.get("resharding_collectives", 0)}


def axis_by_kind(program) -> dict:
    """``op kind -> mesh axis`` for one captured program — the join
    mxtpu.devicescope uses to attribute MEASURED collective-lane time
    to a mesh axis (the trace's op events carry kind but not replica
    groups; the static inventory carries both).

    ``program``: a program name (looked up in the capture table) or a
    record dict. A kind whose rows span more than one axis maps to
    None — ambiguous attribution is reported as unknown, never
    guessed. Returns {} for unknown programs. Never raises."""
    try:
        rec = program
        if not isinstance(rec, dict):
            with _plock:
                rec = _PROGRAMS.get(program)
        if not isinstance(rec, dict):
            return {}
        out = {}
        for row in rec.get("collectives") or []:
            k = row.get("kind")
            if k is None:
                continue
            if k in out and out[k] != row.get("axis"):
                out[k] = None
            else:
                out[k] = row.get("axis")
        return out
    except Exception:  # noqa: BLE001
        return {}


_KIND_COUNTER = {k: "commscope." + k.replace("-", "_")
                 for k in _hlo.COLLECTIVE_KINDS}


def record_inventory(name, collectives, defs=None, mesh=None, mode=None,
                     kind: str = "program", hlo_available: bool = True,
                     extra: dict | None = None) -> dict:
    """Aggregate one program's parsed collectives, run the resharding
    detector, publish counters/flight/table. This is `capture`'s tail
    and the entry point for tests that parsed their own text."""
    peaks = ici_peaks()
    bw = peaks["ici_bytes_per_s"]
    axes = list(getattr(mesh, "axis_names", ()) or ())
    grid = _id_grid(mesh) if mesh is not None else None
    groups_out: "dict[tuple, dict]" = {}
    total_bytes = total_count = 0
    total_est = 0.0
    default_n = int(getattr(mesh, "size", 1) or 1)
    for c in collectives:
        axis = (attribute_axis(c.get("replica_groups"), grid, axes)
                if grid is not None else None)
        n = c.get("group_size") or default_n
        est = estimate_ms(c["kind"], c.get("bytes", 0), n, bw)
        key = (c["kind"], axis)
        slot = groups_out.setdefault(
            key, {"kind": c["kind"], "axis": axis, "count": 0, "bytes": 0,
                  "est_ms": 0.0})
        slot["count"] += 1
        slot["bytes"] += int(c.get("bytes", 0))
        slot["est_ms"] += est
        total_count += 1
        total_bytes += int(c.get("bytes", 0))
        total_est += est
        _counter(_KIND_COUNTER[c["kind"]], "commscope").increment()
    flagged = detect_resharding(collectives, defs or {}, mode)
    rec = {
        "name": name,
        "kind": kind,
        "mode": mode,
        "mesh": dict(getattr(mesh, "shape", {}) or {}) if mesh is not None
                else None,
        "hlo_available": bool(hlo_available),
        "collectives": sorted(groups_out.values(),
                              key=lambda s: -s["bytes"]),
        "totals": {"count": total_count, "bytes": total_bytes,
                   "est_ms": round(total_est, 6)},
        "resharding_collectives": len(flagged),
        "resharding": [{"name": f.get("name"), "kind": f["kind"],
                        "reason": f["reason"],
                        "result_shape": f.get("result_shape"),
                        "operand_shapes": f.get("operand_shapes")}
                       for f in flagged[:16]],
        "estimated": True,     # link time here is analytic, never measured
    }
    if extra:
        rec.update(extra)
    with _plock:
        _PROGRAMS[name] = rec
    _counter("commscope.programs_analyzed", "commscope").increment()
    if total_count:
        _counter("commscope.collectives", "commscope").increment(total_count)
        _counter("commscope.payload_bytes", "commscope").increment(total_bytes)
    if flagged:
        _counter("commscope.resharding_collectives",
                 "commscope").increment(len(flagged))
        if name not in _warned:
            _warned.add(name)
            shapes = [f.get("result_shape") for f in flagged[:4]]
            warnings.warn(
                f"commscope: program {name!r} (mode={mode}) contains "
                f"{len(flagged)} compiler-inserted resharding "
                f"collective(s) ({flagged[0]['reason']}; result shapes "
                f"{shapes}) — an annotation/axis-rule likely does not "
                f"match the computation (docs/commscope.md)",
                stacklevel=3)
    if kind == "train_step":
        _set_gauge("commscope.step_collective_est_ms",
                   round(total_est, 6), "commscope")
        _set_gauge("commscope.step_collective_bytes", total_bytes,
                   "commscope")
    if _flight._REC is not None:
        _flight.record("compile", f"commscope.comms:{name}", {
            "collectives": total_count, "bytes": total_bytes,
            "est_ms": round(total_est, 6),
            "resharding": len(flagged), "mode": mode})
    return rec


def capture(name, lowered=None, compiled=None, mesh=None, mode=None,
            kind: str = "program", extra: dict | None = None):
    """Extract one compiled program's collective inventory.

    Called from perfscope's compile-site hooks when commscope is armed.
    With no mesh (or a 1-device mesh) the program cannot contain GSPMD
    collectives, so an empty inventory is recorded WITHOUT compiling —
    zero cost on every unsharded run. Under a real mesh the optimized
    HLO is read from `compiled` when the site already has it (serving
    buckets) or produced by compiling `lowered` (the one extra compile
    commscope pays; docs/commscope.md). Never raises."""
    try:
        if mesh is None:
            from ..parallel import sharding as _sharding
            mesh = _sharding.get_mesh()
        if mesh is None or int(getattr(mesh, "size", 1) or 1) <= 1:
            return record_inventory(name, [], mesh=mesh, mode=mode,
                                    kind=kind, extra=extra)
        text = None
        try:
            if compiled is None and lowered is not None:
                compiled = lowered.compile()
            if compiled is not None:
                text = compiled.as_text()
        except Exception:  # noqa: BLE001 — backend-dependent surface
            text = None
        if not text:
            return record_inventory(name, [], mesh=mesh, mode=mode,
                                    kind=kind, hlo_available=False,
                                    extra=extra)
        colls = _hlo.parse_collectives(text)
        defs = _hlo.parse_instructions(text) if colls else {}
        return record_inventory(name, colls, defs=defs, mesh=mesh,
                                mode=mode, kind=kind, extra=extra)
    except Exception:  # noqa: BLE001 — extraction must never break compiles
        return None
