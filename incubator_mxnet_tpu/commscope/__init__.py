"""mxtpu.commscope — collective & resharding observability for GSPMD.

The fifth observability layer (docs/observability.md). mxtpu.sharding
(PR 8) replaced explicit KVStore collectives with compiler-inserted
GSPMD collectives inside one jit program — which made perfscope's step
budget structurally blind to communication in exactly the sharded modes
that matter: on a dp4/fsdp4 mesh, all-reduce/all-gather/reduce-scatter
time silently lands in ``device_compute`` while the measured
``kvstore.collective_ms`` reads zero. Commscope makes those collectives
visible again:

* **static HLO extraction** (:mod:`.hlo`) — at every perfscope compile
  site (FusedTrainStep, TrainLoop chunks, the hybridize jit cache,
  serving buckets) the compiled program's optimized HLO is walked for
  its collective inventory: op kind, count, payload bytes (shapes ×
  dtype), replica-group → mesh-axis attribution;
* **analytic link-time estimates** (:mod:`.extract`) — ring-algorithm
  lower bounds against per-topology ICI peak tables (v5e/v4/v5p + CPU
  fallback, ``MXTPU_PEAK_ICI_BW`` override), clearly marked
  ``estimated`` — never confused with a measurement;
* **resharding detector** — compiler-inserted layout-change collectives
  that don't correspond to any annotated spec (the "accidental
  all-gather" a bad ``Block.shard()`` causes) are flagged per program
  with the offending operand shapes, warned about, and counted in
  ``commscope.resharding_collectives``;
* **step-budget integration** — perfscope's decomposition consumes
  :func:`step_estimate` so sharded-mode artifact json splits ``collective``
  out of ``device_compute`` again, with the component's provenance
  pinned (``measured`` | ``estimated`` | ``unavailable``).

Everything lands in the ``commscope.*`` counter family, flight-recorder
compile spans, ``bench_extra()``'s ``extra.commscope``, and
``tools/mxdiag.py comms``.

Cost model: with no mesh registered a capture records an empty
inventory without compiling anything — zero cost on unsharded runs.
Under a mesh, sites that only *lower* (FusedTrainStep, jit cache) pay
one extra XLA compile per captured program signature, which is why
commscope is **off by default**: ``enable()`` arms it,
``MXTPU_COMMSCOPE=1`` arms it at import.
Commscope rides perfscope's capture hooks, so enabling it arms
perfscope too.
"""
from __future__ import annotations

import os

from . import extract
from . import hlo
from .extract import (attribute_axis, axis_by_kind, axis_for_groups,
                      capture, detect_resharding, estimate_ms,
                      expected_kinds, ici_peaks, programs,
                      record_inventory, reset_programs, step_estimate,
                      EXPECTED_KINDS, ICI_TABLE)
from .hlo import (chases_to_parameter, parse_collectives,
                  parse_instructions, parse_replica_groups, parse_shape,
                  shape_bytes, COLLECTIVE_KINDS)

__all__ = ["enable", "disable", "enabled", "enable_from_env",
           "bench_extra", "capture", "programs", "reset_programs",
           "step_estimate", "ici_peaks", "estimate_ms", "attribute_axis",
           "axis_by_kind",
           "axis_for_groups", "detect_resharding", "expected_kinds",
           "record_inventory", "parse_collectives", "parse_instructions",
           "parse_replica_groups", "parse_shape", "shape_bytes",
           "chases_to_parameter", "COLLECTIVE_KINDS", "EXPECTED_KINDS",
           "ICI_TABLE", "hlo", "extract"]

# module global: None = commscope off (the fast-path predicate;
# perfscope's capture hooks guard with `if _cs._CS is not None:`)
_CS = None


class _CommScope:
    """Marker object holding enable-time options (the perfscope/healthmon
    module-global discipline)."""

    def __init__(self):
        pass


def enable():
    """Arm collective extraction at every perfscope compile site. The
    hooks live inside perfscope's analyze functions, so perfscope is
    armed too if it isn't already."""
    global _CS
    from .. import perfscope as _ps
    if _ps._PS is None:
        _ps.enable()
    _CS = _CommScope()
    return _CS


def disable():
    global _CS
    _CS = None


def enabled() -> bool:
    return _CS is not None


def enable_from_env():
    """MXTPU_COMMSCOPE=1 arms commscope at import (like MXTPU_PERFSCOPE)."""
    if os.environ.get("MXTPU_COMMSCOPE", "") == "1":
        enable()


def bench_extra() -> dict:
    """The ``extra.commscope`` payload for artifact json: every captured
    program's collective inventory, the ICI peak row the estimates were
    scored against, and the steady train program's per-step summary."""
    return {"programs": programs(), "peaks": ici_peaks(),
            "step": step_estimate()}
