"""mxtpu.mxlint — framework-invariant static analysis + strict-mode
jit-program auditing.

Two halves, one contract (docs/mxlint.md):

* **static** (:mod:`.engine` + :mod:`.rules`, driven by
  ``tools/mxlint.py``) — an stdlib-``ast`` lint suite whose rules encode
  the invariants PR 6–13's review-hardening passes kept re-finding by
  hand: env reads that bypass ``settings.py``'s documented
  resolution order, counter names drifting from the family tables,
  raises inside never-raise parsers, raw device-kind comparisons,
  unlocked writes to thread-shared module state, and duplicated default
  tables. ``tools/mxlint.py --check`` gates on a
  clean tree; ``mxdiag.py lint`` renders the findings report.
* **runtime** (:mod:`.runtime`, armed by ``MXTPU_STRICT=1``) — a
  strict-mode auditor over the steady train/serve loop:
  transfer-guard-based host-sync detection, a recompile-storm detector
  over perfscope's compile captures, and a donated-buffer-read check,
  all reporting through the ``mxlint.*`` counter family plus flight /
  ``mxtpu.events/1``, and ``bench_extra()``'s ``extra.mxlint`` shape.

:mod:`.families` is the ONE home of the counter-family tables —
``tools/trace_check.py`` derives its ``*_FAMILIES`` globals from it, and
the ``unregistered-counter`` rule reads the same source, so the
validator and the linter cannot disagree.
"""
from __future__ import annotations

from . import engine, families, rules, runtime
from .engine import Finding, lint_paths
from .rules import RULES, default_rules

__all__ = ["engine", "families", "rules", "runtime", "Finding",
           "lint_paths", "RULES", "default_rules", "lint_tree"]


def lint_tree(paths, root=None):
    """Run the default rule set over ``paths`` (files or directories).
    Returns the list of :class:`Finding`."""
    return lint_paths(paths, default_rules(), root=root)
