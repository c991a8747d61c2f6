"""Which way the arrows point: the lower layers of the package (ops,
ndarray, optimizer, gluon, models, autograd, settings, context and the
fused step) import of the layers ABOVE them and of the telemetry
packages exactly what the table below records — for most, nothing.

Equality, not subset: a new upward arrow fails here, and a change that
removes one shortens the table (ROADMAP.md, Queue 3, debt (d)). Imports
are read with ``ast`` from the source, function-level ones too, so
nothing is imported and a lazy import counts like any other.
"""
from __future__ import annotations

import ast
import glob
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "incubator_mxnet_tpu"

# the layers above the ones judged here
UPPER = ("trainloop", "serving", "fleet", "resilience", "tools")
# measurement no lower layer should need in order to compute
TELEMETRY = ("perfscope", "commscope", "devicescope", "memscope",
             "healthmon", "diagnostics", "servescope", "fleetscope",
             "mxlint")
# beside the fused step, not below it
SIDE = ("io", "runtime")
WATCHED = UPPER + TELEMETRY + SIDE

# module -> the watched modules it imports at this PR (dotted, below the
# package root); a module that is not listed imports none
RECORDED = {
    # freeze() hands the block to serving.FrozenModel; the jit cache
    # reports compiles to perfscope, kernel choices to the flight log
    # and parameter bytes to the memory ledger
    "gluon/block.py": {"diagnostics", "perfscope", "serving"},
    # step() feeds the straggler clock and the flight log
    "gluon/trainer.py": {"diagnostics", "healthmon"},
    # the layout's bytes go to the diagnostics ledger
    "parallel/sharding.py": {"diagnostics.memory"},
    # compile capture, OOM forensics, the transfer gate, the cache guard
    "parallel/trainer_step.py": {"io.pipeline", "memscope", "perfscope",
                                 "runtime", "runtime.cache_guard"},
}


def _modules():
    found = []
    for pattern in ("ops/*.py", "ops/pallas/*.py", "ndarray/*.py",
                    "optimizer/*.py", "gluon/*.py", "gluon/nn/*.py",
                    "models/*.py"):
        found += glob.glob(os.path.join(ROOT, PKG, pattern))
    rel = sorted(os.path.relpath(p, os.path.join(ROOT, PKG))
                 .replace(os.sep, "/") for p in found)
    return rel + ["autograd.py", "settings.py", "context.py",
                  "parallel/trainer_step.py", "parallel/sharding.py"]


def package_imports(rel):
    """Dotted names, below the package root, of what ``rel`` imports from
    the package (``tools.x`` for an import of the repository's tools)."""
    here = rel.split("/")[:-1]
    with open(os.path.join(ROOT, PKG, rel)) as f:
        tree = ast.parse(f.read())
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            names = [a.name for a in node.names]
            if node.level:
                base = here[:len(here) - (node.level - 1)]
                mod = base + (node.module.split(".") if node.module else [])
            else:
                parts = (node.module or "").split(".")
                if parts[0] == "tools":
                    out.add(node.module)
                    continue
                if parts[0] != PKG:
                    continue
                mod = parts[1:]
            if node.module and mod:
                out.add(".".join(mod))
            else:               # from . import a, b / from <pkg> import a
                out.update(".".join(mod + [n]) for n in names)
        elif isinstance(node, ast.Import):
            for a in node.names:
                parts = a.name.split(".")
                if parts[0] == "tools":
                    out.add(a.name)
                elif parts[0] == PKG and len(parts) > 1:
                    out.add(".".join(parts[1:]))
    return out


def test_the_table_names_judged_modules_only():
    assert set(RECORDED) <= set(_modules())
    assert len(_modules()) == len(set(_modules()))


@pytest.mark.parametrize("rel", _modules())
def test_upward_imports_are_the_recorded_ones(rel):
    imports = package_imports(rel)
    watched = {m for m in imports if m.split(".")[0] in WATCHED}
    assert watched == RECORDED.get(rel, set()), (
        f"{rel} imports {sorted(watched)} of the layers above it and of "
        f"telemetry; the table records {sorted(RECORDED.get(rel, ()))}")
    if rel == "settings.py":
        # the bottom of the package: nothing of it but profiler's counter
        assert {m.split(".")[0] for m in imports} <= {"profiler"}, imports
