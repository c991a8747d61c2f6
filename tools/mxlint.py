#!/usr/bin/env python
"""mxlint — the framework-invariant static analyzer (docs/mxlint.md).

Runs the mxtpu.mxlint rule suite (stdlib ast, no deps) over the repo:

    python tools/mxlint.py --check            # gate: exit 1 on findings
    python tools/mxlint.py path/to/file.py    # lint specific paths
    python tools/mxlint.py --list-rules       # rule table with hints
    python tools/mxlint.py --check --json     # machine-readable findings

Default lint set: the ``incubator_mxnet_tpu/`` package and ``tools/``
(tests/, examples/ and docs/ are excluded — fixtures carry deliberate
violations). Per-rule path scopes live on the rules themselves (e.g.
``raw-env-read`` judges only the package).

Suppression: ``# mxlint: disable=<rule> -- <reason>`` (the reason is
required; a reasonless directive suppresses nothing and is itself a
finding). A tier-1 test runs ``--check`` over the tree.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_mxlint():
    """Import the rule suite WITHOUT importing the full framework
    package: load the mxlint subpackage by path under its canonical
    name. The static lint needs no jax/backend, must stay seconds-fast,
    and must not trigger the package's
    MXTPU_*-armed import side effects (healthmon watchdogs, strict
    auditor) just to parse source. Reuse an already-imported package's
    subpackage (pytest) so there is never a second module object."""
    existing = sys.modules.get("incubator_mxnet_tpu.mxlint")
    if existing is not None:
        return existing
    import importlib.util
    pkg_dir = os.path.join(_REPO, "incubator_mxnet_tpu", "mxlint")
    spec = importlib.util.spec_from_file_location(
        "incubator_mxnet_tpu.mxlint",
        os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    # the subpackage's relative imports need a parent in sys.modules
    # while it loads; when the real package was never imported, install
    # a stand-in for the duration and REMOVE it afterwards so a later
    # real `import incubator_mxnet_tpu` in this process still runs the
    # genuine package init
    fake_parent = "incubator_mxnet_tpu" not in sys.modules
    if fake_parent:
        import types
        parent = types.ModuleType("incubator_mxnet_tpu")
        parent.__path__ = [os.path.dirname(pkg_dir)]
        sys.modules["incubator_mxnet_tpu"] = parent
    sys.modules["incubator_mxnet_tpu.mxlint"] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        sys.modules.pop("incubator_mxnet_tpu.mxlint", None)
        raise
    finally:
        if fake_parent:
            sys.modules.pop("incubator_mxnet_tpu", None)
    return mod


def default_paths() -> list:
    return [os.path.join(_REPO, "incubator_mxnet_tpu"),
            os.path.join(_REPO, "tools")]


def run_lint(paths=None, rules=None, root=None):
    """Lint entry point shared with mxdiag/tests. Returns (findings,
    root). An EXPLICIT path that does not exist is an error — a typo'd
    gate invocation must fail, not report a clean empty lint set."""
    mxlint = _load_mxlint()
    if paths is None:
        # the optional default entries may be absent in a stripped tree
        paths = [p for p in default_paths() if os.path.exists(p)]
    else:
        missing = [p for p in paths if not os.path.exists(p)]
        if missing:
            raise FileNotFoundError(
                f"mxlint: no such path(s): {missing} — nothing would be "
                f"linted, refusing to report a clean tree")
    root = root or _REPO
    # the static rule set only — the runtime auditor is armed by
    # MXTPU_STRICT, not by the CLI
    rules = rules if rules is not None else mxlint.rules.default_rules()
    return mxlint.engine.lint_paths(paths, rules, root=root), root


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the package "
                         "and tools/)")
    ap.add_argument("--check", action="store_true",
                    help="gate mode: print findings, exit 1 if any")
    ap.add_argument("--json", action="store_true",
                    help="emit findings as JSON")
    ap.add_argument("--rule", action="append", default=None,
                    help="run only these rule ids (repeatable)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    args = ap.parse_args(argv)

    mxlint = _load_mxlint()
    if args.list_rules:
        for r in mxlint.rules.default_rules():
            print(f"{r.id}")
            print(f"    fix: {r.hint}")
        print(f"{mxlint.engine.SUPPRESSION_RULE_ID}")
        print("    fix: append ' -- <reason>' to the mxlint directive")
        return 0

    rules = None
    if args.rule:
        rules = [mxlint.rules.rule_by_id(rid) for rid in args.rule]
    try:
        findings, root = run_lint(args.paths or None, rules=rules)
    except FileNotFoundError as e:
        print(e, file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps({"findings": [f.to_dict() for f in findings],
                          "count": len(findings)}, indent=2))
    else:
        for f in findings:
            print(f.render(root=root))
        n = len(findings)
        print(f"mxlint: {n} finding{'s' if n != 1 else ''}"
              + ("" if n else " — tree is clean"))
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
