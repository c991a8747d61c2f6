"""mxtpu.mxlint.rules — the framework-invariant rule set.

Each rule encodes an invariant a PR 6–13 review-hardening pass paid to
re-learn by hand (docs/mxlint.md cites the motivating PR per rule):

=============================  =========================================
rule id                        invariant
=============================  =========================================
``raw-env-read``               every MXTPU_* read inside the package
                               routes through ``settings.py`` (or the
                               documented allowlist below)
``unregistered-counter``       a metric in a governed family
                               (``mxlint/families.py``) must be
                               registered there before a producer may
                               emit it
``raise-in-never-raise``       modules documented never-raise
                               (commscope/devicescope ingest parsers)
                               may not leak an uncaught ``raise``
``unnormalized-device-kind``   device-kind strings are compared only
                               through ``normalize_device_kind`` (or an
                               explicit ``.lower()`` pipeline)
``thread-shared-mutation``     module-global rebinding inside the
                               threaded subsystems happens under a lock
``duplicated-default-table``   a literal default table must have ONE
                               home — a structurally equal copy in a
                               second module WILL drift
=============================  =========================================
"""
from __future__ import annotations

import ast

from . import families
from .engine import Rule

__all__ = ["RULES", "default_rules", "rule_by_id", "RAW_ENV_ALLOWLIST",
           "NEVER_RAISE_MODULES", "THREADED_MODULES",
           "RawEnvReadRule", "UnregisteredCounterRule",
           "RaiseInNeverRaiseRule", "UnnormalizedDeviceKindRule",
           "ThreadSharedMutationRule", "DuplicatedDefaultTableRule"]


# ---------------------------------------------------------------------------
# raw-env-read
# ---------------------------------------------------------------------------

# The documented allowlist: env name -> {reason, files}. ``files`` (path
# suffixes) pins WHERE the raw read is legal; None = anywhere in the
# package. Every entry needs a reason a reviewer can audit — that IS the
# policy (docs/mxlint.md).
RAW_ENV_ALLOWLIST = {
    "MXTPU_HEALTHMON": {
        "reason": "import-time arming knob, read once from "
                  "enable_from_env before the knob home is guaranteed "
                  "importable",
        "files": ("healthmon/__init__.py",)},
    "MXTPU_DIAG": {
        "reason": "import-time arming knob (diagnostics enable_from_env)",
        "files": ("diagnostics/__init__.py",)},
    "MXTPU_PERFSCOPE": {
        "reason": "import-time arming knob (perfscope enable_from_env; "
                  "carries the non-boolean 'jit0' spelling)",
        "files": ("perfscope/__init__.py",)},
    "MXTPU_COMMSCOPE": {
        "reason": "import-time arming knob (commscope enable_from_env)",
        "files": ("commscope/__init__.py",)},
    "MXTPU_DEVICESCOPE": {
        "reason": "import-time arming knob (devicescope enable_from_env)",
        "files": ("devicescope/__init__.py",)},
    "MXTPU_SERVESCOPE": {
        "reason": "import-time arming knob (servescope enable_from_env)",
        "files": ("servescope/__init__.py",)},
    "MXTPU_MEMSCOPE": {
        "reason": "import-time arming knob (memscope enable_from_env)",
        "files": ("memscope/__init__.py",)},
    "MXTPU_FLEETSCOPE": {
        "reason": "import-time arming knob (fleetscope enable_from_env)",
        "files": ("fleetscope/__init__.py",)},
    "MXTPU_STRICT": {
        "reason": "import-time arming knob (mxlint.runtime "
                  "enable_from_env)",
        "files": ("mxlint/runtime.py",)},
    "MXTPU_AUTO_BULK": {
        "reason": "module-import-time read in the dispatch core, before "
                  "package init finishes — resolving through the knob "
                  "home mid-init would be an import-order bet",
        "files": ("bulk.py",)},
    "MXTPU_PROCESS_ID": {
        "reason": "crash/signal-dump path (flight recorder env snapshot) "
                  "— must stay import-free and never-raise",
        "files": ("diagnostics/flight.py",)},
    "MXTPU_DIAG_DIR": {
        "reason": "crash/signal-dump path (flight recorder dump dir) — "
                  "must stay import-free and never-raise",
        "files": ("diagnostics/flight.py",)},
}

_ENV_PREFIXES = ("MXTPU_",)

# the resolution home itself, plus this package (the rule engine and
# allowlist tables spell knob names as data)
_ENV_EXEMPT_SUFFIXES = ("incubator_mxnet_tpu/settings.py",
                        "mxlint/rules.py", "mxlint/engine.py",
                        "mxlint/families.py")


def _path_matches(relpath: str, suffixes) -> bool:
    """Component-anchored suffix match: 'healthmon/__init__.py' matches
    .../healthmon/__init__.py but NOT .../myhealthmon/__init__.py — an
    unanchored endswith would let a suffix-colliding module escape the
    rule it is named in."""
    anchored = "/" + relpath
    return any(anchored.endswith("/" + s) for s in suffixes)


def _is_environ(node) -> bool:
    """``os.environ`` / bare ``environ`` reference."""
    if isinstance(node, ast.Attribute) and node.attr == "environ":
        return True
    return isinstance(node, ast.Name) and node.id == "environ"


def _is_getenv(func) -> bool:
    """``os.getenv`` / bare ``getenv`` reference."""
    if isinstance(func, ast.Attribute) and func.attr == "getenv":
        return True
    return isinstance(func, ast.Name) and func.id == "getenv"


class RawEnvReadRule(Rule):
    id = "raw-env-read"
    hint = ("resolve through incubator_mxnet_tpu/settings.py (resolve() "
            "for loop_chunk/prefetch_depth/io_workers/pallas; "
            "env_str/env_int/env_float/env_flag for everything else), "
            "or add the name to mxlint.rules.RAW_ENV_ALLOWLIST with a "
            "reason")

    def applies(self, relpath: str) -> bool:
        # the package only: tools/ parse their own command lines
        if "/incubator_mxnet_tpu/" not in f"/{relpath}":
            return False
        return not _path_matches(relpath, _ENV_EXEMPT_SUFFIXES)

    def _name_findings(self, ctx, node, name_node):
        if isinstance(name_node, ast.Constant) \
                and isinstance(name_node.value, str):
            name = name_node.value
            if not name.startswith(_ENV_PREFIXES):
                return []
            entry = RAW_ENV_ALLOWLIST.get(name)
            if entry is not None and (
                    entry["files"] is None
                    or _path_matches(ctx.relpath, entry["files"])):
                return []
            return [self.finding(
                ctx, node,
                f"raw environment read of knob {name!r} bypasses the "
                f"documented resolution order (call-site > MXTPU_* > "
                f"default)")]
        # dynamic name: local env helpers are exactly how the knob
        # spellings historically drifted — they must live in settings.py
        return [self.finding(
            ctx, node,
            f"environment read with a dynamic name "
            f"({ctx.segment(name_node) or '<expr>'!s}) — local env "
            f"helpers are how knob spellings drift",
            hint="call the settings.env_* accessors instead of wrapping "
                 "os.environ locally (allowlist the file if it truly "
                 "cannot import settings.py)")]

    def check(self, ctx):
        out = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                if _is_getenv(node.func) and node.args:
                    out += self._name_findings(ctx, node, node.args[0])
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("get", "setdefault", "pop") \
                        and _is_environ(node.func.value) and node.args:
                    out += self._name_findings(ctx, node, node.args[0])
            elif isinstance(node, ast.Subscript) \
                    and _is_environ(node.value) \
                    and isinstance(node.ctx, ast.Load):
                out += self._name_findings(ctx, node, node.slice)
            elif isinstance(node, ast.Compare) \
                    and any(isinstance(op, (ast.In, ast.NotIn))
                            for op in node.ops) \
                    and any(_is_environ(c) for c in node.comparators):
                out += self._name_findings(ctx, node, node.left)
        return out


# ---------------------------------------------------------------------------
# unregistered-counter
# ---------------------------------------------------------------------------

# registry entry points and where their (name, domain) arguments sit
_COUNTER_CALLS = {"counter": (0, 1), "histogram": (0, 1),
                  "observe": (0, 2), "set_gauge": (0, 2)}
# calls that REQUIRE the metric be histogram-kind in its family table
_HISTOGRAM_CALLS = {"histogram", "observe"}


class UnregisteredCounterRule(Rule):
    id = "unregistered-counter"
    hint = ("register the metric in mxlint/families.py (the ONE family "
            "home trace_check and mxlint both derive from), or fix the "
            "name/domain typo")

    def _call_name(self, func):
        if isinstance(func, ast.Name):
            return func.id.lstrip("_")
        if isinstance(func, ast.Attribute):
            return func.attr.lstrip("_")
        return None

    def _const_str(self, call, pos, kw):
        for k in call.keywords:
            if k.arg == kw:
                node = k.value
                break
        else:
            if pos >= len(call.args):
                return None, False
            node = call.args[pos]
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value, True
        return None, False       # dynamic: not statically resolvable

    def check(self, ctx):
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = self._call_name(node.func)
            if fname not in _COUNTER_CALLS:
                continue
            name_pos, dom_pos = _COUNTER_CALLS[fname]
            name, name_ok = self._const_str(node, name_pos, "name")
            domain, dom_ok = self._const_str(node, dom_pos, "domain")
            if not dom_ok or domain not in families.FAMILY_TABLES:
                continue          # ungoverned domain (or dynamic)
            if not name_ok:
                continue          # dynamic metric name: runtime's job
            full = f"{domain}/{name}"
            kind = families.metric_kind(full)
            if kind is None:
                out.append(self.finding(
                    ctx, node,
                    f"metric {full!r} is not registered in the "
                    f"{domain!r} family table"))
            elif fname in _HISTOGRAM_CALLS and kind != "histogram":
                out.append(self.finding(
                    ctx, node,
                    f"metric {full!r} is declared {kind!r} in its "
                    f"family table but emitted via {fname}() "
                    f"(histogram-kind)"))
            elif fname == "set_gauge" and kind != "gauge":
                out.append(self.finding(
                    ctx, node,
                    f"metric {full!r} is declared {kind!r} in its "
                    f"family table but written via set_gauge()"))
        return out


# ---------------------------------------------------------------------------
# raise-in-never-raise
# ---------------------------------------------------------------------------

# modules whose PUBLIC contract is never-raise (each docstring says so);
# a raise is legal only under a try whose handler catches Exception
NEVER_RAISE_MODULES = {
    "devicescope/ingest.py":
        "devicescope trace ingestion: 'Every entry point is never-raise "
        "by contract'",
    "commscope/hlo.py":
        "commscope HLO parser: unknown spellings bucket as 'other', "
        "never a raise",
}


def _handler_catches_all(handler) -> bool:
    if handler.type is None:
        return True
    names = []
    t = handler.type
    for n in (t.elts if isinstance(t, ast.Tuple) else [t]):
        if isinstance(n, ast.Name):
            names.append(n.id)
        elif isinstance(n, ast.Attribute):
            names.append(n.attr)
    return any(n in ("Exception", "BaseException") for n in names)


class RaiseInNeverRaiseRule(Rule):
    id = "raise-in-never-raise"
    hint = ("wrap the failing region in try/except Exception and degrade "
            "(count + return the empty shape), or move the raising "
            "helper out of the never-raise module")

    def applies(self, relpath: str) -> bool:
        return _path_matches(relpath, NEVER_RAISE_MODULES)

    def check(self, ctx):
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Raise):
                continue
            guarded = False
            child = node
            for parent in ctx.parents(node):
                if isinstance(parent, ast.Try):
                    in_body = any(child is n or self._contains(n, child)
                                  for n in parent.body)
                    if in_body and any(_handler_catches_all(h)
                                       for h in parent.handlers):
                        guarded = True
                        break
                child = parent
            if not guarded:
                out.append(self.finding(
                    ctx, node,
                    "uncaught raise in a module documented never-raise"))
        return out

    @staticmethod
    def _contains(tree, node) -> bool:
        return any(n is node for n in ast.walk(tree))


# ---------------------------------------------------------------------------
# unnormalized-device-kind
# ---------------------------------------------------------------------------

# where the canonical spelling lives — comparisons inside it are the
# definition, not a violation
_DEVICE_KIND_HOME = ("incubator_mxnet_tpu/context.py",)


def _is_device_kind_ref(node) -> bool:
    """A RAW device-kind reference: a name / attribute / const-keyed
    subscript spelled *device_kind*, not wrapped in any normalizing
    call (a wrapped ref parses as a Call, so it never matches here)."""
    if isinstance(node, ast.Attribute):
        return "device_kind" in node.attr
    if isinstance(node, ast.Name):
        return "device_kind" in node.id
    if isinstance(node, ast.Subscript) \
            and isinstance(node.slice, ast.Constant) \
            and isinstance(node.slice.value, str):
        return "device_kind" in node.slice.value
    return False


def _is_stringy(node) -> bool:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return True
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return any(isinstance(e, ast.Constant)
                   and isinstance(e.value, str) for e in node.elts)
    return False


class UnnormalizedDeviceKindRule(Rule):
    id = "unnormalized-device-kind"
    hint = ("compare through context.normalize_device_kind(...) — jax "
            "reports 'TPU v4' raw while perfscope's peaks table stores "
            "lowercase, so a raw == is a silent never-match")

    def applies(self, relpath: str) -> bool:
        return not _path_matches(relpath, _DEVICE_KIND_HOME)

    def check(self, ctx):
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Compare):
                continue
            sides = [node.left] + list(node.comparators)
            raw = [s for s in sides if _is_device_kind_ref(s)]
            lit = [s for s in sides if _is_stringy(s)]
            if raw and lit:
                out.append(self.finding(
                    ctx, node,
                    f"device-kind string compared against a literal "
                    f"without normalize_device_kind "
                    f"({ctx.segment(node)[:60]!r})"))
        return out


# ---------------------------------------------------------------------------
# thread-shared-mutation
# ---------------------------------------------------------------------------

# the subsystems where a worker thread and the training/serving loop
# share module state (each runs at least one daemon thread)
THREADED_MODULES = (
    "serving/batcher.py",
    "fleet/continuous.py",
    "fleet/router.py",
    "fleet/cache.py",
    "io/prefetch.py",
    "io/pipeline.py",
    "resilience/checkpoint.py",
    "resilience/elastic.py",
    "resilience/policy.py",
    "healthmon/__init__.py",
    "healthmon/watchdog.py",
    "kvstore/async_ps.py",
    "diagnostics/__init__.py",
)


class ThreadSharedMutationRule(Rule):
    id = "thread-shared-mutation"
    hint = ("take the module lock around the write (with _lock: ...), "
            "or suppress with a reason proving single-threadedness "
            "(e.g. 'written before the worker thread starts')")

    def applies(self, relpath: str) -> bool:
        return _path_matches(relpath, THREADED_MODULES)

    def check(self, ctx):
        out = []
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            declared = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.Global):
                    declared.update(node.names)
            if not declared:
                continue
            for node in ast.walk(fn):
                if isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = (node.targets
                               if isinstance(node, ast.Assign)
                               else [node.target])
                    flat = []
                    for t in targets:
                        flat.extend(t.elts if isinstance(
                            t, (ast.Tuple, ast.List)) else [t])
                    hit = [t.id for t in flat
                           if isinstance(t, ast.Name) and t.id in declared]
                    if hit and not self._under_lock(ctx, node):
                        out.append(self.finding(
                            ctx, node,
                            f"module-global {hit[0]!r} rebound outside a "
                            f"lock in a threaded module (function "
                            f"{fn.name!r})"))
        return out

    def _under_lock(self, ctx, node) -> bool:
        for parent in ctx.parents(node):
            if isinstance(parent, ast.With):
                for item in parent.items:
                    if "lock" in ctx.segment(
                            item.context_expr).lower():
                        return True
        return False


# ---------------------------------------------------------------------------
# duplicated-default-table
# ---------------------------------------------------------------------------

class DuplicatedDefaultTableRule(Rule):
    id = "duplicated-default-table"
    hint = ("keep ONE home for the table and import it (a copy in a "
            "second module WILL drift); if the copies are genuinely "
            "independent, suppress with a reason")

    MIN_ENTRIES = 4

    def __init__(self):
        self._seen: dict = {}     # shape key -> [(relpath, path, line, name)]

    def _literal_key(self, node):
        """A hashable structural key for a constant-enough dict literal,
        or None when the dict holds computed parts."""
        try:
            items = []
            for k, v in zip(node.keys, node.values):
                if not (isinstance(k, ast.Constant)
                        and isinstance(k.value, (str, int, float))):
                    return None
                items.append((repr(k.value), ast.dump(v)))
            # constant values only — a dict of lambdas/calls is wiring,
            # not a default table
            for v in node.values:
                for sub in ast.walk(v):
                    if isinstance(sub, (ast.Call, ast.Lambda, ast.Name)):
                        return None
            return tuple(sorted(items))
        except Exception:  # noqa: BLE001 — best-effort structural match
            return None

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Assign) \
                    or not isinstance(node.value, ast.Dict) \
                    or len(node.value.keys) < self.MIN_ENTRIES:
                continue
            # module-level assignments only (a table built inside a
            # function is scratch state)
            parent = getattr(node, "_mxlint_parent", None)
            if not isinstance(parent, ast.Module):
                continue
            key = self._literal_key(node.value)
            if key is None:
                continue
            # this rule reports from finish(), after the engine's
            # per-file suppression filter already ran — honor the
            # directive at collection time instead
            if ctx.suppressed(self.id, node.lineno):
                continue
            name = (node.targets[0].id
                    if node.targets
                    and isinstance(node.targets[0], ast.Name) else "?")
            self._seen.setdefault(key, []).append(
                (ctx.relpath, ctx.path, node.lineno, name))
        return []

    def finish(self):
        from .engine import Finding
        out = []
        for key, sites in self._seen.items():
            files = {s[0] for s in sites}
            if len(files) < 2:
                continue
            # canonical home: prefer the package copy, then first path
            sites = sorted(sites, key=lambda s: (
                "incubator_mxnet_tpu/" not in f"/{s[0]}", s[0]))
            canon = sites[0]
            for rel, path, line, name in sites[1:]:
                out.append(Finding(
                    self.id, path, line, 0,
                    f"default table {name!r} is a structural duplicate "
                    f"of {canon[3]!r} in {canon[0]} — two homes WILL "
                    f"drift",
                    self.hint))
        self._seen.clear()
        return out


def default_rules() -> list:
    """Fresh rule instances (the duplicate-table rule is stateful)."""
    return [RawEnvReadRule(), UnregisteredCounterRule(),
            RaiseInNeverRaiseRule(), UnnormalizedDeviceKindRule(),
            ThreadSharedMutationRule(), DuplicatedDefaultTableRule()]


RULES = tuple(r.id for r in default_rules())


def rule_by_id(rule_id: str) -> Rule:
    for r in default_rules():
        if r.id == rule_id:
            return r
    raise KeyError(f"unknown mxlint rule {rule_id!r}; known: {RULES}")
