"""Optimized-HLO text parsing for collective extraction.

GSPMD collectives do not exist in the traced program — the SPMD
partitioner inserts them at compile time, so the only artifact that
names every all-reduce/all-gather the step will actually run is the
compiled module's HLO text (``Compiled.as_text()``). This module turns
that text into structured records without ever raising: the capture
path runs inside compile sites, and a parse surprise must cost a
collective's attribution, not the compile.

What the parser understands (validated against the XLA:CPU dumps the
tier-1 matrix compiles — see tests/test_commscope.py for captured
shapes):

* instruction lines ``%name = <shape> <opcode>(<operands>), attrs`` —
  including ``ROOT`` markers, tuple-typed results, and typed operands;
* the collective op family ``all-reduce`` / ``all-gather`` /
  ``reduce-scatter`` / ``all-to-all`` / ``collective-permute`` plus
  their async ``-start``/``-done`` split (counted once, on the start),
  with any other ``collective-*``/``all-*`` spelling mapped to
  ``"other"`` rather than dropped or raised on;
* both replica-group syntaxes: explicit ``{{0,1},{2,3}}`` and iota
  ``[2,2]<=[4]`` / ``[2,2]<=[2,2]T(1,0)`` (reshape-transpose form);
* shape strings ``f32[64,32]{1,0}`` (layout suffix ignored) and tuple
  shapes, with per-dtype byte widths for payload accounting.

The operand-provenance chase (:func:`chases_to_parameter`) is the
resharding detector's evidence: a collective whose input walks back
through layout-only ops (copy/bitcast/transpose/reshape/convert) to a
program ``parameter`` is moving an *input* the caller annotated, not a
computed value — the "accidental all-gather" signature.
"""
from __future__ import annotations

import re

__all__ = ["COLLECTIVE_KINDS", "DTYPE_BYTES", "parse_shape", "shape_bytes",
           "shape_max_leaf_bytes", "parse_replica_groups",
           "parse_instructions", "parse_collectives",
           "chases_to_parameter"]

# the closed op-kind taxonomy (tools/trace_check.py enforces it in
# extra.commscope): every record's `kind` is one of these. Unknown
# collective spellings land on "other" — never a raise, never a drop.
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute", "other")

# HLO primitive-type byte widths (token/opaque/tuple have no payload)
DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "s16": 2, "s32": 4, "s64": 8,
    "u2": 1, "u4": 1, "u8": 1, "u16": 2, "u32": 4, "u64": 8,
    "f8e5m2": 1, "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "f16": 2, "bf16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

# XLA marks every fifth element of a long tuple shape with a comment,
# "/*index=5*/" — it holds the "=" the tuple patterns below stop at, so
# comments are dropped from a line before it is matched
_COMMENT_RE = re.compile(r"/\*.*?\*/")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{[^}]*\})?")

# one collective instruction: "%name = <shape> <op>(" with the op drawn
# from the all-*/collective-* family (async -start/-done included)
_COLL_RE = re.compile(
    r"%([\w.\-]+)\s*=\s*"                     # instruction name
    r"((?:\([^=]*?\))|(?:\S+))\s+"            # result shape (maybe tuple)
    r"((?:all|collective|reduce-scatter)[a-z\-]*)"   # op name
    r"\(")
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*"
    r"(?:\([^=]*?\)|\S+)\s+([\w\-]+)\(")
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[0-9,{} ]*\}\}|\[[^\]]*\]"
                        r"<=\[[0-9,]*\](?:T\([0-9,]*\))?)")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_DIMS_RE = re.compile(r"dimensions=\{([0-9,]*)\}")
# a typed operand inside the call parens: "f32[16,32]{1,0} %param.1"
_OPERAND_RE = re.compile(r"([a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)?\s*"
                         r"%([\w.\-]+)")

# ops that only change layout/metadata — chasing THROUGH them preserves
# "this value is a program input" provenance
_PASSTHROUGH_OPS = frozenset(
    ("copy", "bitcast", "reshape", "transpose", "convert", "copy-start",
     "copy-done", "optimization-barrier"))


def parse_shape(s):
    """One HLO shape string → list of (dtype, dims) leaves.

    ``"f32[64,32]{1,0}"`` → ``[("f32", (64, 32))]``; a tuple shape
    yields one leaf per element; anything unrecognizable yields ``[]``
    (never raises)."""
    out = []
    try:
        for m in _SHAPE_RE.finditer(s or ""):
            dims = tuple(int(d) for d in m.group(2).split(",") if d != "")
            out.append((m.group(1), dims))
    except Exception:  # noqa: BLE001 — parser contract: never raise
        return []
    return out


def shape_bytes(s) -> int:
    """Total payload bytes of a shape string (tuples summed; unknown
    dtypes count 0 so garbage can't inflate the accounting)."""
    total = 0
    for dtype, dims in parse_shape(s):
        width = DTYPE_BYTES.get(dtype)
        if width is None:
            continue
        n = 1
        for d in dims:
            n *= d
        total += n * width
    return total


def shape_max_leaf_bytes(s) -> int:
    """Largest single leaf's bytes — the right result accounting for
    async ``-start`` ops, whose tuple result aliases the source operand
    and context buffers NEXT TO the destination (summing would count
    the payload ~twice)."""
    best = 0
    for dtype, dims in parse_shape(s):
        width = DTYPE_BYTES.get(dtype)
        if width is None:
            continue
        n = 1
        for d in dims:
            n *= d
        best = max(best, n * width)
    return best


def _iota_groups(dims, reshape, perm):
    n = 1
    for d in reshape:
        n *= d
    flat = list(range(n))
    if perm:
        # reshape to `reshape`, transpose by `perm`, flatten (row-major)
        import itertools
        strides = [0] * len(reshape)
        acc = 1
        for i in range(len(reshape) - 1, -1, -1):
            strides[i] = acc
            acc *= reshape[i]
        out = []
        for idx in itertools.product(*[range(reshape[p]) for p in perm]):
            out.append(sum(idx[k] * strides[perm[k]]
                           for k in range(len(perm))))
        flat = out
    if len(dims) < 1:
        return None
    group_size = dims[-1]
    if group_size <= 0 or len(flat) % group_size:
        return None
    return [flat[i:i + group_size] for i in range(0, len(flat), group_size)]


def parse_replica_groups(s):
    """Replica-group attribute → list of device-id lists, or None.

    Handles the explicit form ``{{0,1},{2,3}}`` and the iota form
    ``[groups,size]<=[reshape-dims]`` with an optional ``T(perm)``
    transpose suffix (the two spellings XLA's CPU/TPU pipelines emit)."""
    if not s:
        return None
    s = s.strip()
    try:
        if s.startswith("{"):
            groups = []
            for grp in re.findall(r"\{([0-9, ]*)\}", s):
                ids = [int(x) for x in grp.replace(" ", "").split(",")
                       if x != ""]
                if ids:
                    groups.append(ids)
            return groups or None
        m = re.match(r"\[([0-9,]*)\]<=\[([0-9,]*)\](?:T\(([0-9,]*)\))?$", s)
        if not m:
            return None
        dims = [int(x) for x in m.group(1).split(",") if x != ""]
        reshape = [int(x) for x in m.group(2).split(",") if x != ""]
        perm = tuple(int(x) for x in m.group(3).split(",") if x != "") \
            if m.group(3) else None
        return _iota_groups(dims, reshape, perm)
    except Exception:  # noqa: BLE001
        return None


def parse_instructions(text) -> dict:
    """All instruction definitions in an HLO module text:
    ``{name: (opcode, first_operand_name)}`` — the minimum the
    provenance chase needs. Malformed lines are skipped."""
    defs = {}
    for line in (text or "").splitlines():
        line = _COMMENT_RE.sub("", line)
        m = _DEF_RE.match(line)
        if not m:
            continue
        first = None
        paren = line[m.end():]
        om = re.search(r"%([\w.\-]+)", paren)
        if om:
            first = om.group(1)
        defs[m.group(1)] = (m.group(2), first)
    return defs


def chases_to_parameter(defs: dict, name, max_depth: int = 8) -> bool:
    """True when `name`'s value is a program input reached only through
    layout-preserving ops. ``defs`` comes from :func:`parse_instructions`."""
    seen = 0
    while name is not None and seen <= max_depth:
        entry = defs.get(name)
        if entry is None:
            return False
        opcode, first = entry
        if opcode == "parameter":
            return True
        if opcode not in _PASSTHROUGH_OPS:
            return False
        name = first
        seen += 1
    return False


def _normalize_kind(raw: str):
    """Raw HLO op name → (taxonomy kind, counted) — async ``-done``
    halves are the uncounted tail of their ``-start``."""
    if raw.endswith("-done"):
        return None, False
    base = raw[:-6] if raw.endswith("-start") else raw
    if base in COLLECTIVE_KINDS:
        return base, True
    # anything else in the all-*/collective-* family: closed-taxonomy
    # bucket, never a raise (collective-broadcast, future op kinds, ...)
    return "other", True


def parse_collectives(text) -> list:
    """Every collective instruction in an HLO module text, as records::

        {"name", "kind", "raw_kind", "result_bytes", "operand_bytes",
         "bytes", "dtype", "replica_groups", "group_size", "dims",
         "channel_id", "operands", "operand_shapes", "result_shape"}

    ``bytes`` is the larger of result/operand payload — the full
    (gathered / pre-scatter) array a ring algorithm actually moves.
    Never raises; returns ``[]`` for text with no collectives."""
    out = []
    if not text:
        return out
    try:
        lines = text.splitlines()
    except Exception:  # noqa: BLE001
        return out
    for line in lines:
        try:
            line = _COMMENT_RE.sub("", line)
            m = _COLL_RE.search(line)
            if not m:
                continue
            name, result_shape, raw = m.group(1), m.group(2), m.group(3)
            kind, counted = _normalize_kind(raw)
            if not counted:
                continue
            # operands: the parenthesized list right after the op name
            paren = line[m.end():]
            depth, end = 1, len(paren)
            for i, ch in enumerate(paren):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            operand_str = paren[:end]
            operands, operand_shapes = [], []
            for om in _OPERAND_RE.finditer(operand_str):
                operands.append(om.group(2))
                if om.group(1):
                    operand_shapes.append(om.group(1))
            attrs = paren[end:]
            gm = _GROUPS_RE.search(attrs)
            groups = parse_replica_groups(gm.group(1)) if gm else None
            cm = _CHANNEL_RE.search(attrs)
            dm = _DIMS_RE.search(attrs)
            # async -start results are tuples bundling the source
            # operand (and context scratch) WITH the destination; the
            # payload is the largest leaf, not the tuple sum — a sync
            # op's tuple result (variadic all-to-all) genuinely sums
            if raw.endswith("-start"):
                result_bytes = shape_max_leaf_bytes(result_shape)
            else:
                result_bytes = shape_bytes(result_shape)
            operand_bytes = sum(shape_bytes(s) for s in operand_shapes)
            leaves = parse_shape(result_shape)
            out.append({
                "name": name,
                "kind": kind,
                "raw_kind": raw,
                "result_shape": result_shape,
                "operand_shapes": operand_shapes,
                "operands": operands,
                "result_bytes": result_bytes,
                "operand_bytes": operand_bytes,
                "bytes": max(result_bytes, operand_bytes),
                "dtype": leaves[0][0] if leaves else None,
                "replica_groups": groups,
                "group_size": (len(groups[0]) if groups and groups[0]
                               else None),
                "dims": ([int(x) for x in dm.group(1).split(",") if x != ""]
                         if dm else None),
                "channel_id": int(cm.group(1)) if cm else None,
            })
        except Exception:  # noqa: BLE001 — skip the line, keep the rest
            continue
    return out
