"""Who owns a device operation, and in which phase of the step it ran: the
names the program compiles into its fused step (docs/profiler.md, "Names
in a device trace"), read back from a profiler trace.

The grammar, on an operation's `op_name` (jax's name stack, which XLA keeps
as metadata of every instruction):

    jit(train_step)/transpose(jvp(transformer_lm_0))/cell_3/attention/dot_general

- the **owner** is the `/`-separated path of block names and op scopes:
  every component but the first (the jitted function) and the last (the
  primitive), wrappers stripped: `jvp(x)` and `transpose(jvp(x))` give
  `x`, a nested `jit(relu)` and remat's `checkpoint` give nothing;
- the **phase** is `backward` if the name holds `transpose(`, else
  `forward` if it holds `jvp(`, else `optimizer` if the owner holds that
  component, else `other` (parameters, what the compiler made itself).
  `loss` is an owner, not a phase.

Where XLA joined two names with `;`, the first decides both. A fusion
carries what its root instruction was traced under: the compiler's rule.

Events and times come from `xplane.load`, the `op_name`s from this file's
own reader of the trace file; `reduce` takes both as plain data, so a test
feeds it hand-made events.
"""
import re
import statistics

from . import intervals, xplane

PHASES = ("forward", "backward", "optimizer", "other")
STEP_SPAN = "mxtpu.step"            # one per FusedTrainStep call
PROGRAMS_LINE = "XLA Modules"       # one event per executed program
OP_NAME_STAT = "tf_op"              # the stat of an `XLA Ops` event
TOP = 15
_WRAPPER = re.compile(r"(\w+)\((.*)\)\Z")
_NOT_A_SCOPE = {"checkpoint", "rematted_computation", "remat"}


def _first(op_name):
    return op_name.split(";", 1)[0]


def owner(op_name):
    parts = _first(op_name).split("/")
    if not parts[0].startswith(("jit(", "pjit(")):
        return ""       # not traced under the step: a parameter, a copy
    path = []
    for part in parts[1:-1]:
        while (wrapped := _WRAPPER.match(part)):
            part = ("" if wrapped.group(1) in ("jit", "pjit")
                    else wrapped.group(2))
        if part and part not in _NOT_A_SCOPE:
            path.append(part)
    return "/".join(path)


def phase(op_name):
    name = _first(op_name)
    if "transpose(" in name:
        return "backward"
    if "jvp(" in name:
        return "forward"
    if "optimizer" in owner(name).split("/"):
        return "optimizer"
    return "other"


def owner_class(path):
    """`cell_3/dense_14` and `cell_7/dense_30` are one class of owner,
    `cell/dense`: the counters at the end of a block's name are the
    order the blocks were made in."""
    return "/".join(re.sub(r"_?\d+\Z", "", part) for part in path.split("/"))


# -- the trace file ---------------------------------------------------------
# `jax.profiler.ProfileData` shows an event's own stats (on a TPU's `XLA
# Ops` line: device_offset_ps, device_duration_ps, Time Scale Multiplier).
# The `op_name` is a stat of the event's METADATA (`tf_op`, as
# "<op_name>:"), which ProfileData does not show, so the few fields of the
# file that hold it are read here, from the protobuf wire format:
#   XSpace.planes = 1;  XPlane.name = 2, .event_metadata = 4 (map: value =
#   2), .stat_metadata = 5 (map);  XEventMetadata.name = 2, .stats = 5;
#   XStatMetadata.id = 1, .name = 2;  XStat.metadata_id = 1, .str_value = 5

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one message: an int for a varint, the
    bytes for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an .xplane.pb")
            value, i = buf[i:i + size], i + size
        yield number, value


def _text(value):
    return bytes(value).decode("utf-8", "replace")


def op_names(path):
    """{event name: op_name} of the operations on the device planes of an
    `.xplane.pb`; empty where the trace holds no such stat."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    found = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        plane = list(_fields(plane))
        if not any(n == 2 and _text(v).startswith(xplane.DEVICE_PLANE)
                   for n, v in plane):
            continue
        stat_ids = set()
        for n, entry in plane:
            if n == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                if _text(meta.get(2, b"")) == OP_NAME_STAT:
                    stat_ids.add(meta.get(1))
        for n, entry in plane:
            if n != 4:
                continue
            name = op_name = None
            for m, value in _fields(dict(_fields(entry))[2]):
                if m == 2:
                    name = _text(value)
                elif m == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in stat_ids and 5 in stat:
                        op_name = _text(stat[5]).rstrip(":")
            if name is not None and op_name is not None:
                found[name] = op_name
    return found


# -- the reduction ----------------------------------------------------------

def _median_ms(spans, name):
    found = [(e - s) / 1e6 for n, s, e in spans if n == name]
    return statistics.median(found) if found else None


def _innermost(gap, children, whole):
    """What the program was doing in an idle gap: the child of `mxtpu.step`
    that covers most of it, else the step itself (its own time, between
    two children), else nothing of the program's."""
    for spans in (children, whole):
        best = max(spans, default=None,
                   key=lambda span: intervals.overlap(gap, span[1:]))
        if best is not None and intervals.overlap(gap, best[1:]):
            return best[0]
    return "no-mxtpu-span"


def reduce(planes, names):
    """Per-step device time by owner and phase, from `xplane.load`'s planes
    and `op_names`'s table. A per-step value is summed device time over
    the trace / the number of `mxtpu.step` spans in it, averaged over the
    chips. None where the trace holds no device operation, no step span or
    no `op_name`: a program without the names (an older checkout) reads
    nothing."""
    chips = [lines[xplane.OPS_LINE] for plane, lines in planes.items()
             if plane.startswith(xplane.DEVICE_PLANE)
             and lines.get(xplane.OPS_LINE)]
    spans = [event for line in planes.get(xplane.HOST_PLANE, {}).values()
             for event in line if event[0].startswith(STEP_SPAN)]
    whole = [span for span in spans if span[0] == STEP_SPAN]
    children = [span for span in spans if span[0] != STEP_SPAN]
    if not chips or not whole or not names:
        return None
    per_step = 1e6 * len(whole) * len(chips)    # ns summed -> ms a step
    by_event, gaps = {}, {}
    for ops in chips:
        for name, start, end in ops:
            by_event[name] = by_event.get(name, 0.0) + (end - start)
        for gap in intervals.gaps([(s, e) for _, s, e in ops]):
            doing = ("between-ops"
                     if gap[1] - gap[0] < xplane.BETWEEN_OPS_NS
                     else _innermost(gap, children, whole))
            gaps[doing] = gaps.get(doing, 0.0) + (gap[1] - gap[0])
    by_phase = dict.fromkeys(PHASES, 0.0)
    by_class, attention = {}, {}
    norm = unowned = 0.0
    for name, ns in by_event.items():
        op_name = names.get(name, "")
        path, when = owner(op_name), phase(op_name)
        by_phase[when] += ns
        key = (owner_class(path), when)
        by_class[key] = by_class.get(key, 0.0) + ns
        parts = path.split("/")
        if "attention" in parts:
            attention[when] = attention.get(when, 0.0) + ns
        if "batch_norm" in parts or "layer_norm" in parts:
            norm += ns
        if not path:
            unowned += ns
    total = sum(by_event.values())
    programs = sum(len(lines.get(PROGRAMS_LINE, ()))
                   for plane, lines in planes.items()
                   if plane.startswith(xplane.DEVICE_PLANE))
    ranked = sorted(by_class.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "steps": len(whole),
        "phase_ms": {when: ns / per_step for when, ns in by_phase.items()},
        "device_ms": total / per_step,
        "attention_ms": {when: ns / per_step
                         for when, ns in attention.items()},
        "norm_ms": norm / per_step,
        "unowned_pct": 100.0 * unowned / total,
        "by_owner_class": [[path, when, ns / per_step]
                           for (path, when), ns in ranked],
        "programs_per_step": programs / (len(whole) * len(chips)),
        "span_ms": {name: _median_ms(spans, name)
                    for name in sorted({n for n, _, _ in spans})},
        "idle_gaps_ms": {doing: ns / per_step
                         for doing, ns in sorted(gaps.items(),
                                                 key=lambda kv: -kv[1])},
    }


def of(bench):
    """The reduction of a `--trace 1` run's trace, made once and kept on
    `bench`; the whole table goes out on an earlier line. None without a
    device trace."""
    if not getattr(bench, "trace_summary", None):
        return None
    if not hasattr(bench, "scoped"):
        path = xplane.newest(bench.trace_dir)
        bench.scoped = reduce(xplane.load(path), op_names(path))
        if bench.scoped:
            busy = bench.trace_summary["busy_s"] * 1e3 / bench.scoped["steps"]
            bench.note(scoped=dict(bench.scoped, busy_ms_per_step=busy))
    return bench.scoped
