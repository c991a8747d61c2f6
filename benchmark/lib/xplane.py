"""The one reduction of the profiler's `.xplane.pb` to numbers: device busy
time (the union of the intervals in which an operation ran), the idle
share of the traced window, the operations that took most time, and the
idle gaps named by what the host was doing in them.

Read with `jax.profiler.ProfileData`, nothing else. A trace without a
device plane raises: "0 busy" must never be a reading.

`describe(path)` lists a trace's planes and lines: look at one by hand
before changing the names below.
"""
import glob
import os
import re

from . import intervals

DEVICE_PLANE = "/device:TPU:"     # one plane per chip
OPS_LINE = "XLA Ops"              # one event per executed HLO operation
HOST_PLANE = "/host:CPU"          # one line per host thread
TOP = 10
LABEL = 160                       # characters of an operation's HLO text
# a hole this short sits between two operations of one program that run
# back to back: nothing the host does could close it
BETWEEN_OPS_NS = 1000


class NoDevicePlane(RuntimeError):
    """The trace holds no plane of an accelerator."""


def newest(trace_dir):
    """The trace a `jax.profiler.start_trace(trace_dir)` session wrote."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path):
    """{plane name: {line name: [(event name, start_ns, end_ns), ...]}}.
    Lines of one name within a plane are joined."""
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, ev.start_ns,
                               ev.start_ns + ev.duration_ns))
    return planes


def instruction(name):
    """The trace names a device operation by its whole HLO text,
    `%fusion.9 = bf16[8,128]{1,0:T(8,128)} fusion(...)`: the part before
    the `=` is the instruction."""
    return name.split(" = ", 1)[0]


def kind(name):
    """`%fusion.123` and `%fusion.7` are one kind of operation, `%fusion`:
    the numbers are the compiler's and change with every build."""
    lhs = instruction(name)
    return re.sub(r"[.\d]+$", "", lhs) or lhs


def label(name):
    """The HLO text without layouts, comments and the compiler's numbers,
    cut to a length a table holds: the same operation on the same shapes
    in twelve layers is ONE row, and survives a rebuild."""
    text = re.sub(r"\{[^{}]*\}|/\*.*?\*/|\.\d+", "", name)
    return text[:LABEL]


def reduce(planes, host_prefix="bench."):
    """The numbers of one trace. `host_prefix` picks the host spans (the
    benchmark's own `TraceAnnotation`s) that may name an idle gap."""
    chips = {name: lines[OPS_LINE] for name, lines in planes.items()
             if name.startswith(DEVICE_PLANE) and lines.get(OPS_LINE)}
    if not chips:
        raise NoDevicePlane(
            f"no plane {DEVICE_PLANE}* with a line {OPS_LINE!r}; the trace "
            f"holds {sorted(planes)}")
    host = [(name, s, e)
            for line in planes.get(HOST_PLANE, {}).values()
            for name, s, e in line if name.startswith(host_prefix)]
    busy_ns = window_ns = 0.0
    by_op, by_host = {}, {}
    for ops in chips.values():
        spans = [(s, e) for _, s, e in ops]
        window = (min(s for s, _ in spans), max(e for _, e in spans))
        busy_ns += intervals.covered(spans)
        window_ns += window[1] - window[0]
        for name, s, e in ops:
            by_op[name] = by_op.get(name, 0.0) + (e - s)
        for gap in intervals.gaps(spans):
            if gap[1] - gap[0] < BETWEEN_OPS_NS:
                doing = "between-ops"
            else:
                span = max(host, key=lambda h: intervals.overlap(gap, h[1:]),
                           default=None)
                doing = (span[0] if span and intervals.overlap(gap, span[1:])
                         else "no-host-span")
            by_host[doing] = by_host.get(doing, 0.0) + (gap[1] - gap[0])
    n = len(chips)

    def top(table):
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ns / n / 1e9] for name, ns in ranked]

    by_kind, by_label = {}, {}
    for name, ns in by_op.items():
        by_kind[kind(name)] = by_kind.get(kind(name), 0.0) + ns
        by_label[label(name)] = by_label.get(label(name), 0.0) + ns
    return {"chips": n, "busy_s": busy_ns / n / 1e9,
            "window_s": window_ns / n / 1e9,
            "device_ops": top(by_label), "device_op_kinds": top(by_kind),
            "idle_gaps": top(by_host)}


def describe(path):
    for plane, lines in load(path).items():
        print(f"plane {plane!r}")
        for line, events in lines.items():
            span = (max(e for _, _, e in events) -
                    min(s for _, s, _ in events)) / 1e9 if events else 0.0
            print(f"  line {line!r}: {len(events)} events over {span:.3f} s;"
                  f" first {[e[0][:60] for e in events[:3]]}")

