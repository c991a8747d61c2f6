"""Device time a step of the operations a per-layer metric owns, picked by
the names the program compiled in (lib/scopes.py's grammar: the owner path
of an operation's `op_name`, and the kernel's own name on the event).

`scopes.reduce` keeps a few fixed sums; a metric of another op scope or
kernel reads the trace's events through here. The events are read once and
kept on `bench`. Nothing to read (no trace, a program without the names,
no operation that matches) is None, never an error.
"""
from . import scopes, xplane


def events(bench):
    """([(event name, owner path parts, phase, ns)], steps x chips) of the
    device operations in a `--trace 1` run's trace; None without one."""
    scoped = scopes.of(bench)
    if not scoped:
        return None
    if not hasattr(bench, "owned_events"):
        path = xplane.newest(bench.trace_dir)
        names = scopes.op_names(path)
        chips = [lines[xplane.OPS_LINE]
                 for plane, lines in xplane.load(path).items()
                 if plane.startswith(xplane.DEVICE_PLANE)
                 and lines.get(xplane.OPS_LINE)]
        bench.owned_events = (
            [(name, scopes.owner(names.get(name, "")).split("/"),
              scopes.phase(names.get(name, "")), end - start)
             for ops in chips for name, start, end in ops],
            scoped["steps"] * len(chips))
    return bench.owned_events


def ms_per_step(bench, wanted):
    """Summed device ms a step of the events for which wanted(event name,
    owner path parts, phase) holds; None where there is none."""
    found = events(bench)
    if not found:
        return None
    every, steps = found
    ns = [ns for name, parts, phase, ns in every if wanted(name, parts, phase)]
    return sum(ns) / 1e6 / steps if ns else None


def under(*scope):
    """wanted(): the owner path holds `scope`'s components in a row."""
    n = len(scope)

    def wanted(name, parts, phase):
        return any(tuple(parts[i:i + n]) == scope
                   for i in range(len(parts) - n + 1))
    return wanted
