"""Device time a step of the operations whose `op_name` holds `jvp(` and not
`transpose(`: the forward pass, the loss included (lib/scopes.py; the names
are the program's: docs/profiler.md, "Names in a device trace"). With
backward, optimizer and the `other` on the earlier `scoped` line it sums to
the busy time of a step."""
from lib import scopes


def read(bench):
    scoped = scopes.of(bench)
    return scoped and scoped["phase_ms"]["forward"]
