"""Device time a step, forward and backward, of everything under the op
scopes `moe/router` (ops/_raw.py `router_mlp` and `sparse_experts`: the
down projection, the average over depth, the three products with GELU
between, softmax, the choice and the load's count; lib/owned.py; the names
are the program's: docs/profiler.md, "Names in a device trace")."""
from lib import owned


def read(bench):
    return owned.ms_per_step(bench, owned.under("moe", "router"))
