"""Device time a step, forward and backward, of everything under the op
scope `moe` (ops/_raw.py `sparse_experts`): router, dispatch, the experts'
grouped products and the combine (lib/owned.py; the names are the
program's: docs/profiler.md, "Names in a device trace")."""
from lib import owned


def read(bench):
    return owned.ms_per_step(bench, owned.under("moe"))
