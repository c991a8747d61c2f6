"""Device time a step, forward and backward, of everything under the op
scope `rms_norm` (ops/_raw.py `rms_norm`: two a block and the final one;
lib/owned.py; the names are the program's: docs/profiler.md, "Names in a
device trace")."""
from lib import owned


def read(bench):
    return owned.ms_per_step(bench, owned.under("rms_norm"))
