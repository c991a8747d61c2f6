"""Device time a step, forward and backward, of everything under the op
scope `linear_attention` (ops/_raw.py `linear_attention`: the short
convolutions, the gates, the chunked scan of the gated delta rule and the
gated output norm of every such layer; the projections around it are the
blocks'; lib/owned.py; the names are the program's: docs/profiler.md,
"Names in a device trace")."""
from lib import owned


def read(bench):
    return owned.ms_per_step(bench, owned.under("linear_attention"))
