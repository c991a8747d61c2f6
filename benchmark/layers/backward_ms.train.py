"""Device time a step of the operations whose `op_name` holds `transpose(`:
the backward pass, with what XLA fused into it (most of the optimizer's
update rides in the fusions that produce the gradients) and, under remat,
the recomputed forward (lib/scopes.py)."""
from lib import scopes


def read(bench):
    scoped = scopes.of(bench)
    return scoped and scoped["phase_ms"]["backward"]
