"""Device time a step of the operations under `train_step`'s `optimizer`
scope that XLA left on their own: an update fused into the operation that
produces its gradient is owned by that operation, and counts as backward
(lib/scopes.py)."""
from lib import scopes


def read(bench):
    scoped = scopes.of(bench)
    return scoped and scoped["phase_ms"]["optimizer"]
