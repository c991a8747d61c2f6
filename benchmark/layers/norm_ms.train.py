"""Device time a step, forward and backward, of the operations owned by the
op scopes `batch_norm` or `layer_norm` (ops/_raw.py). A fusion is owned by
what its ROOT was traced under: a normalisation pass that XLA fused into a
convolution or a matmul is that operation's, not counted here
(lib/scopes.py)."""
from lib import scopes


def read(bench):
    scoped = scopes.of(bench)
    return scoped and scoped["norm_ms"]
