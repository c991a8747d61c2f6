"""Backward device time a step under the op scope `attention`: the kernels
`flash_attention_dq` and `flash_attention_dkv`, or XLA's attention
backward, with what surrounds them in the scope (lib/scopes.py)."""
from lib import scopes


def read(bench):
    scoped = scopes.of(bench)
    return scoped and scoped["attention_ms"].get("backward")
