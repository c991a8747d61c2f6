"""Forward device time a step under the op scope `attention` (ops/_raw.py
`multihead_attention`, both of ops/select.py's branches): the kernel
`flash_attention_fwd` or XLA's attention, with the head split, padding and
merge around it (lib/scopes.py). The kernel alone is a row of the earlier
`scoped` line."""
from lib import scopes


def read(bench):
    scoped = scopes.of(bench)
    return scoped and scoped["attention_ms"].get("forward")
