"""Device time a step, forward and backward, of everything under the op
scope `cross_entropy` (ops/_raw.py `softmax_cross_entropy`: the softmax
cross-entropy of the loss, over the head's logits; lib/owned.py; the names
are the program's: docs/profiler.md, "Names in a device trace").

Only what XLA left with a `cross_entropy` root: where the compiler fuses
the loss's backward pass into the head's products, that time is the
head's. A program without the scope reads nothing."""
from lib import owned


def read(bench):
    return owned.ms_per_step(bench, owned.under("cross_entropy"))
