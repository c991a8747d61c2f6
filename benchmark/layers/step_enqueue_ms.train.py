"""Median host time of the span `mxtpu.step.enqueue`: the call of the jitted
step inside `FusedTrainStep.__call__`, a part of `dispatch_ms.train`. From
the traced part, on the trace's clock (lib/scopes.py); its siblings `args`
and `rebind` are on the earlier `scoped` line."""
from lib import scopes


def read(bench):
    scoped = scopes.of(bench)
    return scoped and scoped["span_ms"].get("mxtpu.step.enqueue")
