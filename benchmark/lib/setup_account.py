"""The program's own account of its set-up: the `setup/*` gauges of
`profiler.counters()` (incubator_mxnet_tpu/profiler/build.py, docs/profiler.md
"Set-up's spans and counters"). The account closes itself at the end of the
first fused-step dispatch that compiled nothing, so what the harness builds
after the window (`program_bytes`, the reference comparison) is not in it."""
from incubator_mxnet_tpu import profiler


def seconds(*gauges):
    """The sum of the named gauges; None where the program keeps no such
    account (a checkout from before it had one)."""
    counted = profiler.counters()
    values = [counted.get("setup/" + gauge) for gauge in gauges]
    return None if None in values else sum(values)
