"""Median host time of one `FusedTrainStep.__call__`, from the benchmark's
own timer around the call: what the entry point costs the host per step."""


def read(bench):
    return bench.median_ms("dispatch_s")
