"""Share of the traced window in which no operation ran on the device:
1 - union of device-op intervals / window, averaged over the chips used.
The window runs from the first device operation to the last."""


def read(bench):
    trace = bench.trace_summary
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
