"""Model FLOP/s utilisation of the WHOLE traced window, idle included: the
operations the model needs for the traced steps (from its shapes, the
configuration's own function, whatever implements them) over window
seconds x chips x the published bf16 peak. Equal to (100 -
device_idle_pct) x busy_flops_pct.train / 100, and never over 100."""
from lib import peaks


def read(bench):
    trace, flops = bench.trace_summary, bench.outcome.get("traced_flops")
    if not trace or not flops:
        return None
    peak = peaks.peak(bench.device_kind, "bf16_flops")
    return 100.0 * flops / (trace["window_s"] * trace["chips"] * peak)
